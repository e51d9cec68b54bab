"""mesh.round_roofline: the mesh round program's share of its roofline on
one chip, in percent.

Each chip holds groups x voters / chips lanes. The least time of one fleet
round on a chip is those lanes' canonical carry bytes (`carry_bytes.json`,
the count round_roofline uses, read once and written once) over the chip's
HBM bandwidth (`peaks.json`). The share is that least time over the
measured device time of a round (mesh.round.device_ms)."""

import importlib.util
import os
import sys

import peaks

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(name: str):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), os.path.join(HERE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reduce(rec, trace):
    ms = _load("mesh.round.device_ms").reduce(rec, trace)
    if not ms:
        return None
    cfg = rec.values["config"]
    lanes = cfg["groups"] * cfg["voters"] // rec.values["chips"]
    per_lane = _load("round_roofline").carry_bytes_per_lane(cfg)
    least_s = 2 * lanes * per_lane / peaks.peak(
        rec.values["device_kind"], "hbm_bytes_per_s")
    print(f"mesh.round_roofline: bound by bytes, {least_s * 1e3:.3f} ms "
          f"least a chip against {ms:.3f} ms measured", file=sys.stderr)
    return 100.0 * least_s / (ms / 1e3)
