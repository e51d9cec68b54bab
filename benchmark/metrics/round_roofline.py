"""round_roofline: the round program's share of its roofline, in percent.

The least time of one fleet round is the canonical carry's bytes
(`carry_bytes.json`: the slim, unpacked state and fabric at the
configuration's sizes, read once and written once) over the chip's HBM
bandwidth (`peaks.json`, by device kind). The round counts no arithmetic
that could bound it instead, so the bound is the bytes. The share is that
least time over the measured device time of a round (round.device_ms)."""

import importlib.util
import json
import math
import os
import sys

import peaks

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def carry_bytes_per_lane(config: dict) -> int:
    with open(os.path.join(BENCH, "carry_bytes.json")) as f:
        cols = json.load(f)["columns"]
    dims = {
        "V": config["voters"], "W": config["log_window"],
        "E": config["max_msg_entries"], "F": config["max_inflight"],
        "R": config["max_read_index"],
    }
    return sum(c["bytes"] * math.prod(dims[d] for d in c["per_lane"])
               for c in cols)


def _device_ms(rec, trace):
    spec = importlib.util.spec_from_file_location(
        "round_device_ms", os.path.join(HERE, "round.device_ms.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.reduce(rec, trace)


def reduce(rec, trace):
    ms = _device_ms(rec, trace)
    if not ms:
        return None
    cfg = rec.values["config"]
    lanes = cfg["groups"] * cfg["voters"]
    least_s = 2 * lanes * carry_bytes_per_lane(cfg) / peaks.peak(
        rec.values["device_kind"], "hbm_bytes_per_s")
    share = 100.0 * least_s / (ms / 1e3)
    print(f"round_roofline: bound by bytes, {least_s * 1e3:.3f} ms least "
          f"against {ms:.3f} ms measured", file=sys.stderr)
    return share
