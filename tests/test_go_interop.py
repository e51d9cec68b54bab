"""Go interop layer: build + run the C-ABI end-to-end test
(native/test_multiraft_xla.cc) — the compile-and-run gate for the
`multiraft_xla` export surface that go/multiraft_xla.go binds
(reference parity target: the public RawNode API, rawnode.go:34-559)."""

import os
import shutil
import subprocess
import sys

import pytest

NATIVE = os.path.join(os.path.dirname(__file__), "..", "raft_tpu", "native")


@pytest.mark.skipif(
    shutil.which("g++") is None or shutil.which("python3-config") is None,
    reason="native toolchain unavailable",
)
def test_c_abi_end_to_end():
    r = subprocess.run(
        ["make", "-s", "libmultiraft_xla.so", "test_multiraft_xla"],
        cwd=NATIVE, capture_output=True, text=True,
    )
    assert r.returncode == 0, r.stderr

    env = dict(os.environ)
    site = next(p for p in sys.path if p.endswith("site-packages"))
    repo = os.path.abspath(os.path.join(NATIVE, "..", ".."))
    env["PYTHONPATH"] = f"{repo}:{site}"
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [os.path.join(NATIVE, "test_multiraft_xla")],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert "codec round-trip: OK" in r.stdout
    assert "engine e2e via C ABI: OK" in r.stdout


def test_go_wrapper_source_exists():
    """The Go-side binding (built with -tags multiraft_xla; no Go toolchain
    in this image, so presence + header coherence is the check here — the
    C half is compile- and run-tested above)."""
    go = os.path.join(os.path.dirname(__file__), "..", "go", "multiraft_xla.go")
    with open(go) as f:
        src = f.read()
    assert "//go:build multiraft_xla" in src.splitlines()[0]
    for sym in (
        "mrx_init", "mrx_engine_new", "mrx_step_wire", "mrx_ready",
        "mrx_advance", "mrx_propose", "mrx_campaign", "mrx_tick",
        "mrx_has_ready", "mrx_status_json",
    ):
        assert sym in src, f"Go wrapper missing {sym}"
    hdr = os.path.join(NATIVE, "multiraft_xla.h")
    with open(hdr) as f:
        hsrc = f.read()
    for sym in ("mrx_init", "mrx_engine_new", "mrx_step_wire", "mrx_ready"):
        assert sym in hsrc
