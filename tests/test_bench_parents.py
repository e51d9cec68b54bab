"""One process per chip: an A/B bench parent must not initialise a JAX
backend before it starts its children, or on a TPU the parent would hold
the chip and every child would fail or hang waiting for it."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PARENTS = [
    "diet_ab", "dispatch_ab", "egress_ab", "fabric_ab", "lease_ab",
    "multichip_ab", "paged_ab", "pallas_ab", "tier_ab", "trace_ab",
]

# runs the parent's main() up to its first child, which is stubbed out
_PROBE = """
import subprocess, sys
sys.path.insert(0, {repo!r})
from jax._src import xla_bridge as xb
import benches.{mod} as mod

class FirstChild(BaseException):
    pass

def first_child(*a, **k):
    raise FirstChild

subprocess.run = subprocess.Popen = first_child
sys.argv = [{mod!r}, "--smoke"]
try:
    mod.main()
except FirstChild:
    pass
print("backend" if xb._backends else "clean")
"""


@pytest.mark.parametrize("mod", PARENTS)
def test_bench_parent_leaves_the_chip_to_its_children(mod):
    out = subprocess.run(
        [sys.executable, "-c", _PROBE.format(repo=REPO, mod=mod)],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split()[-1] == "clean"
