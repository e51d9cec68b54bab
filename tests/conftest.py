"""Test config: run everything on a virtual 8-device CPU mesh so sharding
tests exercise real collectives without TPU hardware (chip_smoke.py runs
the same code on the real chip).

Pallas kernels run in interpret mode here, chosen explicitly through
RAFT_TPU_PALLAS_INTERPRET (the engine never infers it from the backend).
Backends are still uninitialized at conftest time, so XLA_FLAGS and the
config updates take."""

import ctypes
import ctypes.util
import gc
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("RAFT_TPU_PALLAS_INTERPRET", "1")

import jax
import pytest

jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: the step kernel takes ~45s to compile on the
# virtual CPU backend; cache it across pytest runs. JAX_COMPILATION_CACHE_DIR,
# when set, is jax's own setting and stands.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update(
        "jax_compilation_cache_dir",
        os.path.join(os.path.dirname(__file__), ".jax_cache"),
    )
jax.config.update("jax_persistent_cache_min_compile_time_secs", 2.0)


# glibc keeps freed heap mapped; malloc_trim returns it (absent elsewhere)
_MALLOC_TRIM = getattr(ctypes.CDLL(ctypes.util.find_library("c")), "malloc_trim", None)


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_programs():
    """Free every compiled program when a module ends, and hand the freed
    heap back to the OS. An xdist worker runs many modules; without this
    its resident set grew past 13 GB (a module alone peaks at 1.5-2.5 GB,
    most of it freed but kept by malloc), six workers outgrew the machine,
    and the OOM killer took one, losing every file queued behind it."""
    yield
    jax.clear_caches()
    gc.collect()
    if _MALLOC_TRIM is not None:
        _MALLOC_TRIM(0)


@pytest.fixture
def no_persistent_cache():
    """Keep this module's programs out of the persistent compile cache:
    XLA's CPU executable serializer aborts the worker on the largest
    shard_map programs (fatal abort inside put_executable_and_time), and
    compiles for a described TPU can never be read back. Flipping the
    enable flag plus reset_cache() takes effect even after an earlier
    module initialized the cache (re-pointing the directory does not)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def pytest_addoption(parser, pluginmanager):
    """Keep the pytest.ini xdist defaults (-n 6 --dist loadfile
    --max-worker-restart 4) parseable when the xdist plugin is disabled
    (`-p no:xdist`, e.g. the ROADMAP tier-1 verify command): register
    inert stand-ins for the options xdist would own, so the values
    parse and are ignored and the run proceeds in-process (the
    modifyitems warning below still flags full-suite single-process
    runs). The group's private _addoption is the only way to claim a
    lowercase short option (-n) from a conftest — same mechanism xdist
    itself uses."""
    if pluginmanager.hasplugin("xdist"):
        return
    group = parser.getgroup("xdist-standin")
    group._addoption(
        "-n", "--numprocesses", dest="numprocesses", default=None
    )
    group._addoption("--dist", dest="dist", default="no")
    group._addoption(
        "--max-worker-restart", dest="maxworkerrestart", default=None
    )


# The longest files, each 5-8 minutes on one xdist worker, in that order.
# Alphabetical order would start them last and leave them to run alone at
# the tail of the sweep.
_LONG_FILES = (
    "test_sharded.py", "test_lockstep_more.py", "test_mesh_twins.py",
    "test_mesh.py",
)


def pytest_collection_modifyitems(config, items):
    """Run the longest files first (loadfile hands files out in collection
    order; the sort is stable, so every file keeps its own test order).

    Warn when the FULL suite is collected into one process: XLA:CPU
    reproducibly aborts once a few hundred distinct programs have been
    compiled in a single process (see runtests.sh), so the suite must be
    spread over pytest-xdist workers. `./runtests.sh` does this correctly."""
    rank = {name: i for i, name in enumerate(_LONG_FILES)}
    items.sort(key=lambda item: rank.get(item.path.name, len(rank)))
    # The xdist controller never collects items, so this hook only runs in
    # workers (PYTEST_XDIST_WORKER/_COUNT set) or in a plain in-process run.
    # Require enough workers that no single process crosses the
    # compile-count threshold (runtests.sh uses 6; below 4 a worker's share
    # of a full-suite run is still risky). Warn from gw0 only to avoid one
    # warning per worker.
    worker = os.environ.get("PYTEST_XDIST_WORKER")
    # numprocesses may still be 'auto'/'logical' if read before xdist
    # resolves it (plugin-ordering dependent) — treat non-int as unknown.
    _np = getattr(config.option, "numprocesses", None)
    nworkers = int(os.environ.get("PYTEST_XDIST_WORKER_COUNT") or 0) or (
        _np if isinstance(_np, int) else 0
    )
    safe = nworkers >= 4
    if worker not in (None, "gw0"):
        return
    if len({i.path for i in items}) > 30 and not safe:
        import warnings

        warnings.warn(
            "Running the full suite in ONE process will hit a known "
            "XLA:CPU compile-count crash partway through. Use "
            "./runtests.sh (pytest-xdist, one file per worker) instead.",
            stacklevel=1,
        )
