"""Test env: force CPU PJRT with 8 virtual devices BEFORE jax initializes.

Mirrors the reference's fake-device strategy (fake_cpu_device.h /
test/custom_runtime/): all tests — including multi-chip sharding tests — run
on a virtual 8-device CPU mesh so CI needs no accelerator.
"""

import os
import re
import shutil
import signal
import tempfile
import threading

# FORCE cpu: the tests are written for the CPU backend — 8 virtual devices,
# Pallas kernels interpreted, bitwise/tolerance chains stated for XLA CPU —
# and must run the same on a host that has a chip. The env var covers child
# processes; config.update after import covers this one.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# ONE compile cache for the run unless the caller placed one: the serving
# tests build hundreds of engines whose programs are `jax.jit` of fresh
# closures over the same HLO, and without a persistent cache XLA compiles
# each of them again. The process that is no xdist worker makes a fresh
# directory HERE — the controller imports this file before it spawns its
# workers, so they and the gangs they launch inherit the path — and removes
# it in `pytest_unconfigure`. Never the checkout's `.jax_cache`: a run
# reads nothing an earlier tree wrote and leaves nothing behind. Through
# `flags.py`'s rule 2 (PADDLE2_TPU_CACHE_DIR), so a test that sets
# `FLAGS_compilation_cache_dir` itself still gets its own.
_RUN_CACHE = None      # the directory THIS process made, and removes
# what is worth writing: everything. Most of what a warm cache saves an
# engine test is the hundreds of small programs around its engine (eager
# ops, scatters), a few KB each: one test went 57 s -> 38 s warm at 0 and
# 75 s -> 57 s at 0.5 (CHANGES.md, PR 44)
CACHE_MIN_COMPILE_S = "0"
if "PYTEST_XDIST_WORKER" not in os.environ \
        and "JAX_COMPILATION_CACHE_DIR" not in os.environ \
        and "PADDLE2_TPU_CACHE_DIR" not in os.environ:
    _RUN_CACHE = os.environ["PADDLE2_TPU_CACHE_DIR"] = tempfile.mkdtemp(
        prefix="p2t_test_cache_")
    os.environ.setdefault("PADDLE2_TPU_CACHE_MIN_COMPILE_S",
                          CACHE_MIN_COMPILE_S)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# A test's own limit, seconds: at least three times the slowest test of
# the tier-1 table (CHANGES.md, PR 44), so that a hang costs one test and
# not the rest of its worker's run.
TEST_LIMIT_S = 360.0


# xdist's `--dist loadfile` hands files out by their NUMBER of tests,
# largest first, two files deep a worker: the engine files, a few tests of
# a minute each, came last and all at once (a tail of 110 s on 1,060).
# The heavy files go out first, by number of tests, then the others as
# xdist would order them. Heavy: a file that imports `served` builds
# engines; and these, over 45 s in the tier-1 table (CHANGES.md, PR 44)
# without it. A heavy file not known here still runs; it may run late.
HEAVY_WITHOUT_ENGINES = frozenset("test_" + name + ".py" for name in """
    chip_compile elastic ernie moe nn nn_parity_r5 pallas_flash
    pipeline_parallel quantization sequence_parallel ssd_state_step_blocks
    surface_r5 surface_r5b""".split())


def pytest_configure(config):
    """The order below, not xdist's (`--no-loadscope-reorder`, a public
    option whose `dest` this is: the driver's command cannot carry it)."""
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False
    elif config.pluginmanager.hasplugin("xdist"):
        config.issue_config_time_warning(pytest.PytestConfigWarning(
            "xdist has no `loadscopereorder` option any more: the heavy "
            "files are not handed out first (tests/conftest.py)"), 2)


def _is_heavy(path):
    if os.path.basename(path) in HEAVY_WITHOUT_ENGINES:
        return True
    with open(path) as f:
        return re.search(r"^(from|import) served\b", f.read(), re.M) \
            is not None


@pytest.hookimpl(trylast=True)
def pytest_collection_modifyitems(items):
    """Whole files, the heavy ones first, each group by number of tests."""
    files = {}
    for item in items:
        files.setdefault(str(item.path), []).append(item)
    order = sorted(files, key=lambda f: (not _is_heavy(f), -len(files[f])))
    items[:] = [item for f in order for item in files[f]]


@pytest.hookimpl(optionalhook=True)
def pytest_configure_node(node):
    """(xdist controller) tell each worker which cache the run uses."""
    node.workerinput["p2t_run_cache"] = os.environ.get(
        "PADDLE2_TPU_CACHE_DIR", "")


def pytest_unconfigure(config):
    """The last hook of the process that made the run's cache, after the
    session where there was one (``--markers`` and ``--help`` have none)."""
    if _RUN_CACHE is not None:
        shutil.rmtree(_RUN_CACHE, ignore_errors=True)


@pytest.fixture(scope="session", autouse=True)
def _run_cache(request):
    """A worker compiles into the controller's directory, not its own."""
    sent = getattr(request.config, "workerinput", {}).get("p2t_run_cache")
    if sent is not None and "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        from paddle2_tpu.flags import compile_cache_dir
        assert os.environ.get("PADDLE2_TPU_CACHE_DIR", "") == sent \
            == compile_cache_dir()
    yield


_LIMITED = [""]        # what the running timer bounds, for the message


def _expired(signum, frame):
    pytest.fail(f"{_LIMITED[0]} passed the per-test limit of "
                f"{TEST_LIMIT_S:g} s (tests/conftest.py)", pytrace=False)


@pytest.fixture(scope="module", autouse=True)
def _module_limit(request):
    """The same limit over the set-up of the module fixtures that a
    file's FIRST test asks for (the span files' traced serves): armed
    here, before them, and taken over by that test's ``_test_limit``.
    The handler and the timer found are put back when the module ends."""
    if threading.current_thread() is not threading.main_thread():
        yield
        return
    handler = signal.signal(signal.SIGALRM, _expired)
    _LIMITED[0] = f"the set-up of {request.node.nodeid}'s fixtures"
    timer = signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, *timer)
        signal.signal(signal.SIGALRM, handler)


@pytest.fixture(autouse=True)
def _test_limit(request):
    """Fail a test that passes ``TEST_LIMIT_S`` (SIGALRM, which nothing
    else here uses), its function-scoped fixtures with it. What this
    cannot stop: a module or session fixture first set up for a LATER
    test of its file (pytest sets those up before this one); and a hang
    inside one blocking C call — an XLA compile, a lock — since Python
    runs a signal's handler only between two bytecodes: the test fails
    when the call returns, if it does. No timer runs between tests, where
    a worker may wait for the controller."""
    if threading.current_thread() is not threading.main_thread():
        yield
        return
    _LIMITED[0] = request.node.nodeid
    signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)


@pytest.fixture(scope="module", autouse=True)
def _no_mesh_left_by_another_file():
    """A file starts with no global mesh, as if it were the first of its
    worker: one that an earlier file left installed shards layers and
    wraps the flash kernel in ``shard_map`` (seen in ``test_moe.py`` at
    PR 36 and, once the order of files changed, in ``test_deepseek.py``
    at PR 44: a jaxpr's transposes were counted per shard)."""
    from paddle2_tpu.distributed import mesh
    mesh.set_mesh(None)
    yield


@pytest.fixture(autouse=True)
def _seed_all():
    np.random.seed(0)
    import paddle2_tpu as paddle
    paddle.seed(0)
    yield
