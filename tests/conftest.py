"""Test harness config (SURVEY.md §4 pattern 4): run the whole suite on
an 8-virtual-device CPU platform so sharding/multi-device paths are
exercised without TPU hardware. MXNET_TEST_ON_TPU=1 leaves the platform
alone (the reference's gpu-suite pattern); no PR has shown the suite
passing on the chip that way — chip_smoke.py is the on-chip check.
"""
import faulthandler
import os
import signal
import sys
import threading
import time
import traceback

_ON_TPU = bool(os.environ.get("MXNET_TEST_ON_TPU"))
if not _ON_TPU:
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

if not _ON_TPU:
    jax.config.update("jax_platforms", "cpu")

# exact-precision matmuls for numeric ground-truth checks (the framework
# default stays backend-fast: bf16 passes on the MXU, checked with loose
# tolerances in the TPU-suite run)
jax.config.update("jax_default_matmul_precision", "highest")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _seed_all(request):
    """Seed np + mx per test and log the seed on failure (ref:
    tests/python/unittest/common.py :: with_seed)."""
    seed = np.random.randint(0, 2**31)
    override = request.node.get_closest_marker("seed")
    if override is not None:
        seed = override.args[0]
    np.random.seed(seed)
    import mxnet_tpu as mx
    mx.random.seed(seed)
    yield
    # pytest reports only on failure via -ra; print for reproducibility
    request.node.user_properties.append(("seed", seed))


# ---------------------------------------------------------------------------
# one deadline for every test: a wait that never returns costs the test
# it is in, not the run
#
# Stage 1, where a signal reaches (the main thread in Python, or in a
# lock, queue, socket, sleep or ``Popen.wait``): an interval timer over
# setup, call and teardown whose handler fails the test with every
# thread's stack and kills the children the test left behind; fixtures
# unwind and the worker goes on with its file. Stage 2, where none does
# (the main thread in a native call: a collective short of a peer, a
# compile): ``faulthandler`` writes the stacks to the worker's stderr and
# ends the process; xdist reports the test as failed ("node down"),
# starts another worker and gives it the rest of the file.
# ---------------------------------------------------------------------------
DEADLINE_S = 300.0        # a quiet run's slowest test is ~60 s; a test
                          # marked ``slow`` (minutes by design) gets four
_UNWIND_S = 10.0          # what teardown is left with after a deadline
_LAST_RESORT_S = 20.0     # after the deadline, before the worker is ended
_test = {"ends": 0.0, "began": 0.0, "stderr": None}


def _kill_children_since(began):
    """Kill and reap this process's descendants born after ``began``
    (epoch seconds); returns what was killed, as text."""
    import psutil
    young = [c for c in psutil.Process().children(recursive=True)
             if c.create_time() >= began - 1.0]
    said = []
    for c in young:
        try:
            said.append("%d %s" % (c.pid, " ".join(c.cmdline())[:120]))
            c.kill()
        except psutil.Error:
            pass
    psutil.wait_procs(young, timeout=5)
    return said


def _on_deadline(signum, frame):
    main = threading.main_thread().ident
    names = {t.ident: t.name for t in threading.enumerate()}
    others = "".join(
        "\n--- thread %s ---\n%s" % (
            names.get(ident, ident),
            "".join(traceback.format_stack(f)[-12:]))
        for ident, f in sys._current_frames().items() if ident != main)
    killed = _kill_children_since(_test["began"])
    pytest.fail("the test's deadline passed (DEADLINE_S = %g s in "
                "tests/conftest.py); children killed: %s; the other "
                "threads:%s" % (DEADLINE_S, killed or "none", others))


@pytest.hookimpl(optionalhook=True)
def pytest_xdist_make_scheduler(config, log):
    """``--dist loadfile`` as xdist 3.8 has it, with a dead worker's
    leavings mended. xdist puts back all the worker was ever given: the
    files it had finished and the test it died in too. A replacement
    handed a finished file, or a file's one last test, reports nothing
    (a worker starts a test only once it knows the next, or that there is
    none) and is never asked again, so the run never ends; one handed the
    fatal test dies of it."""
    if config.getvalue("dist") != "loadfile":
        return None
    from xdist.scheduler import LoadFileScheduling

    class Mended(LoadFileScheduling):
        def remove_node(self, node):
            crashitem = super().remove_node(node)
            for scope, unit in list(self.workqueue.items()):
                if crashitem in unit:
                    unit[crashitem] = True
                if all(unit.values()):
                    del self.workqueue[scope]
            return crashitem

        def _reschedule(self, node):
            super()._reschedule(node)
            while (not node.shutting_down
                   and self._pending_of(self.assigned_work[node]) < 2):
                if self.workqueue:
                    self._assign_work_unit(node)
                else:
                    node.shutdown()

    return Mended(config, log)


@pytest.hookimpl(wrapper=True, tryfirst=True)
def pytest_runtest_protocol(item, nextitem):
    allowed = DEADLINE_S * (4 if item.get_closest_marker("slow") else 1)
    _test["began"] = time.time()
    _test["ends"] = time.monotonic() + allowed
    faulthandler.dump_traceback_later(allowed + _LAST_RESORT_S,
                                      exit=True, file=_test["stderr"])
    try:
        return (yield)
    finally:
        faulthandler.cancel_dump_traceback_later()


@pytest.hookimpl(wrapper=True, tryfirst=True)
def pytest_runtest_setup(item):
    """Each phase runs with what is left of the test's deadline on the
    interval timer (teardown always gets ``_UNWIND_S``)."""
    left = max(_test["ends"] - time.monotonic(), _UNWIND_S)
    signal.setitimer(signal.ITIMER_REAL, left)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)


pytest_runtest_call = pytest_runtest_teardown = pytest_runtest_setup


def pytest_configure(config):
    # capture is suspended here, so fd 2 is the run's own stderr
    _test["stderr"] = os.fdopen(os.dup(2), "w")
    if threading.current_thread() is threading.main_thread():
        signal.signal(signal.SIGALRM, _on_deadline)
    config.addinivalue_line("markers", "seed(n): pin the RNG seed")
    config.addinivalue_line("markers", "slow: long-running test")
    config.addinivalue_line(
        "markers", "fault: fault-injection / chaos-recovery test "
        "(tests/test_fault_tolerance.py, tools/chaos_run.py)")
    config.addinivalue_line(
        "markers", "guard: training-guardrail test (gradient defense, "
        "engine error propagation, comms watchdogs — "
        "tests/test_guardrails.py; tier-1, NOT slow)")
    config.addinivalue_line(
        "markers", "obs: observability / telemetry test (metrics "
        "registry, span tracing, heartbeat — tests/test_telemetry.py; "
        "tier-1, NOT slow)")
    config.addinivalue_line(
        "markers", "zero: ZeRO weight-update sharding test "
        "(MXNET_ZERO parity/guard/checkpoint/memory — "
        "tests/test_zero.py; tier-1, NOT slow)")
    config.addinivalue_line(
        "markers", "staticcheck: mxlint static-analysis test (AST "
        "linter, graph checker, engine race detector, self-lint gate "
        "— tests/test_staticcheck.py; tier-1, NOT slow)")
    config.addinivalue_line(
        "markers", "serve: inference-engine test (shape-bucketed "
        "serving, continuous batching, tenancy/SLO — "
        "tests/test_serve.py; tier-1, NOT slow)")
    config.addinivalue_line(
        "markers", "quant: quantized-collectives test (int8/fp8 wire, "
        "error feedback, MXNET_KVSTORE_QUANTIZE — "
        "tests/test_quantize.py; tier-1, NOT slow)")
    config.addinivalue_line(
        "markers", "elastic: elastic-topology test (checkpoint "
        "resharding, live shrink/grow, MXNET_ELASTIC — "
        "tests/test_reshard.py; tier-1, NOT slow)")


# ---------------------------------------------------------------------------
# multiprocess-collective capability probe (ISSUE 17 satellite)
#
# The tests/test_dist.py multiprocess tests need REAL cross-process XLA
# collectives, which some jaxlib builds refuse on the CPU backend
# ("Multiprocess computations aren't implemented on the CPU backend").
# Instead of hardcoding a version check, probe the actual capability
# once per session: two spawned processes rendezvous through
# jax.distributed and run one allgather. test_dist.py marks the
# affected tests with pytest.mark.skipif on this probe (a lazily
# evaluated string condition, so tier-1 runs that deselect those tests
# never pay the probe's ~10s).
# ---------------------------------------------------------------------------
_MP_PROBE_RESULT = [None]

_MP_PROBE_SRC = """
import sys
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(sys.argv[1], num_processes=2,
                           process_id=int(sys.argv[2]))
import jax.numpy as jnp
from jax.experimental import multihost_utils
out = multihost_utils.process_allgather(jnp.ones((1,)))
assert out.size == 2, out
print("MP_PROBE_OK")
"""


def multiprocess_collectives_supported() -> bool:
    """True when this jax backend can run cross-process collectives on
    this host (memoized; one ~5s two-process probe per session)."""
    if _MP_PROBE_RESULT[0] is None:
        _MP_PROBE_RESULT[0] = _run_mp_probe()
    return _MP_PROBE_RESULT[0]


def _run_mp_probe() -> bool:
    # one process per chip: this parent has touched JAX, so the children
    # must never need the chip — _MP_PROBE_SRC pins them to the CPU
    import socket
    import subprocess
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coord = "127.0.0.1:%d" % s.getsockname()[1]
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)           # no virtual-device carryover
    procs = [subprocess.Popen(
        [sys.executable, "-c", _MP_PROBE_SRC, coord, str(i)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        for i in range(2)]
    ok = True
    for p in procs:
        try:
            out, _ = p.communicate(timeout=60)
        except subprocess.TimeoutExpired:
            p.kill()
            out = b""
        ok = ok and p.returncode == 0 and b"MP_PROBE_OK" in out
    return ok


@pytest.fixture()
def pallas_interpret(monkeypatch):
    """Pin Pallas kernels to interpreter mode for this test (exact
    CPU-mesh numerics wherever the suite runs)."""
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    yield


# ---------------------------------------------------------------------------
# programs compiled for a described chip: tests/test_chip_compile_*.py,
# the only files that load the TPU library
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def one_chip():
    """The sharding of one device of a described v5e:2x2 (no chip is
    attached: what is compiled for it never runs)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    # a compile for a described device is written to the persistent
    # cache but cannot be read back without the chip: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_mode(monkeypatch):
    """Kernels built for the chip, not the interpreter — steered from
    the test: the code under test asks ``interpret_mode()`` and, with
    only CPU devices attached, would answer True."""
    from mxnet_tpu.ops import pallas_common
    monkeypatch.setattr(pallas_common, "interpret_mode", lambda: False)


@pytest.fixture(scope="module")
def compiled():
    """``compiled(key, build)``: what ``build()`` lowers and compiles,
    once a ``key`` in a file. The key names everything that defines the
    program (the function, its sizes and attributes, the fixtures that
    steer its trace), so two tests that read one program pay for one
    compile (Mosaic's is single-threaded: 20 to 30 s a mixer)."""
    memo = {}

    def get(key, build):
        if key not in memo:
            memo[key] = build()
        return memo[key]

    return get
