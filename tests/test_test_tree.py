"""The test tree's own rules (tests/numerics.py and
tests/decoder_harness.py are the helpers' home)."""
import ast
import os
import pathlib
import signal
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import numerics

TESTS = pathlib.Path(__file__).parent


def _imported_modules(path):
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            yield from (alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            yield node.module


def test_no_test_file_imports_from_another_test_file():
    """What two test files share lives in a plain module: a helper
    imported from ``test_x.py`` runs that file's module-level code in
    the importer's worker, ties the two files' fixtures together, and
    is copied instead of mended when it is slow."""
    sideways = sorted(
        "%s imports %s" % (path.name, module)
        for path in TESTS.glob("test_*.py")
        for module in _imported_modules(path)
        if module.split(".")[0].startswith("test_"))
    assert not sideways, sideways


def _waits_without_bound(path):
    """The calls in ``path`` that can wait for ever: a child process run
    to its end with no ``timeout=``, and ``join`` / ``wait`` / ``acquire``
    with no argument at all (a ``str.join`` or ``os.path.join`` always has
    one), or ``get`` with none on a name the file binds to a queue (a
    counter's or a metric's ``get()`` waits for nothing)."""
    tree = ast.parse(path.read_text(), str(path))
    queues = {
        ast.unparse(target)
        for node in ast.walk(tree) if isinstance(node, ast.Assign)
        and isinstance(node.value, ast.Call)
        and ast.unparse(node.value.func).endswith("Queue")
        for target in node.targets}
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)):
            continue
        name, on = node.func.attr, ast.unparse(node.func.value)
        bare = not node.args and not node.keywords
        if (on == "subprocess" and name in (
                "run", "call", "check_call", "check_output")
                or name == "communicate"):
            unbounded = not any(k.arg in ("timeout", None)
                                for k in node.keywords)
        else:
            unbounded = bare and (name in ("join", "wait", "acquire")
                                  or name == "get" and on in queues)
        if unbounded:
            yield "%s:%d %s.%s" % (path.name, node.lineno, on, name)


def test_no_test_waits_without_a_bound():
    """A wait in a test names its own bound: the deadline of
    ``conftest.py`` is for the wait nobody foresaw, and costs its whole
    length. (``tests/mxbench_tests/`` is the benchmark's; its every
    child process has a timeout.)"""
    unbounded = [found for path in sorted(TESTS.glob("*.py"))
                 for found in _waits_without_bound(path)]
    assert not unbounded, unbounded


# ---------------------------------------------------------------------------
# the deadline of conftest.py, on planted wedges
# ---------------------------------------------------------------------------
_INNER_DEADLINE_S = 2.0
_INNER_CONFTEST = """
exec(compile(open(%r).read(), %r, "exec"))
DEADLINE_S, _UNWIND_S, _LAST_RESORT_S = %r, 1.0, 2.0
""" % (str(TESTS / "conftest.py"), str(TESTS / "conftest.py"),
       _INNER_DEADLINE_S)

_WEDGES = {
    # a lock nobody frees: a signal reaches the wait
    "lock": """
import threading

def wait_that_never_returns():
    held = threading.Lock()
    held.acquire(timeout=1)
    held.acquire(timeout=1000)
""",
    # a child that never exits, waited for: a signal reaches the wait,
    # and the child has to go with the test
    "child": """
import subprocess, sys

def wait_that_never_returns():
    child = subprocess.Popen([sys.executable, "-c",
                              "import time; time.sleep(1000)"])
    child.wait(timeout=1000)
""",
    # a native call that never comes back (a default pthread mutex
    # locked twice): no signal reaches it, the worker has to go
    "native": """
import ctypes

def wait_that_never_returns():
    lock = ctypes.CDLL(None).pthread_mutex_lock
    lock.argtypes, lock.restype = [ctypes.c_char_p], ctypes.c_int
    mutex = ctypes.create_string_buffer(64)
    lock(mutex)
    lock(mutex)
""",
}


def _alive_in_group(pgid):
    import psutil
    alive = []
    for p in psutil.process_iter():
        try:
            if (os.getpgid(p.pid) == pgid
                    and p.status() != psutil.STATUS_ZOMBIE):
                alive.append("%d %s" % (p.pid, " ".join(p.cmdline())))
        except (psutil.Error, ProcessLookupError):
            pass
    return alive


@pytest.mark.parametrize("kind", sorted(_WEDGES))
def test_a_wait_that_never_returns_costs_one_test(kind, tmp_path):
    """An inner run of the driver's kind (xdist, file-wise) over a file
    that is finished and a file whose first test never returns, under
    this tree's ``conftest.py`` with the deadline shortened: the wedged
    test fails and says where it stood, the test after it runs, nothing
    the run started outlives it, and the price is the deadline."""
    (tmp_path / "conftest.py").write_text(_INNER_CONFTEST)
    (tmp_path / "test_a_done.py").write_text("def test_done():\n    pass\n")
    (tmp_path / "test_b_wedged.py").write_text(_WEDGES[kind] + """

def test_wedged():
    wait_that_never_returns()

def test_after():
    pass
""")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(TESTS.parent))
    began = time.monotonic()
    run = subprocess.Popen(
        [sys.executable, "-m", "pytest", "-v", "-p", "no:cacheprovider",
         "-p", "xdist", "-n", "1", "--dist", "loadfile", "-p", "no:randomly",
         "test_a_done.py", "test_b_wedged.py"],
        cwd=tmp_path, env=env, start_new_session=True, text=True,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    try:
        said, _ = run.communicate(timeout=_INNER_DEADLINE_S + 60)
        took = time.monotonic() - began
        left = _alive_in_group(run.pid)
    finally:
        try:
            os.killpg(run.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    assert "1 failed, 2 passed" in said, said
    assert "FAILED test_b_wedged.py::test_wedged" in said, said
    assert "PASSED test_b_wedged.py::test_after" in said, said
    assert "wait_that_never_returns" in said, said      # the stack
    assert said.count("node down") == (kind == "native"), said
    assert not left, left
    assert took < _INNER_DEADLINE_S + 30, took


@pytest.mark.parametrize("shape, dtype, scale", [
    ((3, 37, 5), jnp.float32, 0.3), ((2, 700), jnp.bfloat16, 1.0)],
    ids=["float32", "bfloat16"])
def test_normal_draws_what_jax_random_normal_draws(shape, dtype, scale):
    """``numerics.normal`` takes the head of a longer row: the same
    values bit for bit only while entry ``i`` of a draw hangs on the key
    and ``i`` alone. A JAX whose default generator says otherwise would
    change every seeded input of the kernel and model tests without a
    word; it fails here first."""
    key = jax.random.key(7)
    want = (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    got = numerics.normal(key, shape, dtype, scale)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_jitted_traces_under_the_state_in_force():
    """``jax.jit`` of a function that outlives a change of the state it
    reads finds the program traced before it (JAX keeps traces by the
    function object and the operands' shapes); ``numerics.jitted`` and
    ``numerics.value_and_grads`` trace anew, so a monkeypatched block
    size or interpret mode is what the test runs."""
    state = {"k": 1.0}

    def scaled(x):
        return x * state["k"]

    x = jnp.arange(3.0)
    assert float(jax.jit(scaled)(x)[2]) == 2.0
    state["k"] = 5.0
    assert float(jax.jit(scaled)(x)[2]) == 2.0      # the trace made before
    assert float(numerics.jitted(scaled)(x)[2]) == 10.0
    value, grad = numerics.value_and_grads(scaled, x, cot=1.0)
    assert float(value[2]) == 10.0 and float(grad[2]) == 5.0
