"""The test tree's own rules (tests/numerics.py and
tests/decoder_harness.py are the helpers' home)."""
import ast
import pathlib

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
