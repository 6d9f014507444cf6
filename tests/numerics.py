"""What the op, kernel and model tests compare with, in one place (a plain
module, not collected: ``tests/`` is on the path, so ``from numerics
import ...``): seeded inputs, the two tolerances, the plain float32
attention references, the benchmark's reference modules, and
``value_and_grads``, which traces a function and its pullback once and
runs them as one compiled program.

A value / gradient comparison in a test goes through ``value_and_grads``
(or ``jax.jit(jax.grad(...))`` where a scalar loss is the point), never
through bare ``jax.grad`` / ``jax.vjp``: run eagerly, every primitive of
the op, of its reference and of both backward passes is an XLA program
of its own, and an interpreted Pallas kernel is thousands of them (the
same comparison 3 to 8 times as long: CHANGES.md, PR 46).

What a test monkeypatches has to be in force when the function is
traced, and building the ``jax.jit`` inside the test is NOT enough for
that: JAX keeps a function's trace by the function object and the
operands' shapes, so ``jax.jit(R.sum_slots)`` (or its ``.lower``, or
``jax.make_jaxpr`` of it) after a patch runs the program traced before
it. A function that outlives the test (a module's, an op's ``impl``, a
reference's) is therefore compiled through ``jitted``, which wraps it in
a function made on the spot, as ``value_and_grads`` does; ``jax.jit``
itself is for what the test defines in its own body.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxbench import manifest

F32, BF = jnp.float32, jnp.bfloat16


@functools.lru_cache(maxsize=None)
def reference(name):
    """The benchmark's plain reference of a configuration
    (``mxbench/reference/<name>.py``), loaded once a process."""
    return manifest.load_module("reference", name + ".py")


@pytest.fixture
def highest():
    """Float32 products at full precision while a test runs and traces
    (import it and ask for it with ``usefixtures``)."""
    with jax.default_matmul_precision("highest"):
        yield


@functools.partial(jax.jit, static_argnums=1)
def _normal_row(key, n):
    return jax.random.normal(key, (n,), F32)


def normal(key, shape, dtype=F32, scale=1.0):
    """``(scale * jax.random.normal(key, shape, float32)).astype(dtype)``
    value for value, without a compile a shape (0.5 s each on this CPU):
    entry ``i`` of a draw hangs on the key and ``i`` alone, so a shape is
    the head of a row whose length is a power of two, one program a
    length."""
    size = int(np.prod(shape))
    row = np.asarray(_normal_row(key, max(1024, 1 << (size - 1).bit_length())))
    return jnp.asarray((np.float32(scale) * row[:size]).reshape(shape)
                       .astype(dtype))


def rand(seed, *shapes, scale=1.0, dtype=F32):
    """One normal array a shape, drawn in float32 from ``seed``."""
    keys = jax.random.split(jax.random.key(seed), len(shapes))
    return [normal(k, s, dtype, scale) for k, s in zip(keys, shapes)]


def qkv(seed, length, heads, kv, d=128, batch=1, dtype=BF):
    """Queries, keys, values and a cotangent of the context."""
    return rand(seed, (batch, length, heads, d), (batch, length, kv, d),
                (batch, length, kv, d), (batch, length, heads, d), dtype=dtype)


def swiglu_experts(seed, hidden=12, routed=16, held=4, width=10, offset=4):
    """(weights, configuration) of a toy softmax / SwiGLU expert layer:
    ``held`` of ``routed`` experts from ``offset`` on, three a token."""
    r, gate_up, down = rand(seed, (routed, hidden),
                            (held, 2 * width, hidden), (held, hidden, width))
    return {"router_weight": r, "experts_gate_up_weight": gate_up,
            "experts_down_weight": down}, {
                "num_experts_per_tok": 3, "norm_topk_prob": True,
                "expert_offset": offset}


def close(got, want, tol=2e-5):
    """Leaf for leaf within ``tol``, relative and absolute."""
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want), strict=True):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=tol,
                                   atol=tol)


def near(got, want, rel):
    """Every leaf finite and within ``rel`` of the wanted leaf's largest
    entry (a bf16 path against float32 numbers, or two bf16 roundings of
    one sum taken in different orders)."""
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want), strict=True):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert g.shape == w.shape
        assert np.all(np.isfinite(g))
        assert np.abs(g - w).max(initial=0.0) <= rel * np.abs(w).max(
            initial=0.0)


def jitted(fn):
    """``jax.jit`` of ``fn`` behind a wrapper made here, so the trace is
    this call's own: nothing traced earlier under other module state
    (a patched block size, another interpret mode) is found again."""
    return jax.jit(lambda *args, **kwargs: fn(*args, **kwargs))


def value_and_grads(fn, *inputs, cot):
    """``[*fn(*inputs), *gradients]`` in float32: the outputs' leaves,
    then the pullback of ``cot`` (a leaf an output, cast and broadcast
    to it) to every input. One trace of ``fn``, one compiled program."""
    @jax.jit
    def run(inputs, cot):
        out, pull = jax.vjp(fn, *inputs)
        cot = jax.tree_util.tree_map(
            lambda c, o: jnp.broadcast_to(jnp.asarray(c, o.dtype), o.shape),
            cot, out)
        return out, pull(cot)

    return [jnp.asarray(t, F32)
            for t in jax.tree_util.tree_leaves(run(inputs, cot))]


def same_values_and_grads(fn, ref, args, tol=2e-5):
    """``fn`` and ``ref`` at ``args``: the value and, under one seeded
    cotangent, the gradient to every argument, ``close`` to ``tol``."""
    (cot,) = rand(99, jax.eval_shape(ref, *args).shape)
    close(value_and_grads(fn, *args, cot=cot),
          value_and_grads(ref, *args, cot=cot), tol)


def remat_count(fn, *args):
    """How often ``fn``'s trace says checkpoint or remat."""
    text = str(jax.make_jaxpr(fn)(*args))
    return text.count("checkpoint") + text.count("remat")


# ---------------------------------------------------------------------------
# plain float32 attention: whole masks, no block, no slice
# ---------------------------------------------------------------------------
def _masked_attention(q, k, v, seen):
    heads, kv = q.shape[2], k.shape[2]
    k, v = (jnp.repeat(t, heads // kv, axis=2) for t in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(q.shape[-1]))
    att = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", att, v)


def attention_ref(q, k, v):
    """Causal grouped-query attention."""
    return _masked_attention(
        q, k, v, jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool)))


def window_ref(q, k, v, window):
    """Causal attention over the last ``window`` keys, by index
    arithmetic."""
    t = jnp.arange(q.shape[1])[:, None]
    u = jnp.arange(k.shape[1])[None, :]
    return _masked_attention(q, k, v, (u <= t) & (t - u < window))


# ---------------------------------------------------------------------------
# programs compiled for a described chip (tests/test_chip_compile_*.py)
# ---------------------------------------------------------------------------
def described(sharding, *shapes):
    """Abstract operands placed by ``sharding``: a shape (bf16) or a
    ``(shape, dtype)`` each."""
    shapes = [s if s and isinstance(s[0], tuple) else (s, BF) for s in shapes]
    return [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]


def mosaic_calls(text):
    """The lines of a compiled program's text that call a Mosaic
    kernel."""
    return [line for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def sum32(x):
    return jnp.sum(x.astype(F32))
