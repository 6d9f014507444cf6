"""Chipless compiles, the BERT path: every Pallas kernel of it, at
BERT-base widths, through the TPU compiler for a DESCRIBED v5e chip (no
chip is attached here; nothing runs), alone, a shard on a split batch,
and in the whole ``dp4`` step (the attention kernel alone, a length a
case: ``test_chip_compile_flash.py``). Interpret mode — what every other
kernel test uses — cannot see what Mosaic refuses: block shapes off the
(8, 128) tiling, unsupported shape casts, primitives with no TPU lowering.

The ``test_chip_compile_*.py`` files are the only ones that load the TPU
library: the topology is described inside a module-scoped fixture
(``one_chip`` in conftest.py, never at import), and every compile happens
in the test's own process, once a program (``compiled`` in conftest.py).
They are cut by what they compile into files of a dozen tests or fewer:
xdist gives a file to one worker and hands files out by their count of
tests, the largest first, so a long file of few tests starts late and the
run waits for it alone.
A compile that passes is not a chip run; ``chip_smoke.py`` is.
"""
import re

import pytest

import jax
import jax.numpy as jnp

from numerics import BF, described, jitted, mosaic_calls, sum32

# BERT-base: seq 128, batch 32, 12 heads x 64, hidden 768, FFN 3072
L, N, H, D, C = 128, 32, 12, 64, 768


def _custom_calls(one_chip, fn, *shapes):
    return len(mosaic_calls(jitted(fn).lower(
        *described(one_chip, *shapes)).compile().as_text()))


def test_layer_norm(one_chip, compiled_mode):
    from mxnet_tpu.ops.pallas_norm import (pallas_layer_norm,
                                           pallas_ln_available)
    assert pallas_ln_available((L, N, C), BF, 2)
    shapes = [(L, N, C), (C,), (C,)]
    assert _custom_calls(one_chip, pallas_layer_norm, *shapes) == 1
    grad = jax.grad(lambda x, g, b: sum32(pallas_layer_norm(x, g, b)),
                    argnums=(0, 1, 2))
    assert _custom_calls(one_chip, grad, *shapes) >= 1


def test_the_op_at_512_positions_compiles_to_its_two_kernels(one_chip,
                                                             compiled_mode):
    """The registered op's value and gradient at 512 positions: two
    Mosaic custom calls named ``pallas_selfatt_packed_*`` (what
    ``pallas_ms`` sums); nothing of the composition's is left: no
    product outside the kernels, no mask drawn by XLA."""
    from mxnet_tpu.ops import get_op
    op = get_op("_contrib_sdp_selfatt").impl
    grad = jax.value_and_grad(lambda qkv, key: sum32(
        op(key, qkv, heads=H, dropout=0.1, _train=True)))
    text = jax.jit(grad).lower(*described(
        one_chip, (512, 8, 3 * H * D), ((2,), jnp.uint32))).compile().as_text()
    calls = mosaic_calls(text)
    assert len(calls) == 2
    assert sum("pallas_selfatt_packed_fwd" in c for c in calls) == 1
    assert sum("pallas_selfatt_packed_bwd" in c for c in calls) == 1
    assert "bernoulli" not in text and "dot(" not in text


def test_dropout(one_chip, compiled_mode):
    from mxnet_tpu.ops.pallas_dropout import (pallas_dropout,
                                              pallas_dropout_available)
    assert pallas_dropout_available((L, N, C), BF, 0.1)

    def fwd(x):
        return pallas_dropout(jax.random.key(0), x, 0.1)

    shapes = [(L, N, C)]
    assert _custom_calls(one_chip, fwd, *shapes) == 1
    # cotangent made to depend on x: the backward reads only the seeds,
    # and a program with no used chip-resident input lowers for the CPU
    assert _custom_calls(one_chip, jax.grad(lambda x: sum32(fwd(x) * x)),
                         *shapes) >= 1


@pytest.fixture
def four_chips(one_chip):
    """The described v5e:2x2 as a ``dp`` mesh, with the shardings of a
    batch-split ``(L, N, ...)`` operand and of a replicated one."""
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices), ("dp",))
    return (mesh, NamedSharding(mesh, P(None, "dp")),
            NamedSharding(mesh, P()))


def _collectives(text):
    return {k: text.count(k + "(") + text.count(k + "-start(")
            for k in ("all-gather", "all-to-all", "all-reduce")}


def test_gspmd_refuses_a_mosaic_kernel_and_the_scope_stands_it_down(
        four_chips, compiled_mode):
    """Why ShardedTrainStep traces inside auto_partitioned(mesh): a
    bare kernel in a program GSPMD partitions over four chips is
    refused. Inside the scope a kernel with no rule answers "not
    available"; the BERT kernels that have one, through their ops, run
    once a shard on batch-split operands (next test)."""
    from mxnet_tpu.ops.pallas_attention import selfatt_plan
    from mxnet_tpu.ops.pallas_common import auto_partitioned, kernels_allowed
    from mxnet_tpu.ops.pallas_norm import (pallas_layer_norm,
                                           pallas_ln_available)
    mesh, rows, rep = four_chips
    args = described(rows, (L, N, C)) + described(rep, (C,), (C,))
    with pytest.raises(NotImplementedError, match="automatically part"):
        jitted(pallas_layer_norm).lower(*args).compile()
    with auto_partitioned(mesh, batch=("dp", N)):
        assert not kernels_allowed()
        assert not pallas_ln_available((L, N, C), BF, 2)
        assert selfatt_plan(L, H, N, 0.0, dtype=BF, head_dim=D) is not None
    with auto_partitioned(mesh):        # no batch stated: nothing to split
        assert selfatt_plan(L, H, N, 0.0, dtype=BF, head_dim=D) is None
    assert kernels_allowed()
    one = type(mesh)(mesh.devices.reshape(-1)[:1], ("dp",))
    with auto_partitioned(one):
        assert kernels_allowed() and pallas_ln_available((L, N, C), BF, 2)


def _op(name, **attrs):
    """The registered op ``name`` as a function of its array operands
    (a PRNG key first where it draws)."""
    def call(*arrays):
        from mxnet_tpu.ops import get_op
        op = get_op(name)
        if attrs:
            arrays = (jax.random.key(0),) + arrays
        return op.impl(*arrays, **attrs)
    return call


NB = 512    # the dp4 cell's batch: 128 a chip (and not the length:
            # a row kernel finds the batch by its size)


# op, operand shapes ("rows": split on N), custom calls forward + backward
@pytest.mark.parametrize("op, shapes, calls", [
    (_op("Dropout", p=0.1, _train=True), [((L, NB, C), "rows")], 2),
    (_op("_contrib_sdp_selfatt", heads=H, dropout=0.1, _train=True),
     [((L, NB, 3 * H * D), "rows")], 2),
    (_op("LayerNorm"), [((L, NB, C), "rows"), ((C,), None), ((C,), None)],
     0),
], ids=["dropout", "attention", "norm"])
def test_a_bert_kernel_compiles_once_a_shard_on_a_split_batch(
        four_chips, compiled_mode, op, shapes, calls):
    """ISSUE 45: value and gradient through the op on a described
    v5e:2x2, 128 samples a chip: the Mosaic calls are there (inside a
    ``shard_map`` the compiler takes), nothing is gathered, and the
    only collective is the sum of the loss and the parameters'
    gradients. The layer norm and the two epilogues keep their
    compositions (no custom call): a shard each they lost to XLA's
    fusions on the chip."""
    from mxnet_tpu.ops.pallas_common import auto_partitioned
    mesh, rows, rep = four_chips
    args = [jax.ShapeDtypeStruct(s, BF, sharding=rows if d else rep)
            for s, d in shapes]

    def loss(*a):
        with auto_partitioned(mesh, batch=("dp", NB)):
            out = op(*a)
        # a cotangent that depends on the operand keeps every kernel's
        # backward alive
        return sum32(out * a[0][..., :out.shape[-1]])

    text = jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(len(args))))).lower(*args) \
        .compile().as_text()
    assert len(mosaic_calls(text)) == calls
    found = _collectives(text)
    assert found["all-gather"] == found["all-to-all"] == 0
    assert found["all-reduce"] <= 2


def _bert_step(mesh, layers, seq, batch, monkeypatch):
    """The BERT-base pretraining step (the zoo model through
    ``trace_block``, bf16 on float32 masters, dropout 0.1, LAMB through
    the shared ``_apply_update``) compiled for the described devices of
    ``mesh`` with ``batch`` samples split over them, traced in the scope
    ``ShardedTrainStep`` opens. Returns the compiled step, its Mosaic
    calls by kernel, how the trace moved the kernels' counters, and its
    collectives by kind."""
    from mxbench import manifest
    from mxnet_tpu import telemetry
    from mxnet_tpu.ops import pallas_common
    from mxnet_tpu.parallel.sharded import _apply_update, trace_block
    from jax.sharding import NamedSharding, PartitionSpec as P
    rep = NamedSharding(mesh, P())
    sizes, cfgmod, _ = manifest.config("bert_base")
    net, loss, n_in = cfgmod.sharded_parts(
        dict(sizes, num_hidden_layers=layers), 0.1, seq)
    # only now: the shapes above were resolved by an eager forward, on
    # the CPU and interpreted
    monkeypatch.setattr(pallas_common, "interpret_mode", lambda: False)
    fn, data_names, names, _ = trace_block(net, loss, n_in)
    shapes = {n: p.shape for block in (net, loss.head)
              for n, p in block.collect_params().items()}

    def sds(shape, dt=jnp.float32, sharding=rep):
        return jax.ShapeDtypeStruct(tuple(shape), dt, sharding=sharding)

    hp = dict(lr=1e-3, momentum=0.0, wd=0.01, beta1=0.9, beta2=0.999,
              epsilon=1e-8, clip_gradient=-1.0, rescale_grad=1.0)

    def loss_of(params, data, key):
        feed = {k: v.astype(BF) for k, v in params.items()}
        feed.update(zip(data_names, data))
        with pallas_common.auto_partitioned(mesh, batch=("dp", batch)):
            out, _ = fn(feed, rng=key)
        return sum32(out[0])

    def step(params, states, t, key, *data):
        value, grads = jax.value_and_grad(loss_of)(params, list(data), key)
        return value, {k: _apply_update(
            "lamb", hp, w, grads[k].astype(jnp.float32), states[k], t)
            for k, w in params.items()}

    params = {n: sds(shapes[n]) for n in names}
    ids = sds((batch, seq), jnp.int32, NamedSharding(mesh, P("dp")))
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=rep)
    counters = [("mx_attn_selfatt_path_total", ("path", path))
                for path in ("pallas", "xla")]
    counters += [("mx_pallas_partitioned_total", ("kernel", kernel),
                  ("how", how))
                 for kernel in ("pallas_selfatt_packed", "pallas_dropout")
                 for how in ("sharded", "composition")]

    def read():
        return {k: telemetry.counter(k[0], **dict(k[1:])).get()
                for k in counters}

    was = telemetry.enabled()
    telemetry.enable(True)
    try:
        start = read()
        compiled = jax.jit(step).lower(
            params, {n: (params[n], params[n]) for n in names}, sds(()),
            key, ids, ids, ids).compile()
        traced = {k: n - start[k] for k, n in read().items()}
    finally:
        telemetry.enable(was)
    text = compiled.as_text()
    calls = {}
    for line in mosaic_calls(text):
        name = re.findall(r"pallas_(?!call)\w+", re.search(
            r'op_name="([^"]*)"', line).group(1))[-1]
        calls[name] = calls.get(name, 0) + 1
    return compiled, calls, traced, _collectives(text)


def _attention_and_dropout(layers, how=None):
    """A layer's attention forward and backward and dropout's kernels
    (one more dropout after the embedding), and what tracing them
    counts: ``how`` they ran on a mesh."""
    calls = {"pallas_selfatt_packed_fwd": layers,
             "pallas_selfatt_packed_bwd": layers,
             "pallas_dropout_fwd": layers + 1,
             "pallas_dropout_bwd": layers + 1}
    traced = {("mx_attn_selfatt_path_total", ("path", "pallas")): layers,
              ("mx_attn_selfatt_path_total", ("path", "xla")): 0}
    for kernel, n in (("pallas_selfatt_packed", layers),
                      ("pallas_dropout", layers + 1)):
        for h in ("sharded", "composition"):
            traced["mx_pallas_partitioned_total", ("kernel", kernel),
                   ("how", h)] = n if h == how else 0
    return calls, traced


# the whole depth compiles in 40 s alone: `slow`, its two-layer twin not
@pytest.mark.parametrize("layers", [2, pytest.param(
    12, marks=pytest.mark.slow)])
def test_the_bert_dp4_step_at_128_a_chip_holds_every_kernel(
        four_chips, layers, monkeypatch):
    """The ``bert_base_pretrain_s128_dp4`` step compiled for the
    described 2x2 with 512 samples split four ways: a layer's attention
    forward and backward and dropout's kernels as ``tpu_custom_call``s
    (50 at 12 layers: the one-chip step's 102 less the 52 layer-norm
    calls, which keep their composition on a mesh), no all-gather or
    all-to-all, four all-reduces (loss and gradients, combined).
    Traced, the step counts its 12 attention calls under
    ``path="pallas"`` and every kernel ``how="sharded"``, none
    ``composition``. Temporaries: PERF.md section 6, PR 45."""
    compiled, calls, traced, found = _bert_step(four_chips[0], layers, 128,
                                                512, monkeypatch)
    assert (calls, traced) == _attention_and_dropout(layers, "sharded")
    assert found["all-gather"] == found["all-to-all"] == 0
    assert found["all-reduce"] <= 4
    if layers == 12:
        assert found["all-reduce"] == 4
        # 8.37 GB in the parent's own step on the chip
        assert compiled.memory_analysis().temp_size_in_bytes < 6e9


@pytest.mark.parametrize("layers", [2, pytest.param(
    12, marks=pytest.mark.slow)])
def test_the_one_chip_bert_step_holds_no_epilogue_kernel(
        four_chips, layers, monkeypatch):
    """The ``bert_base_pretrain_s128`` step (256 x 128 on one described
    chip): the attention and dropout calls of the ``dp4`` step and the
    layer norm's (two a layer, the embedding's and the head's, forward
    and backward), 102 at 12 layers, and no other. The Dense epilogues are
    XLA's fusions here as on a mesh (PR 48: 138 calls before it, 36 of
    them ``pallas_bias_gelu_*`` / ``pallas_residual_fwd``), and on one
    device nothing is wrapped a shard."""
    mesh = four_chips[0]
    one = type(mesh)(mesh.devices.reshape(-1)[:1], ("dp",))
    _, calls, traced, found = _bert_step(one, layers, 128, 256, monkeypatch)
    want, counted = _attention_and_dropout(layers)
    want.update(pallas_layer_norm_fwd=2 * layers + 2,
                pallas_layer_norm_bwd=2 * layers + 2)
    assert (calls, traced) == (want, counted)
    assert sum(found.values()) == 0
