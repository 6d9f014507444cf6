"""Chipless compiles: every Pallas kernel of the BERT path, at BERT-base
widths, through the TPU compiler for a DESCRIBED v5e chip (no chip is
attached here; nothing runs). Interpret mode — what every other kernel
test uses — cannot see what Mosaic refuses: block shapes off the (8, 128)
tiling, unsupported shape casts, primitives with no TPU lowering.

This is the one file that loads the TPU library: the topology is
described inside a module-scoped fixture (never at import), and every
compile happens in the test's own process. A compile that passes is not
a chip run; ``chip_smoke.py`` is.
"""
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

# BERT-base: seq 128, batch 32, 12 heads x 64, hidden 768, FFN 3072
L, N, H, D, C = 128, 32, 12, 64, 768


@pytest.fixture(scope="module")
def one_chip():
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
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_mode(monkeypatch):
    """Kernels built for the chip, not the interpreter — steered from
    the test: the code under test asks ``interpret_mode()`` and, with
    only CPU devices attached, would answer True."""
    from mxnet_tpu.ops import pallas_common
    monkeypatch.setattr(pallas_common, "interpret_mode", lambda: False)


def _custom_calls(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text() \
        .count("tpu_custom_call")


def _sum32(x):
    return jnp.sum(x.astype(jnp.float32))


BF = jnp.bfloat16


def test_layer_norm(one_chip, compiled_mode):
    from mxnet_tpu.ops.pallas_norm import (pallas_layer_norm,
                                           pallas_ln_available)
    assert pallas_ln_available((L, N, C), BF, 2)
    shapes = [((L, N, C), BF), ((C,), BF), ((C,), BF)]
    assert _custom_calls(one_chip, pallas_layer_norm, *shapes) == 1
    grad = jax.grad(lambda x, g, b: _sum32(pallas_layer_norm(x, g, b)),
                    argnums=(0, 1, 2))
    assert _custom_calls(one_chip, grad, *shapes) >= 1


# (length, batch) of 32,768 tokens a step: the s128 cell's call, the
# s512 cell's, and lengths no cell runs, up to the cap (ISSUE 39: a plan
# past 336 positions, under a VMEM limit the call states itself)
@pytest.mark.parametrize("p", [0.0, 0.1])
@pytest.mark.parametrize("length, batch", [(L, N), (384, 85), (512, 64),
                                           (768, 42), (1024, 32)],
                         ids=["L128", "L384", "L512", "L768", "L1024"])
def test_flash_attention(one_chip, compiled_mode, length, batch, p):
    from mxnet_tpu.ops.pallas_attention import flash_selfatt, selfatt_plan
    plan = selfatt_plan(length, H, batch, p, dtype=BF, head_dim=D)
    assert plan is not None
    shapes = [((length, batch, 3 * H * D), BF),
              ((plan["n_blocks"],), jnp.int32)]

    def fwd(qkv, seeds):
        return flash_selfatt(qkv, seeds, heads=H, dropout=p,
                             block_heads=plan["bbh"])

    assert _custom_calls(one_chip, fwd, *shapes) == 1
    grad = jax.grad(lambda qkv, seeds: _sum32(fwd(qkv, seeds)))
    assert _custom_calls(one_chip, grad, *shapes) >= 1


def test_the_op_at_512_positions_compiles_to_its_two_kernels(one_chip,
                                                             compiled_mode):
    """The registered op's value and gradient at 512 positions: two
    Mosaic custom calls named ``pallas_selfatt_packed_*`` (what
    ``pallas_ms`` sums); nothing of the composition's is left: no
    product outside the kernels, no mask drawn by XLA."""
    from mxnet_tpu.ops import get_op
    op = get_op("_contrib_sdp_selfatt").impl
    grad = jax.value_and_grad(lambda qkv, key: _sum32(
        op(key, qkv, heads=H, dropout=0.1, _train=True)))
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in [((512, 8, 3 * H * D), BF), ((2,), jnp.uint32)]]
    text = jax.jit(grad).lower(*args).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2
    assert sum("pallas_selfatt_packed_fwd" in c for c in calls) == 1
    assert sum("pallas_selfatt_packed_bwd" in c for c in calls) == 1
    assert "bernoulli" not in text and "dot(" not in text


def test_bias_gelu(one_chip, compiled_mode):
    from mxnet_tpu.ops.pallas_epilogue import (bias_gelu_available,
                                               pallas_bias_gelu)
    assert bias_gelu_available((L, N, 4 * C), BF, BF)
    shapes = [((L, N, 4 * C), BF), ((4 * C,), BF)]
    assert _custom_calls(one_chip, pallas_bias_gelu, *shapes) == 1
    grad = jax.grad(lambda x, b: _sum32(pallas_bias_gelu(x, b)),
                    argnums=(0, 1))
    assert _custom_calls(one_chip, grad, *shapes) >= 1


def test_bias_residual(one_chip, compiled_mode):
    from mxnet_tpu.ops.pallas_epilogue import (bias_residual_available,
                                               pallas_bias_residual)
    assert bias_residual_available((L, N, C), BF, BF, BF)
    shapes = [((L, N, C), BF), ((C,), BF), ((L, N, C), BF)]
    assert _custom_calls(one_chip, pallas_bias_residual, *shapes) == 1


def test_dropout(one_chip, compiled_mode):
    from mxnet_tpu.ops.pallas_dropout import (pallas_dropout,
                                              pallas_dropout_available)
    assert pallas_dropout_available((L, N, C), BF, 0.1)

    def fwd(x):
        return pallas_dropout(jax.random.key(0), x, 0.1)

    shapes = [((L, N, C), BF)]
    assert _custom_calls(one_chip, fwd, *shapes) == 1
    # cotangent made to depend on x: the backward reads only the seeds,
    # and a program with no used chip-resident input lowers for the CPU
    assert _custom_calls(one_chip, jax.grad(lambda x: _sum32(fwd(x) * x)),
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
    args = [jax.ShapeDtypeStruct((L, N, C), BF, sharding=rows),
            jax.ShapeDtypeStruct((C,), BF, sharding=rep),
            jax.ShapeDtypeStruct((C,), BF, sharding=rep)]
    with pytest.raises(NotImplementedError, match="automatically part"):
        jax.jit(pallas_layer_norm).lower(*args).compile()
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
    (_op("_contrib_bias_gelu"),
     [((L, NB, 4 * C), "rows"), ((4 * C,), None)], 0),
    (_op("_contrib_bias_add_residual"),
     [((L, NB, C), "rows"), ((C,), None), ((L, NB, C), "rows")], 0),
], ids=["dropout", "attention", "norm", "gelu", "residual"])
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
        return _sum32(out * a[0][..., :out.shape[-1]])

    text = jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(len(args))))).lower(*args) \
        .compile().as_text()
    assert text.count("tpu_custom_call") == calls
    found = _collectives(text)
    assert found["all-gather"] == found["all-to-all"] == 0
    assert found["all-reduce"] <= 2


# the whole depth compiles in 40 s alone, at the end of the file that
# already takes a tier-1 worker longest: `slow`, its two-layer twin not
@pytest.mark.parametrize("layers", [2, pytest.param(
    12, marks=pytest.mark.slow)])
def test_the_bert_dp4_step_at_128_a_chip_holds_every_kernel(
        four_chips, layers, monkeypatch):
    """The ``bert_base_pretrain_s128_dp4`` step (the zoo model through
    ``trace_block``, bf16 on float32 masters, dropout 0.1, LAMB through
    the shared ``_apply_update``) compiled for the described 2x2 with
    512 samples split four ways: a layer's attention forward and
    backward and dropout's kernels as ``tpu_custom_call``s (50 at 12
    layers: the one-chip step's 138 less the 52 layer-norm and the 36
    epilogue calls, which keep their compositions on a mesh), no
    all-gather or all-to-all, four all-reduces (loss and gradients,
    combined).
    Traced, the step counts its 12 attention calls under
    ``path="pallas"`` and every kernel ``how="sharded"``, none
    ``composition``. Temporaries: PERF.md section 6, PR 45."""
    from mxbench import manifest
    from mxnet_tpu import telemetry
    from mxnet_tpu.ops import pallas_common
    from mxnet_tpu.parallel.sharded import _apply_update, trace_block
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh, _, rep = four_chips
    seq, batch = 128, 512
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
        return _sum32(out[0])

    def step(params, states, t, key, *data):
        value, grads = jax.value_and_grad(loss_of)(params, list(data), key)
        return value, {k: _apply_update(
            "lamb", hp, w, grads[k].astype(jnp.float32), states[k], t)
            for k, w in params.items()}

    params = {n: sds(shapes[n]) for n in names}
    ids = sds((batch, seq), jnp.int32, NamedSharding(mesh, P("dp")))
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=rep)
    drops = layers + 1
    traced = {("mx_attn_selfatt_path_total", ("path", "pallas")): layers,
              ("mx_attn_selfatt_path_total", ("path", "xla")): 0}
    for kernel, n in (("pallas_selfatt_packed", layers),
                      ("pallas_dropout", drops)):
        traced["mx_pallas_partitioned_total", ("kernel", kernel),
               ("how", "sharded")] = n
        traced["mx_pallas_partitioned_total", ("kernel", kernel),
               ("how", "composition")] = 0

    def read():
        return {k: telemetry.counter(k[0], **dict(k[1:])).get()
                for k in traced}

    was = telemetry.enabled()
    telemetry.enable(True)
    try:
        start = read()
        compiled = jax.jit(step).lower(
            params, {n: (params[n], params[n]) for n in names}, sds(()),
            key, ids, ids, ids).compile()
        assert {k: n - start[k] for k, n in read().items()} == traced
    finally:
        telemetry.enable(was)
    text = compiled.as_text()
    calls = {}
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            name = re.findall(r"pallas_(?!call)\w+", re.search(
                r'op_name="([^"]*)"', line).group(1))[-1]
            calls[name] = calls.get(name, 0) + 1
    assert calls == {
        "pallas_selfatt_packed_fwd": layers,
        "pallas_selfatt_packed_bwd": layers,
        "pallas_dropout_fwd": drops, "pallas_dropout_bwd": drops}
    found = _collectives(text)
    assert found["all-gather"] == found["all-to-all"] == 0
    assert found["all-reduce"] <= 4
    if layers == 12:
        assert found["all-reduce"] == 4
        # 8.37 GB in the parent's own step on the chip
        assert compiled.memory_analysis().temp_size_in_bytes < 6e9


# ---------------------------------------------------------------------------
# the hybrid decoder's ops at the Nemotron-H widths (hidden 2688; 64
# Mamba heads x 64, 8 groups x 128; 32/2 attention heads x 128; experts
# 2688 x 1856, 8 of 128 held, top 6), forward and backward: XLA
# compositions, no kernel of this repo's or of the compiler's own; but
# attention through its op, which takes the flash kernel (last test)
# ---------------------------------------------------------------------------
@pytest.fixture
def composed_experts(monkeypatch):
    """The expert buffer's products as the XLA composition, the grouped
    kernels stood down (a plain CPU's answer, made explicit)."""
    from mxnet_tpu.ops import pallas_grouped_mlp
    monkeypatch.setattr(pallas_grouped_mlp, "grouped_mlp_available",
                        lambda *a: False)


def test_expert_product_follows_the_buffer_not_the_experts(one_chip,
                                                           composed_experts):
    from mxnet_tpu.ops import decoder_ops as D
    t, hidden, width, held, routed = 8192, 2688, 1856, 8, 128

    def loss(x, r, b, up, down):
        y, rows = D._moe_experts(x, r, b, up, down, top_k=6, offset=0,
                                 scale=2.5, norm_topk=True)
        return _sum32(y)

    shapes = [((t, hidden), BF), ((routed, hidden), BF),
              ((routed,), jnp.float32), ((held, width, hidden), BF),
              ((held, hidden, width), BF)]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 3, 4))) \
        .lower(*args).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    # the sorted path's FLOPs follow the buffer (20 blocks of 512 rows),
    # not buffer x experts: 5 products of 10240 x 2688 x 1856 (forward
    # and backward, the last forward product dead under a sum), beside
    # the dense path's loop under the conditional, whose body (one
    # expert over the 8192 rows: forward, recomputation, backward) is
    # counted once
    one = 2 * 10240 * hidden * width
    flops = compiled.cost_analysis()["flops"]
    assert 5 * one < flops < (5 + 7 * 8192 / 10240) * one * 1.2


@pytest.mark.parametrize("length", [1024])
def test_scan_and_attention_compile_at_published_widths(one_chip, length):
    from mxnet_tpu.ops import decoder_ops as D
    f32 = jnp.float32
    scan = jax.grad(lambda *a: _sum32(D._ssd(*a, 128)), argnums=(0, 1, 3, 4))
    shapes = [((1, length, 64, 64), BF), ((1, length, 64), f32), ((64,), f32),
              ((1, length, 8, 128), BF), ((1, length, 8, 128), BF),
              ((64,), f32)]
    assert _custom_calls(one_chip, scan, *shapes) == 0
    attn = jax.grad(lambda *a: _sum32(D._causal_gqa(*a, 512)),
                    argnums=(0, 1, 2))
    shapes = [((1, length, 32, 128), BF), ((1, length, 2, 128), BF),
              ((1, length, 2, 128), BF)]
    assert _custom_calls(one_chip, attn, *shapes) == 0


@pytest.mark.parametrize("length", [8192, 1024])
def test_causal_gqa_kernels_compile_under_the_scope_the_benchmark_reads(
        one_chip, compiled_mode, length):
    """The op's gradient at the published widths takes the flash kernel:
    two Mosaic custom calls, named ``pallas_causal_gqa_*`` (what
    ``pallas_ms`` sums), each placed under ``mx.attn.causal`` by the
    benchmark's own reader, the one traced in the backward rule too."""
    from mxbench import scopes
    from mxnet_tpu.ops import get_op
    op = get_op("_contrib_causal_gqa_attention").impl
    grad = jax.grad(lambda *a: _sum32(op(*a)), argnums=(0, 1, 2))
    shapes = [((1, length, 32, 128), BF), ((1, length, 2, 128), BF),
              ((1, length, 2, 128), BF)]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(grad).lower(*args).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    placed = scopes.scope_map(text, ["mx.attn.causal"])
    names = sorted(name for name in placed
                   if name.startswith("pallas_causal_gqa_"))
    assert len(calls) == len(names) == 2
    assert names[0].startswith("pallas_causal_gqa_bwd")
    assert names[1].startswith("pallas_causal_gqa_fwd")
    for line in calls:
        assert 'mx.attn.causal' in line.split('op_name="')[1].split('"')[0]


# a chunk's decays or mix over every chunk and head, as the compiled
# composition holds them: (chunks, groups, heads a group, chunk, chunk)
_DECAYS = re.compile(r"(f32|bf16)\[(1,)?\d+,(64|8,8),128,128\]")


def _holds_the_ssd_kernels(text, names):
    """A compiled program's Mosaic custom calls are the scan's kernels
    ``names``, each placed under ``mx.mamba2.ssd`` by the benchmark's
    own reader and carrying the scope in its ``op_name``; and nothing
    of a chunk's decays is left in HBM."""
    from mxbench import scopes
    calls = [line.split('op_name="')[1].split('"')[0]
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    placed = scopes.scope_map(text, ["mx.mamba2.ssd", "mx.mamba2"])
    kernels = {name: s for name, s in placed.items()
               if name.startswith("pallas_ssd_")}
    assert len(calls) == len(kernels) == len(names)
    assert sorted(n.split(".")[0] for n in kernels) == names
    assert set(kernels.values()) == {"mx.mamba2.ssd"}
    assert all("mx.mamba2.ssd" in op_name for op_name in calls)
    assert not _DECAYS.search(text)


@pytest.mark.parametrize("length", [8192, 1024])
def test_ssd_kernels_compile_under_the_scope_the_benchmark_reads(
        one_chip, compiled_mode, length):
    """The scan op's gradient at the published widths (64 heads x 64,
    8 groups x 128, chunk 128) takes the kernels: the forward rule's
    (which writes the chunks' entering states) and the backward, named
    ``pallas_ssd_*`` (what ``pallas_ms`` sums), each placed under
    ``mx.mamba2.ssd`` by the benchmark's own reader, the one traced in
    the backward rule too; and nothing of a chunk's decays is left in
    HBM (64 chunks x 64 heads of 128 x 128 float32 are 268 MB)."""
    from mxnet_tpu.ops import get_op
    op = get_op("_contrib_ssd_scan").impl
    f32 = jnp.float32
    grad = jax.grad(lambda *a: _sum32(op(*a, chunk_size=128)),
                    argnums=tuple(range(6)))
    shapes = [((1, length, 64, 64), BF), ((1, length, 64), f32), ((64,), f32),
              ((1, length, 8, 128), BF), ((1, length, 8, 128), BF),
              ((64,), f32)]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(grad).lower(*args).compile()
    _holds_the_ssd_kernels(compiled.as_text(),
                           ["pallas_ssd_bwd", "pallas_ssd_fwd_states"])
    # 269 MB at 8,192 (the entering states 67, the per-step columns and
    # their gradient a lane tile wide in HBM 34 each, dy and the views);
    # the composition's gradient holds 442
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 300e6 * length / 8192


def test_mamba2_mixer_at_8192_recomputes_through_the_kernels(one_chip,
                                                             compiled_mode):
    """The whole mixer's gradient at the cell's shape (hidden 2,688, a
    conv of 4): the forward kernel once (it writes no states), and in
    the recomputation the forward rule's kernel, not the composition,
    then the backward; all three under ``mx.mamba2.ssd`` inside
    ``mx.mamba2``; no array of a chunk's decays."""
    from mxnet_tpu.ops import get_op
    op = get_op("_contrib_mamba2_mixer").impl
    length, hidden, heads, p, groups, n = 8192, 2688, 64, 64, 8, 128
    inner, conv = heads * p, heads * p + 2 * groups * n
    f32 = jnp.float32
    shapes = [((1, length, hidden), BF), ((hidden,), BF),
              ((inner + conv + heads, hidden), BF), ((conv, 4), BF),
              ((conv,), BF), ((heads,), f32), ((heads,), f32),
              ((heads,), f32), ((inner,), BF), ((hidden, inner), BF)]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(jax.value_and_grad(
        lambda *a: _sum32(op(*a, num_heads=heads, head_dim=p,
                             n_groups=groups, state_size=n, chunk_size=128)),
        argnums=tuple(range(10)))).lower(*args).compile()
    _holds_the_ssd_kernels(
        compiled.as_text(),
        ["pallas_ssd_bwd", "pallas_ssd_fwd", "pallas_ssd_fwd_states"])


# ---------------------------------------------------------------------------
# the Keye-VL language model's sparse-attention mixer at the published
# widths (hidden 2048, 32 / 4 heads of 128, selector 16 x 64), and a
# whole toy training step: all XLA, nothing of Mosaic's, the selection
# without a sort
# ---------------------------------------------------------------------------
def test_sparse_attention_mixer_compiles_at_published_widths(one_chip):
    """1,024 tokens, top-k 256 so that selection engages in both query
    blocks: forward + backward for the described chip; the three inner
    scopes name instructions under ``mx.attn.dsa``; the k-th largest
    score comes from a loop of counts, no ``sort``."""
    from mxbench import scopes
    from mxnet_tpu.ops import get_op
    op = get_op("_contrib_sparse_gqa_mixer").impl
    length, hidden, h, kv, d, ih, idim = 1024, 2048, 32, 4, 128, 16, 64
    attrs = dict(num_heads=h, num_kv_heads=kv, head_dim=d, index_heads=ih,
                 index_head_dim=idim, top_k=256, rope_theta=1e7,
                 rope_sections=(16, 24, 24), eps=1e-6)

    def loss(*a):
        y, index_loss, _ = op(*a, **attrs)
        return _sum32(y) + index_loss[0]

    shapes = [((1, length, hidden), BF), ((hidden,), BF),
              ((h * d, hidden), BF), ((kv * d, hidden), BF),
              ((kv * d, hidden), BF), ((hidden, h * d), BF), ((d,), BF),
              ((d,), BF), ((ih * idim, hidden), BF), ((idim, hidden), BF),
              ((ih, hidden), BF), ((idim,), BF), ((idim,), BF),
              ((2,), jnp.float32)]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(13)))) \
        .lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    names = ("mx.attn.index", "mx.attn.select", "mx.attn.sparse",
             "mx.attn.dsa")
    found = scopes.scope_map(text, names)
    assert set(found.values()) == set(names)
    select = [line for line in text.splitlines() if "mx.attn.select" in line]
    assert [line for line in select if " while(" in line]
    assert not [line for line in select if " sort(" in line]
    # two blocks of scores, never a length x length one
    assert "f32[1,4,8,512,1024]" in text
    assert "f32[1,4,8,1024,1024]" not in text


def _sparse_mixer_gradient(one_chip, length, top_k):
    """The compiled text of the sparse mixer's value and gradient (both
    outputs, to all 13 parameters) at the published widths."""
    from mxnet_tpu.ops import get_op
    op = get_op("_contrib_sparse_gqa_mixer").impl
    hidden, h, kv, d, ih, idim = 2048, 32, 4, 128, 16, 64
    attrs = dict(num_heads=h, num_kv_heads=kv, head_dim=d, index_heads=ih,
                 index_head_dim=idim, top_k=top_k, rope_theta=1e7,
                 rope_sections=(16, 24, 24), eps=1e-6)

    def loss(*a):
        y, index_loss, _ = op(*a, **attrs)
        return _sum32(y) + index_loss[0]

    shapes = [((1, length, hidden), BF), ((hidden,), BF),
              ((h * d, hidden), BF), ((kv * d, hidden), BF),
              ((kv * d, hidden), BF), ((hidden, h * d), BF), ((d,), BF),
              ((d,), BF), ((ih * idim, hidden), BF), ((idim, hidden), BF),
              ((ih, hidden), BF), ((idim,), BF), ((idim,), BF),
              ((2,), jnp.float32)]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(jax.value_and_grad(loss, argnums=tuple(range(13)))) \
        .lower(*args).compile().as_text()


@pytest.mark.parametrize("length, top_k", [(1024, 256), (8192, 2048)])
def test_sparse_gqa_kernels_compile_under_the_scope_the_benchmark_reads(
        one_chip, compiled_mode, length, top_k):
    """Compiled, not interpreted, the mixer takes the flash kernels:
    Mosaic accepts them within the VMEM limit, every custom call is
    named ``pallas_sparse_gqa_*`` (what ``pallas_ms`` sums) and placed
    under ``mx.attn.sparse`` by the benchmark's own reader (the forward
    kernel once: the recomputation does not run it again; the
    probabilities a query block in the forward and again in the
    backward rule; one backward kernel), and no score block is left in
    the program."""
    from mxbench import scopes
    text = _sparse_mixer_gradient(one_chip, length, top_k)
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    names = ("mx.attn.index", "mx.attn.select", "mx.attn.sparse",
             "mx.attn.dsa")
    placed = scopes.scope_map(text, names)
    assert set(placed.values()) == set(names)
    kernels = {name: scope for name, scope in placed.items()
               if name.startswith("pallas_sparse_gqa_")}
    blocks = length // 512
    assert len(calls) == len(kernels) == 2 + 2 * blocks
    assert set(kernels.values()) == {"mx.attn.sparse"}
    kinds = [name.split(".")[0] for name in kernels]
    assert kinds.count("pallas_sparse_gqa_fwd") == 1
    assert kinds.count("pallas_sparse_gqa_bwd") == 1
    assert kinds.count("pallas_sparse_gqa_probs") == 2 * blocks
    for line in calls:
        assert "mx.attn.sparse" in line.split('op_name="')[1].split('"')[0]
    assert "f32[1,4,8,512," not in text
    assert "f32[1,32,512," not in text


def test_a_whole_toy_keye_step_compiles_for_the_chip(one_chip):
    """The zoo model through ``trace_block`` as ``ShardedTrainStep``
    traces it (both losses, bf16 compute, AdamW through the shared
    ``_apply_update``), at the configuration's toy widths."""
    from mxbench import manifest
    from mxnet_tpu.parallel.sharded import _apply_update, trace_block
    sizes, cfgmod, _ = manifest.config("keye_vl2_30b_a3b")
    sizes = dict(sizes, **sizes["toy"])
    net, loss, n_in = cfgmod.sharded_parts(sizes, 0.0, 64)
    fn, data_names, names, _ = trace_block(net, loss, n_in)
    shapes = {n: p.shape for block in (net, loss.head)
              for n, p in block.collect_params().items()}
    aux_names = [n for n in names if n in fn._aux_names]
    names = [n for n in names if n not in fn._aux_names]
    assert len(aux_names) == 2 * sizes["num_hidden_layers"]

    def sds(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dt, sharding=one_chip)

    hp = dict(lr=1e-5, momentum=0.9, wd=1e-6, beta1=0.9, beta2=0.95,
              epsilon=1e-8, clip_gradient=-1.0, rescale_grad=1.0)

    def loss_of(params, aux, data):
        feed = {k: v.astype(BF) for k, v in params.items()}
        feed.update(zip(data_names, data))
        feed.update(aux)
        out, new_aux = fn(feed)
        return _sum32(out[0]), new_aux

    def step(params, aux, states, t, *data):
        (value, new_aux), grads = jax.value_and_grad(
            loss_of, has_aux=True)(params, aux, list(data))
        new = {k: _apply_update("adamw", hp, w, grads[k], states[k], t)
               for k, w in params.items()}
        return value, new_aux, new

    params = {n: sds(shapes[n]) for n in names}
    aux = {n: sds(shapes[n]) for n in aux_names}
    ids = sds((2, 64), jnp.int32)
    text = jax.jit(step).lower(
        params, aux, {n: (params[n], params[n]) for n in names}, sds(()),
        ids, ids).compile().as_text()
    assert "tpu_custom_call" not in text
    for scope in cfgmod.SCOPES:
        assert scope in text, scope


# ---------------------------------------------------------------------------
# Mellum 2's two mixers at the published widths (hidden 2304, 32 / 4
# heads of 128, 16 of 64 experts of width 896) and the cell's 16,384
# tokens: what a step of the long-context cell is made of
# ---------------------------------------------------------------------------
def _rotary_mixer_gradient(one_chip, length, **attrs):
    from mxnet_tpu.ops import get_op
    op = get_op("_contrib_rotary_gqa_mixer").impl
    hidden, h, kv, d = 2304, 32, 4, 128
    shapes = [((1, length, hidden), BF), ((hidden,), BF),
              ((h * d, hidden), BF), ((kv * d, hidden), BF),
              ((kv * d, hidden), BF), ((hidden, h * d), BF), ((d,), BF),
              ((d,), BF)]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(jax.value_and_grad(
        lambda *a: _sum32(op(*a, num_heads=h, num_kv_heads=kv, head_dim=d,
                             rope_theta=5e5, eps=1e-6, **attrs)),
        argnums=tuple(range(8)))).lower(*args).compile()


@pytest.mark.parametrize("kind, attrs, scope, other", [
    ("sliding", dict(window=1024), "mx.attn.window", "mx.attn.causal"),
    ("full", dict(rope_yarn=(16, 8192, 32, 1),
                  attention_factor=1.2772588722239782),
     "mx.attn.causal", "mx.attn.window")])
def test_rotary_mixer_at_16384_takes_the_kernel_under_its_kind_s_scope(
        one_chip, compiled_mode, kind, attrs, scope, other):
    """Both kinds of Mellum 2's attention layer at the cell's length:
    Mosaic accepts the windowed kernels (a loop from a traced first
    tile, a ``cond`` around the band's tile) and the causal ones at
    twice the Nemotron cell's length; the forward kernel is in the
    program once (the mixer's recomputation keeps the context and the
    log-sum-exp), the backward once; both under the scope the benchmark
    reads for that kind, and the whole mixer's temporaries stay under a
    gigabyte and a half."""
    from mxbench import scopes
    compiled = _rotary_mixer_gradient(one_chip, 16384, **attrs)
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    placed = scopes.scope_map(text, [scope, other, "mx.attn.rotary"])
    kernels = {name: s for name, s in placed.items()
               if name.startswith("pallas_causal_gqa_")}
    assert len(calls) == len(kernels) == 2
    assert set(kernels.values()) == {scope}
    assert sorted(n.split(".")[0] for n in kernels) == [
        "pallas_causal_gqa_bwd", "pallas_causal_gqa_fwd"]
    assert other not in placed.values()
    assert "mx.attn.rotary" in placed.values()
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9
    # no score block: 512 queries against a band, or against every key
    assert "f32[1,4,8,512," not in text


def test_expert_mixer_at_16384_chunks_its_blocks(one_chip, composed_experts):
    """16,384 tokens over 16 held experts of 64 at top 8 fill a buffer
    of 144 blocks, three times what one batched product takes
    (``BLOCKS_AT_ONCE``): the composition's product runs as a loop over
    chunks of blocks, and the mixer's gradient keeps under 4 GB of
    temporaries (9.5 GB as one product, which the step cannot give
    it)."""
    from mxnet_tpu.ops import decoder_ops as D, get_op
    op = get_op("_contrib_moe_mixer").impl
    length, hidden, width, held, routed = 16384, 2304, 896, 16, 64

    def loss(x, g, r, w1, w2):
        y, _ = op(x, g, r, jnp.zeros((2, held), jnp.float32), w1, w2,
                  top_k=8, score_func="softmax", activation="swiglu",
                  eps=1e-6)
        return _sum32(y)

    shapes = [((1, length, hidden), BF), ((hidden,), BF),
              ((routed, hidden), BF), ((held, 2 * width, hidden), BF),
              ((held, hidden, width), BF)]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))) \
        .lower(*args).compile()
    assert 144 > D.BLOCKS_AT_ONCE and 144 % D.BLOCKS_A_CHUNK == 0
    text = compiled.as_text()
    chunk = "%d,%d,512,%d" % (144 // D.BLOCKS_A_CHUNK, D.BLOCKS_A_CHUNK,
                              2 * width)
    assert "f32[%s]" % chunk in text        # a chunk's kept hidden layer
    assert "bf16[144,%d,%d]" % (2 * width, hidden) not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 4e9


# ---------------------------------------------------------------------------
# the expert mixer of the three decoder cells at their published widths:
# the buffer's products are the grouped kernels of ops/pallas_grouped_mlp
# where the widths are whole lane tiles
# ---------------------------------------------------------------------------
def _expert_mixer_args(sharding, length, hidden, width, held, routed, mul):
    shapes = [((1, length, hidden), BF), ((hidden,), BF),
              ((routed, hidden), BF), ((held, mul * width, hidden), BF),
              ((held, hidden, width), BF)]
    return [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]


def _expert_mixer_gradient(held, **attrs):
    from mxnet_tpu.ops import get_op
    op = get_op("_contrib_moe_mixer").impl

    def loss(x, g, r, w1, w2):
        y, _ = op(x, g, r, jnp.zeros((2, held), jnp.float32), w1, w2,
                  eps=1e-6, **attrs)
        return _sum32(y)

    return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))


EXPERT_CELLS = {
    # length, hidden, width, held, routed, rows of w1 a width, blocks
    "mellum2": ((16384, 2304, 896, 16, 64, 2), 144,
                dict(top_k=8, score_func="softmax", activation="swiglu")),
    "keye_vl": ((8192, 2048, 768, 16, 128, 2), 48,
                dict(top_k=8, score_func="softmax", activation="swiglu")),
}


@pytest.mark.parametrize("cell", sorted(EXPERT_CELLS))
def test_expert_mixer_takes_the_grouped_kernels_under_its_scope(
        one_chip, compiled_mode, cell):
    """Mosaic accepts the three kernels at both cells' widths; a step's
    seven calls (two
    forward, the first again in the mixer's recomputation, four
    backward) are placed under the scope the benchmark reads; no
    gathered copy of a weight and no float32 gradient a block exists;
    and the Mellum 2 mixer's gradient needs 1.9 GB of temporaries where
    the chunked composition needs 3.63."""
    from mxbench import scopes
    sizes, blocks, attrs = EXPERT_CELLS[cell]
    length, hidden, width, held, routed, mul = sizes
    compiled = jax.jit(_expert_mixer_gradient(held, **attrs)).lower(
        *_expert_mixer_args(one_chip, *sizes)).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    placed = scopes.scope_map(text, ["mx.moe.experts", "mx.moe"])
    # (a call traced inside the backward's ``jax.vjp`` is named
    # ``jvp_pallas_grouped_mlp_nt_``)
    kernels = {name: s for name, s in placed.items()
               if "pallas_grouped_mlp_" in name}
    # (beside them the slot sum's two calls: the next test)
    assert len(kernels) == 7 and len(calls) == 9
    assert set(kernels.values()) == {"mx.moe.experts"}
    assert sorted(re.search("pallas_grouped_mlp_(dw|nn|nt)", n).group(1)
                  for n in kernels) == ["dw"] * 2 + ["nn"] * 2 + ["nt"] * 3
    assert "s32[%d]" % blocks in text
    for out, inner in ((mul * width, hidden), (hidden, width)):
        assert "bf16[%d,%d,%d]" % (blocks, out, inner) not in text
        assert "f32[%d,%d,%d]" % (blocks, out, inner) not in text
    memory = compiled.memory_analysis()
    # nothing but its inputs crosses the overflow ``cond`` (a copy of
    # the weights and a zero array of their size did: 168 MB of program
    # at the Keye-VL widths for 17)
    assert memory.generated_code_size_in_bytes < 40e6
    if cell == "mellum2":
        assert memory.temp_size_in_bytes < 2.5e9


def test_expert_mixer_off_the_lane_tiles_keeps_the_composition(one_chip,
                                                               compiled_mode):
    """The Nemotron cell's width, 1,856, is 14.5 lane tiles:
    ``grouped_mlp_available`` says no (Mosaic takes the width as one
    whole tile, but the step's AUTO parameter layouts then do not
    survive the persistent compile cache: PERF.md section 6, PR 35),
    and the mixer's products compile as the composition; the only
    Mosaic calls are the slot sum's two (``ops/pallas_moe_rows.py``
    takes activations of any whole number of lane tiles: 2,688 is
    21)."""
    from mxnet_tpu.ops import pallas_grouped_mlp
    sizes = (8192, 2688, 1856, 8, 128, 1)
    assert not pallas_grouped_mlp.grouped_mlp_available(
        jax.ShapeDtypeStruct((20, 512, 2688), BF),
        jax.ShapeDtypeStruct((8, 1856, 2688), BF),
        jax.ShapeDtypeStruct((8, 2688, 1856), BF))
    text = jax.jit(_expert_mixer_gradient(
        8, top_k=6, routed_scaling_factor=2.5)).lower(
            *_expert_mixer_args(one_chip, *sizes)).compile().as_text()
    assert "pallas_grouped_mlp" not in text
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2 and all("pallas_moe_rows_sum" in c for c in calls)


# the expert mixer's gradient with the slot sum's window kernel in it:
# buffer rows, (tokens, top_k), hidden; and the temporaries of the same
# compile with the kernel stood down (PR 43's readings: 1,846,272,000 /
# 593,056,768 / 724,051,456 bytes; with it 1,832,087,040 / 491,890,176 /
# 695,194,112)
ROWS_CELLS = {
    "mellum2": (EXPERT_CELLS["mellum2"][0], 0, EXPERT_CELLS["mellum2"][2],
                73728, 1.84e9),
    "laguna": ((8192, 2048, 512, 32, 256, 2), 512,
               dict(top_k=8, routed_scaling_factor=2.5, score_func="softmax",
                    activation="swiglu"), 32768, 0.55e9),
    "keye_vl": (EXPERT_CELLS["keye_vl"][0], 0, EXPERT_CELLS["keye_vl"][2],
                24576, 0.71e9),
}


@pytest.mark.parametrize("cell", sorted(ROWS_CELLS))
def test_expert_mixer_sums_its_slots_by_the_window_kernel(one_chip,
                                                          compiled_mode, cell):
    """Mosaic accepts ``pallas_moe_rows_sum`` at the cells' shapes; its
    two calls a layer (the forward's sum, which the recomputation does
    not need again, and the pullback of the gather in the backward, whose
    rule is traced after the caller's scopes have closed) are placed
    under the scope the benchmark reads; no (tokens, top_k, hidden)
    gather is left (the three gathers into the buffer are XLA's); and
    the gradient's temporaries stay under what the same compile took
    with the kernel stood down."""
    from mxbench import scopes
    from mxnet_tpu.ops import get_op
    sizes, shared, attrs, cap, bound = ROWS_CELLS[cell]
    length, hidden, width, held, routed, mul = sizes
    moe = get_op("_contrib_moe_mixer").impl

    def loss(x, g, r, w1, w2, *s):
        y, _ = moe(x, g, r, jnp.zeros((2, held), jnp.float32), w1, w2, None,
                   *s, eps=1e-6, **attrs)
        return _sum32(y)

    args = _expert_mixer_args(one_chip, *sizes) + [
        jax.ShapeDtypeStruct(s, BF, sharding=one_chip)
        for s in ((2 * shared, hidden), (hidden, shared)) if shared]
    compiled = jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(len(args))))).lower(*args).compile()
    text = compiled.as_text()
    placed = scopes.scope_map(text, ["mx.moe.experts", "mx.moe"])
    sums = {name: s for name, s in placed.items()
            if "pallas_moe_rows_sum" in name}
    assert len(sums) == 2 and set(sums.values()) == {"mx.moe.experts"}
    assert sum("transpose(jvp" in line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line
               and "pallas_moe_rows_sum" in line) == 1
    top_k = attrs["top_k"]
    assert "bf16[%d,%d,%d]" % (length, top_k, hidden) not in text
    assert "bf16[%d,%d]" % (length * top_k, hidden) not in text
    gathers = [line for line in text.splitlines() if " gather(" in line
               and "bf16[%d,%d]" % (cap, hidden) in line.split(" gather(")[0]]
    assert len(gathers) == 3
    assert compiled.memory_analysis().temp_size_in_bytes < bound


def test_expert_mixer_under_a_mesh_keeps_the_composition(one_chip,
                                                         compiled_mode):
    """Traced for a program GSPMD partitions over the described 2 x 2
    chips, the expert buffer's kernels stand down: the mixer compiles
    there with no Mosaic call."""
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from mxnet_tpu.ops.pallas_common import auto_partitioned
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices), ("dp",))
    sizes, _, attrs = EXPERT_CELLS["keye_vl"]
    args = _expert_mixer_args(NamedSharding(mesh, P()), *sizes)
    args[0] = jax.ShapeDtypeStruct((4,) + args[0].shape[1:], BF,
                                   sharding=NamedSharding(mesh, P("dp")))
    with auto_partitioned(mesh):
        text = jax.jit(_expert_mixer_gradient(sizes[3], **attrs)) \
            .lower(*args).compile().as_text()
    assert "tpu_custom_call" not in text


# ---------------------------------------------------------------------------
# GLM-4.7-Flash's mixers at the published widths (hidden 2048, 20 heads
# of 192 + 64 / 256 lanes through bottlenecks of 768 and 512, a dense
# MLP of 10,240, 8 of 64 experts of width 1,536 beside a shared one)
# and the cell's 8,192 tokens
# ---------------------------------------------------------------------------
def _mla_mixer_gradient(one_chip, length):
    from mxnet_tpu.ops import get_op
    op = get_op("_contrib_mla_mixer").impl
    hidden, h, qr, kvr, nope, rope, vd = 2048, 20, 768, 512, 192, 64, 256
    shapes = [((1, length, hidden), BF), ((hidden,), BF), ((qr, hidden), BF),
              ((qr,), BF), ((h * (nope + rope), qr), BF),
              ((kvr + rope, hidden), BF), ((kvr,), BF),
              ((h * (nope + vd), kvr), BF), ((hidden, h * vd), BF)]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(jax.value_and_grad(
        lambda *a: _sum32(op(*a, num_heads=h, qk_nope_head_dim=nope,
                             qk_rope_head_dim=rope, v_head_dim=vd,
                             rope_theta=1e6, eps=1e-5)),
        argnums=tuple(range(9)))).lower(*args).compile()


def test_mla_mixer_at_8192_takes_the_causal_kernel_at_256_lanes(
        one_chip, compiled_mode):
    """The latent-attention mixer at the cell's shape: Mosaic accepts
    the causal kernels at 256-wide heads and a group of one, as they
    are; the forward kernel is in the program once (the mixer's
    recomputation keeps the context and the log-sum-exp, and expands
    q, k, v again), the backward once; both under ``mx.attn.causal``
    inside ``mx.attn.mla``; no score block exists; and the whole
    mixer's temporaries stay under a gigabyte."""
    from mxbench import scopes
    compiled = _mla_mixer_gradient(one_chip, 8192)
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    placed = scopes.scope_map(text, ["mx.attn.causal", "mx.attn.mla"])
    kernels = {name: s for name, s in placed.items()
               if name.startswith("pallas_causal_gqa_")}
    assert len(calls) == len(kernels) == 2
    assert set(kernels.values()) == {"mx.attn.causal"}
    assert sorted(n.split(".")[0] for n in kernels) == [
        "pallas_causal_gqa_bwd", "pallas_causal_gqa_fwd"]
    assert "mx.attn.mla" in placed.values()
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9
    assert "f32[1,20,1,512," not in text and "f32[1,20,512," not in text


def test_glm_expert_and_dense_mixers_compile_at_published_widths(
        one_chip, compiled_mode):
    """The expert op's fourth combination (sigmoid scores with a
    selection bias, SwiGLU experts, a SwiGLU shared expert, x 1.8) at 8
    of 64 experts of width 1,536: the grouped kernels' seven calls
    under ``mx.moe.experts`` (24 blocks), the shared expert's products
    outside it under ``mx.moe``; and the dense gated MLP of width
    10,240 under ``mx.mlp``."""
    from mxbench import scopes
    from mxnet_tpu.ops import get_op
    moe = get_op("_contrib_moe_mixer").impl
    length, hidden, width, held, routed = 8192, 2048, 1536, 8, 64

    def loss(x, g, r, w1, w2, bias, s1, s2):
        y, _ = moe(x, g, r, jnp.zeros((2, held), jnp.float32), w1, w2, bias,
                   s1, s2, top_k=4, routed_scaling_factor=1.8,
                   score_func="sigmoid", activation="swiglu", eps=1e-5)
        return _sum32(y)

    args = _expert_mixer_args(one_chip, length, hidden, width, held, routed,
                              2) + [
        jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in (
            ((routed,), jnp.float32), ((2 * width, hidden), BF),
            ((hidden, width), BF))]
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4, 6, 7))) \
        .lower(*args).compile().as_text()
    placed = scopes.scope_map(text, ["mx.moe.experts", "mx.moe"])
    kernels = {name: s for name, s in placed.items()
               if "pallas_grouped_mlp_" in name}
    assert len(kernels) == 7 and set(kernels.values()) == {"mx.moe.experts"}
    assert "s32[24]" in text
    assert "mx.moe" in placed.values()

    mlp = get_op("_contrib_glu_mlp_mixer").impl
    shapes = [((1, length, hidden), BF), ((hidden,), BF),
              ((2 * 10240, hidden), BF), ((hidden, 10240), BF)]
    compiled = jax.jit(jax.value_and_grad(
        lambda *a: _sum32(mlp(*a, eps=1e-5)), argnums=(0, 1, 2, 3))).lower(
        *[jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
          for s, dt in shapes]).compile()
    assert set(scopes.scope_map(compiled.as_text(), ["mx.mlp"]).values()) \
        == {"mx.mlp"}
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


# ---------------------------------------------------------------------------
# Laguna-XS.2's mixers at the published widths (hidden 2048, 48 / 64
# query heads over 8 key-value heads of 128, a gate a head, 32 of 256
# experts of width 512 beside a shared one) and the cell's 8,192 tokens
# ---------------------------------------------------------------------------
def _gated_mixer_gradient(one_chip, heads, **attrs):
    from mxnet_tpu.ops import get_op
    op = get_op("_contrib_rotary_gqa_mixer").impl
    length, hidden, kv, d = 8192, 2048, 8, 128
    shapes = [(1, length, hidden), (hidden,), (heads * d, hidden),
              (kv * d, hidden), (kv * d, hidden), (hidden, heads * d),
              (heads, hidden)]
    args = [jax.ShapeDtypeStruct(s, BF, sharding=one_chip) for s in shapes]
    return jax.jit(jax.value_and_grad(
        lambda *a: _sum32(op(*a[:6], gate_weight=a[6], num_heads=heads,
                             num_kv_heads=kv, head_dim=d, eps=1e-6, **attrs)),
        argnums=tuple(range(7)))).lower(*args).compile()


@pytest.mark.parametrize("kind, heads, attrs, scope, other", [
    ("sliding", 64, dict(window=512, rope_theta=1e4),
     "mx.attn.window", "mx.attn.causal"),
    ("full", 48, dict(rotary_dim=64, rope_theta=5e5,
                      rope_yarn=(64, 4096, 64, 1),
                      attention_factor=1.4158883083359672),
     "mx.attn.causal", "mx.attn.window")])
def test_gated_rotary_mixer_at_8192_takes_the_kernel_at_groups_of_6_and_8(
        one_chip, compiled_mode, kind, heads, attrs, scope, other):
    """Both kinds of Laguna-XS.2's attention layer at the cell's
    length: Mosaic accepts the causal kernels at a group of 6 query
    heads a key-value head (no power of two) and the windowed ones at a
    window of one tile (the diagonal tile and one ``cond``-ed edge
    tile); forward once, backward once, under the kind's scope; the
    gate's instructions under ``mx.attn.gate``; no q/k norm weight is
    an input; the mixer's temporaries stay under 1.2 GB."""
    from mxbench import scopes
    compiled = _gated_mixer_gradient(one_chip, heads, **attrs)
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    placed = scopes.scope_map(text, ["mx.attn.gate", scope, other,
                                     "mx.attn.rotary"])
    kernels = {name: s for name, s in placed.items()
               if name.startswith("pallas_causal_gqa_")}
    assert len(calls) == len(kernels) == 2
    assert set(kernels.values()) == {scope}
    assert other not in placed.values()
    assert {"mx.attn.rotary", "mx.attn.gate"} <= set(placed.values())
    assert compiled.memory_analysis().temp_size_in_bytes < 1.2e9
    assert "f32[1,8,%d,512," % (heads // 8) not in text     # no score block


def test_laguna_expert_mixer_fills_a_quarter_of_its_blocks(one_chip,
                                                           compiled_mode):
    """The expert op's fifth combination (softmax scores renormalised
    and x 2.5, SwiGLU experts, a SwiGLU shared expert) at 32 of 256
    experts of width 512: an expert's even share of 8,192 tokens at top
    8 is 256 rows, half a block, so the buffer is 64 blocks of 512
    (twice the share and a block an expert); the grouped kernels' seven
    calls under ``mx.moe.experts``, the shared expert outside it."""
    from mxbench import scopes
    from mxnet_tpu.ops import get_op
    moe = get_op("_contrib_moe_mixer").impl
    length, hidden, width, held, routed = 8192, 2048, 512, 32, 256

    def loss(x, g, r, w1, w2, s1, s2):
        y, _ = moe(x, g, r, jnp.zeros((2, held), jnp.float32), w1, w2, None,
                   s1, s2, top_k=8, routed_scaling_factor=2.5,
                   score_func="softmax", activation="swiglu", eps=1e-6)
        return _sum32(y)

    args = _expert_mixer_args(one_chip, length, hidden, width, held, routed,
                              2) + [
        jax.ShapeDtypeStruct(s, BF, sharding=one_chip)
        for s in ((2 * width, hidden), (hidden, width))]
    compiled = jax.jit(jax.value_and_grad(loss, argnums=tuple(range(7)))) \
        .lower(*args).compile()
    text = compiled.as_text()
    placed = scopes.scope_map(text, ["mx.moe.experts", "mx.moe"])
    kernels = {name: s for name, s in placed.items()
               if "pallas_grouped_mlp_" in name}
    assert len(kernels) == 7 and set(kernels.values()) == {"mx.moe.experts"}
    assert "s32[64]" in text
    assert "mx.moe" in placed.values()
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9
