"""Chipless compiles, the decoders' attention, scan and selector at
their published widths for a described v5e chip (see
tests/test_chip_compile_bert.py for what such a compile can and cannot
show): the causal, windowed and sparse flash kernels, the Mamba-2 scan's
kernels, each under the scope the benchmark reads, and whole toy
steps of four decoders.
"""
import re

import pytest

import jax
import jax.numpy as jnp

from numerics import BF, described, mosaic_calls, sum32


# ---------------------------------------------------------------------------
# the hybrid decoder's ops at the Nemotron-H widths (hidden 2688; 64
# Mamba heads x 64, 8 groups x 128; 32/2 attention heads x 128; experts
# 2688 x 1856, 8 of 128 held, top 6), forward and backward: XLA
# compositions, no kernel of this repo's or of the compiler's own; but
# attention and the scan through their ops, which take the kernels
# ---------------------------------------------------------------------------
def _scan_operands(one_chip, length):
    """x, dt, a, B, C, d of the scan: 64 heads x 64, 8 groups x 128."""
    f32 = jnp.float32
    return described(
        one_chip, (1, length, 64, 64), ((1, length, 64), f32), ((64,), f32),
        (1, length, 8, 128), (1, length, 8, 128), ((64,), f32))


def _attention_operands(one_chip, length):
    """q, k, v: 32 / 2 heads of 128."""
    return described(one_chip, (1, length, 32, 128), (1, length, 2, 128),
                     (1, length, 2, 128))


@pytest.mark.parametrize("length", [1024])
def test_scan_and_attention_compile_at_published_widths(one_chip, length):
    from mxnet_tpu.ops import decoder_ops as D
    scan = jax.grad(lambda *a: sum32(D._ssd(*a, 128)), argnums=(0, 1, 3, 4))
    assert not mosaic_calls(jax.jit(scan).lower(
        *_scan_operands(one_chip, length)).compile().as_text())
    attn = jax.grad(lambda *a: sum32(D._causal_gqa(*a, 512)),
                    argnums=(0, 1, 2))
    assert not mosaic_calls(jax.jit(attn).lower(
        *_attention_operands(one_chip, length)).compile().as_text())


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
    grad = jax.grad(lambda *a: sum32(op(*a)), argnums=(0, 1, 2))
    text = jax.jit(grad).lower(
        *_attention_operands(one_chip, length)).compile().as_text()
    calls = mosaic_calls(text)
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
             for line in mosaic_calls(text)]
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
    grad = jax.grad(lambda *a: sum32(op(*a, chunk_size=128)),
                    argnums=tuple(range(6)))
    compiled = jax.jit(grad).lower(
        *_scan_operands(one_chip, length)).compile()
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
    args = described(
        one_chip, (1, length, hidden), (hidden,),
        (inner + conv + heads, hidden), (conv, 4), (conv,), ((heads,), f32),
        ((heads,), f32), ((heads,), f32), (inner,), (hidden, inner))
    compiled = jax.jit(jax.value_and_grad(
        lambda *a: sum32(op(*a, num_heads=heads, head_dim=p,
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
def _sparse_mixer(one_chip, length, top_k):
    """(the sparse mixer's loss over both outputs, its 13 parameters and
    the state) at the published widths."""
    from mxnet_tpu.ops import get_op
    op = get_op("_contrib_sparse_gqa_mixer").impl
    hidden, h, kv, d, ih, idim = 2048, 32, 4, 128, 16, 64
    attrs = dict(num_heads=h, num_kv_heads=kv, head_dim=d, index_heads=ih,
                 index_head_dim=idim, top_k=top_k, rope_theta=1e7,
                 rope_sections=(16, 24, 24), eps=1e-6)

    def loss(*a):
        y, index_loss, _ = op(*a, **attrs)
        return sum32(y) + index_loss[0]

    return loss, described(
        one_chip, (1, length, hidden), (hidden,), (h * d, hidden),
        (kv * d, hidden), (kv * d, hidden), (hidden, h * d), (d,), (d,),
        (ih * idim, hidden), (idim, hidden), (ih, hidden), (idim,), (idim,),
        ((2,), jnp.float32))


def test_sparse_attention_mixer_compiles_at_published_widths(one_chip):
    """1,024 tokens, top-k 256 so that selection engages in both query
    blocks: forward + backward for the described chip; the three inner
    scopes name instructions under ``mx.attn.dsa``; the k-th largest
    score comes from a loop of counts, no ``sort``."""
    from mxbench import scopes
    loss, args = _sparse_mixer(one_chip, 1024, 256)
    text = jax.jit(jax.grad(loss, argnums=tuple(range(13)))) \
        .lower(*args).compile().as_text()
    assert not mosaic_calls(text)
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
    loss, args = _sparse_mixer(one_chip, length, top_k)
    return jax.jit(jax.value_and_grad(loss, argnums=tuple(range(13)))) \
        .lower(*args).compile().as_text()


# the selector's kernels (ops/pallas_index_scores.py): the scores'
# forward in the forward and again in the backward rule, one backward
_INDEX_KERNELS = {"pallas_index_scores_fwd": 2, "pallas_index_scores_bwd": 1}
# a query block's index product a head, as the composition holds it
_PER_HEAD_SCORES = re.compile(r"f32\[(1,)?16,512,\d+\]|f32\[\d+,512,16\]")


# two and four query blocks in tier-1; the published length's sixteen
# (37 Mosaic calls, two minutes of one core) makes every same assertion:
# `slow` here, and compiled on the chip by the Keye-VL cell
@pytest.mark.parametrize("length, top_k", [
    (1024, 256), (2048, 512),
    pytest.param(8192, 2048, marks=pytest.mark.slow)])
def test_sparse_gqa_kernels_compile_under_the_scope_the_benchmark_reads(
        one_chip, compiled_mode, length, top_k):
    """Compiled, not interpreted, the mixer takes the flash kernels:
    Mosaic accepts them within the VMEM limit, every custom call is
    named ``pallas_sparse_gqa_*`` (what ``pallas_ms`` sums) and placed
    under ``mx.attn.sparse`` by the benchmark's own reader (the forward
    kernel once: the recomputation does not run it again; the
    probabilities a query block in the forward and again in the
    backward rule; one backward kernel), and no score block is left in
    the program. The index scores likewise: three calls named
    ``pallas_index_scores_*`` whatever the number of blocks, under
    ``mx.attn.index`` (what ``index_scores_ms`` reads), and no index
    product a head is left either."""
    from mxbench import scopes
    text = _sparse_mixer_gradient(one_chip, length, top_k)
    calls = mosaic_calls(text)
    names = ("mx.attn.index", "mx.attn.select", "mx.attn.sparse",
             "mx.attn.dsa")
    placed = scopes.scope_map(text, names)
    assert set(placed.values()) == set(names)
    kernels = {name: scope for name, scope in placed.items()
               if name.startswith("pallas_sparse_gqa_")}
    index = {name: scope for name, scope in placed.items()
             if name.startswith("pallas_index_scores_")}
    blocks = length // 512
    assert len(kernels) == 2 + 2 * blocks
    assert len(calls) == len(kernels) + len(index)
    assert set(kernels.values()) == {"mx.attn.sparse"}
    assert set(index.values()) == {"mx.attn.index"}
    kinds = [name.split(".")[0] for name in list(kernels) + list(index)]
    assert kinds.count("pallas_sparse_gqa_fwd") == 1
    assert kinds.count("pallas_sparse_gqa_bwd") == 1
    assert kinds.count("pallas_sparse_gqa_probs") == 2 * blocks
    assert {k: kinds.count(k) for k in _INDEX_KERNELS} == _INDEX_KERNELS
    for line in calls:
        scope = "index" if "pallas_index_scores" in line else "sparse"
        assert "mx.attn." + scope in line.split('op_name="')[1].split('"')[0]
    assert "f32[1,4,8,512," not in text
    assert "f32[1,32,512," not in text
    assert not _PER_HEAD_SCORES.search(text)


# temporaries, arguments, outputs of the toy Keye-VL step on PR 52's parent
_KEYE_TOY_BYTES = (15484416, 3672064, 3673600)


def _toy_step(one_chip, name, length=64, **widths):
    """A zoo decoder through ``trace_block`` as ``ShardedTrainStep``
    traces it (its losses, bf16 compute, AdamW through the shared
    ``_apply_update``), at the configuration's toy widths (but for
    ``widths``), two sequences of ``length`` tokens: (the compiled step,
    the configuration's module, its auxiliary states' names)."""
    from mxbench import manifest
    from mxnet_tpu.parallel.sharded import _apply_update, trace_block
    sizes, cfgmod, _ = manifest.config(name)
    sizes = dict(sizes, **dict(sizes["toy"], **widths))
    net, loss, n_in = cfgmod.sharded_parts(sizes, 0.0, length)
    fn, data_names, names, _ = trace_block(net, loss, n_in)
    shapes = {n: p.shape for block in (net, loss.head)
              for n, p in block.collect_params().items()}
    aux_names = [n for n in names if n in fn._aux_names]
    names = [n for n in names if n not in fn._aux_names]

    def sds(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dt, sharding=one_chip)

    hp = dict(lr=1e-5, momentum=0.9, wd=1e-6, beta1=0.9, beta2=0.95,
              epsilon=1e-8, clip_gradient=-1.0, rescale_grad=1.0)

    def loss_of(params, aux, data):
        feed = {k: v.astype(BF) for k, v in params.items()}
        feed.update(zip(data_names, data))
        feed.update(aux)
        out, new_aux = fn(feed)
        return sum32(out[0]), new_aux

    def step(params, aux, states, t, *data):
        (value, new_aux), grads = jax.value_and_grad(
            loss_of, has_aux=True)(params, aux, list(data))
        new = {k: _apply_update("adamw", hp, w, grads[k], states[k], t)
               for k, w in params.items()}
        return value, new_aux, new

    params = {n: sds(shapes[n]) for n in names}
    aux = {n: sds(shapes[n]) for n in aux_names}
    ids = sds((2, length), jnp.int32)
    return jax.jit(step).lower(
        params, aux, {n: (params[n], params[n]) for n in names}, sds(()),
        ids, ids).compile(), cfgmod, aux_names


@pytest.mark.parametrize("name", [
    "keye_vl2_30b_a3b", "laguna_xs2_33b_a3b", "lfm2_24b_a2b",
    "nemotron_twotower_30b_a3b"])
def test_a_whole_toy_decoder_step_compiles_for_the_chip(one_chip, compiled,
                                                        name):
    """The Keye-VL step, whose mixers keep what they kept, and the three
    whose mixers keep a product that reads their normed input (the
    rotary mixer's v, the short-convolution mixer's ``W_in``, the
    Mamba-2 mixer's ``in_proj``: Laguna-XS.2, LFM2, Nemotron): every
    scope the configuration's readers name is in the
    compiled program, and at toy widths nothing of Mosaic's."""
    step, cfgmod, _ = compiled(("toy step", name),
                               lambda: _toy_step(one_chip, name))
    text = step.as_text()
    assert not mosaic_calls(text)
    for scope in cfgmod.SCOPES:
        assert scope in text, scope


def test_the_toy_keye_step_takes_the_bytes_it_took(one_chip, compiled):
    """The sparse mixer shares ``_normed_rotary_qkv`` with the rotary
    one and keeps no projection of its own (its cell stands 16 MB under
    the chip): the toy step's buffers are, byte for byte, those of the
    tree before the rotary, short-convolution and Mamba-2 mixers kept a
    product (PR 52's parent, read by this test's own code there)."""
    step, _, aux_names = compiled(
        ("toy step", "keye_vl2_30b_a3b"),
        lambda: _toy_step(one_chip, "keye_vl2_30b_a3b"))
    assert len(aux_names) == 2 * 2          # two states a layer, two layers
    m = step.memory_analysis()
    assert (m.temp_size_in_bytes, m.argument_size_in_bytes,
            m.output_size_in_bytes) == _KEYE_TOY_BYTES


def test_the_toy_keye_step_on_heads_the_kernels_serve_holds_them_all(
        one_chip, compiled_mode):
    """The toy step with the published heads (128 lanes; index heads of
    64, in pairs) over two query blocks, compiled and not interpreted:
    a layer's attention kernels and the selector's three, the latter
    under ``mx.attn.index``, in the whole step as ``ShardedTrainStep``
    traces it (two sequences: the kernels' batch axis)."""
    from mxbench import manifest
    toy = manifest.config("keye_vl2_30b_a3b")[0]["toy"]
    step, _, _ = _toy_step(
        one_chip, "keye_vl2_30b_a3b", length=1024, head_dim=128,
        rope_scaling=dict(toy["rope_scaling"], mrope_section=[16, 24, 24]),
        sa_config=dict(toy["sa_config"], indexer_head_dim=64))
    text = step.as_text()
    names = [line.split("=")[0].strip().lstrip("%").split(".")[0]
             for line in mosaic_calls(text)]
    names = [n for n in names if "_sparse_gqa_" in n or "_index_scores_" in n]
    layers = 2
    assert {n: names.count(n) for n in set(names)} == {
        "pallas_sparse_gqa_fwd": layers, "pallas_sparse_gqa_bwd": layers,
        "pallas_sparse_gqa_probs": layers * 2 * 2,
        **{k: layers * n for k, n in _INDEX_KERNELS.items()}}
    for line in mosaic_calls(text):
        if "pallas_index_scores" in line:
            assert "mx.attn.index" in line.split('op_name="')[1].split('"')[0]
    assert not re.search(r"f32\[(2,)?4,512,\d+\]", text)


# what the compiler may give the Keye-VL cell's whole step in temporaries
_KEYE_STEP_TEMPORARIES = 4.5e9


@pytest.mark.slow
def test_the_keye_cell_s_whole_step_stays_under_its_bytes(one_chip):
    """``tools/step_bytes.py keye_vl2_30b_a3b_midtrain_s8192`` in this
    process (two to three minutes, 8 GB): the cell's step as
    ``ShardedTrainStep`` builds it, compiled for the described chip,
    fits, and its temporaries stay under a bound. Read here: 9,873,819,136
    bytes on PR 54's parent (arguments 7,910,355,968, code 210,776,576:
    16 MB under the chip by ``memory_peak_bytes``, nineteen of the twenty
    largest buffers at the heap's peak the selector's per-head index
    scores); **3,733,122,560** since PR 54 sums those scores over their
    heads in VMEM (arguments the same, code 649,655,296). The bound
    leaves a fifth of room: a change that brings a gigabyte back has
    to say so here (ROADMAP A11)."""
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "step_bytes", os.path.join(os.path.dirname(__file__), os.pardir,
                                   "tools", "step_bytes.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    found = tool.step_bytes("keye_vl2_30b_a3b_midtrain_s8192")
    assert found["layers"] == 6
    assert found["temporaries"] <= _KEYE_STEP_TEMPORARIES, found


# ---------------------------------------------------------------------------
# Mellum 2's two mixers at the published widths (hidden 2304, 32 / 4
# heads of 128, 16 of 64 experts of width 896) and the cell's 16,384
# tokens: what a step of the long-context cell is made of
# ---------------------------------------------------------------------------
def _rotary_mixer_gradient(one_chip, length, **attrs):
    from mxnet_tpu.ops import get_op
    op = get_op("_contrib_rotary_gqa_mixer").impl
    hidden, h, kv, d = 2304, 32, 4, 128
    args = described(one_chip, (1, length, hidden), (hidden,),
                     (h * d, hidden), (kv * d, hidden), (kv * d, hidden),
                     (hidden, h * d), (d,), (d,))
    return jax.jit(jax.value_and_grad(
        lambda *a: sum32(op(*a, num_heads=h, num_kv_heads=kv, head_dim=d,
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
    calls = mosaic_calls(text)
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


# ---------------------------------------------------------------------------
# LFM2's widths (hidden 2048; 32 / 8 attention heads of 64 lanes; a gated
# short convolution of three taps)
# ---------------------------------------------------------------------------
def test_heads_of_64_lanes_take_the_kernel_two_a_step(one_chip,
                                                      compiled_mode):
    """The op's gradient at LFM2's heads (32 over 8, 64 lanes; four
    sequences of 8,192, the cell's batch) takes the flash kernel: Mosaic
    accepts the step of two heads (the lane roll, the selects, the
    accumulators' 64-row reads), two custom calls under
    ``mx.attn.causal``; an odd group of such heads keeps the
    composition."""
    from mxbench import scopes
    from mxnet_tpu.ops import get_op
    op = get_op("_contrib_causal_gqa_attention").impl
    grad = jax.grad(lambda *a: sum32(op(*a)), argnums=(0, 1, 2))
    text = jax.jit(grad).lower(*described(
        one_chip, (4, 8192, 32, 64), (4, 8192, 8, 64),
        (4, 8192, 8, 64))).compile().as_text()
    calls = mosaic_calls(text)
    placed = scopes.scope_map(text, ["mx.attn.causal"])
    names = sorted(name for name in placed
                   if name.startswith("pallas_causal_gqa_"))
    assert len(calls) == len(names) == 2
    assert names[0].startswith("pallas_causal_gqa_bwd")
    assert names[1].startswith("pallas_causal_gqa_fwd")
    odd = jax.jit(grad).lower(*described(
        one_chip, (1, 1024, 24, 64), (1, 1024, 8, 64),
        (1, 1024, 8, 64))).compile().as_text()
    assert not mosaic_calls(odd)


def test_the_short_conv_mixer_compiles_under_its_two_scopes(one_chip):
    """An XLA composition (no Mosaic call) whose gates and taps stand
    under ``mx.conv.gate`` and whose products under ``mx.conv``, in the
    compiled program the benchmark's reader maps."""
    from mxbench import scopes
    from mxnet_tpu.ops import get_op
    op = get_op("_contrib_short_conv_mixer").impl
    grad = jax.grad(lambda *a: sum32(op(*a, eps=1e-5)),
                    argnums=(0, 2, 3, 4))
    text = jax.jit(grad).lower(*described(
        one_chip, (1, 2048, 2048), (2048,), (6144, 2048), (2048, 3),
        (2048, 2048))).compile().as_text()
    assert not mosaic_calls(text)
    placed = set(scopes.scope_map(text, ["mx.conv.gate", "mx.conv"])
                 .values())
    assert placed == {"mx.conv.gate", "mx.conv"}
