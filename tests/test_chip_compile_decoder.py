"""Chipless compiles, the decoders' attention, scan and selector at
their published widths for a described v5e chip (see
tests/test_chip_compile_bert.py for what such a compile can and cannot
show): the causal, windowed and sparse flash kernels, the Mamba-2 scan's
kernels, each under the scope the benchmark reads (the cells' other
attention and convolution mixers: ``test_chip_compile_mixers.py``; whole
toy steps of four decoders: ``test_chip_compile_steps.py``).
"""
import re

import pytest

import jax
import jax.numpy as jnp

from numerics import described, mosaic_calls, sum32


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
