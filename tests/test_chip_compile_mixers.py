"""Chipless compiles, the decoder cells' attention and convolution mixers
at their published widths for a described v5e chip, each at its cell's
tokens and under the scope the benchmark reads (see
tests/test_chip_compile_bert.py for what such a compile can and cannot
show; the kernels alone: ``test_chip_compile_decoder.py``; the expert
mixers: ``test_chip_compile_experts.py``).
"""
import pytest

import jax

from numerics import described, mosaic_calls, sum32


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


# ---------------------------------------------------------------------------
# GLM-4.7-Flash's latent attention at the published widths (hidden 2048,
# 20 heads of 192 + 64 / 256 lanes through bottlenecks of 768 and 512)
# and the cell's 8,192 tokens
# ---------------------------------------------------------------------------
def _mla_mixer_gradient(one_chip, length):
    from mxnet_tpu.ops import get_op
    op = get_op("_contrib_mla_mixer").impl
    hidden, h, qr, kvr, nope, rope, vd = 2048, 20, 768, 512, 192, 64, 256
    args = described(
        one_chip, (1, length, hidden), (hidden,), (qr, hidden), (qr,),
        (h * (nope + rope), qr), (kvr + rope, hidden), (kvr,),
        (h * (nope + vd), kvr), (hidden, h * vd))
    return jax.jit(jax.value_and_grad(
        lambda *a: sum32(op(*a, num_heads=h, qk_nope_head_dim=nope,
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
    calls = mosaic_calls(text)
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


# ---------------------------------------------------------------------------
# Laguna-XS.2's attention at the published widths (hidden 2048, 48 / 64
# query heads over 8 key-value heads of 128, a gate a head) and the
# cell's 8,192 tokens
# ---------------------------------------------------------------------------
def _gated_mixer_gradient(one_chip, heads, **attrs):
    from mxnet_tpu.ops import get_op
    op = get_op("_contrib_rotary_gqa_mixer").impl
    length, hidden, kv, d = 8192, 2048, 8, 128
    args = described(one_chip, (1, length, hidden), (hidden,),
                     (heads * d, hidden), (kv * d, hidden), (kv * d, hidden),
                     (hidden, heads * d), (heads, hidden))
    return jax.jit(jax.value_and_grad(
        lambda *a: sum32(op(*a[:6], gate_weight=a[6], num_heads=heads,
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
    an input; the mixer's temporaries stay under 1.25 GB (17 MB of them
    the v projection that a step keeps)."""
    from mxbench import scopes
    compiled = _gated_mixer_gradient(one_chip, heads, **attrs)
    text = compiled.as_text()
    calls = mosaic_calls(text)
    placed = scopes.scope_map(text, ["mx.attn.gate", scope, other,
                                     "mx.attn.rotary"])
    kernels = {name: s for name, s in placed.items()
               if name.startswith("pallas_causal_gqa_")}
    assert len(calls) == len(kernels) == 2
    assert set(kernels.values()) == {scope}
    assert other not in placed.values()
    assert {"mx.attn.rotary", "mx.attn.gate"} <= set(placed.values())
    assert compiled.memory_analysis().temp_size_in_bytes < 1.25e9
    assert "f32[1,8,%d,512," % (heads // 8) not in text     # no score block


# ---------------------------------------------------------------------------
# granite-4.0-h-micro's two mixers at the published widths (hidden 2048;
# 64 scan heads of 64 lanes in one group, state 128; 32 / 8 attention
# heads of 64 lanes with the model's own score factor) on a packed row
# of 8,192 tokens: the document ids as a traced operand
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind", ["mamba", "attention"])
def test_granite_s_mixers_take_their_kernels_on_a_packed_row(
        one_chip, compiled_mode, kind):
    """Mosaic accepts the scan kernels at a lane tile of 4,096 (64 heads
    in one group, chunks of 128: the forward that writes its states and
    the backward; a whole step runs the plain forward too) and the
    causal kernels with the
    ids as a row and a column operand (the integer compare broadcast
    both ways, the 4 MB column beside k and v); each under the scope
    the benchmark reads. (That the scan stands down to the composition
    at the published chunk of 256 is ``tests/test_granite_hybrid.py``'s,
    by the kernels' own rule.)"""
    import jax.numpy as jnp
    from mxbench import scopes
    from mxnet_tpu.ops import get_op
    hidden, length = 2048, 8192
    ids = jax.ShapeDtypeStruct((1, length), jnp.int32, sharding=one_chip)
    if kind == "mamba":
        op = get_op("_contrib_mamba2_mixer").impl
        shapes = [(1, length, hidden), (hidden,), (8512, hidden), (4352, 4),
                  (4352,), (64,), (64,), (64,), (4096,), (hidden, 4096)]
        attrs = dict(num_heads=64, head_dim=64, n_groups=1, state_size=128)
        scope, kernel, calls = "mx.mamba2.ssd", "pallas_ssd_", 2
    else:
        op = get_op("_contrib_gqa_mixer").impl
        shapes = [(1, length, hidden), (hidden,), (hidden, hidden),
                  (512, hidden), (512, hidden), (hidden, hidden)]
        attrs = dict(num_heads=32, num_kv_heads=8, head_dim=64,
                     scale=0.015625)
        scope, kernel, calls = "mx.attn.causal", "pallas_causal_gqa_", 2

    def text(**more):
        return jax.jit(jax.grad(
            lambda seg, *a: sum32(op(*a, segment_ids=seg, **attrs, **more)),
            argnums=tuple(range(1, len(shapes) + 1)))).lower(
                ids, *described(one_chip, *shapes)).compile().as_text()

    got = text(chunk_size=128) if kind == "mamba" else text()
    placed = scopes.scope_map(got, [scope])
    assert len(mosaic_calls(got)) == calls == len(
        [name for name in placed if name.startswith(kernel)])
