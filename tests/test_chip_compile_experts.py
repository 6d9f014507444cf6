"""Chipless compiles, the expert mixers of the decoder cells at their
published widths for a described v5e chip (see
tests/test_chip_compile_bert.py for what such a compile can and cannot
show): the composition, the grouped kernels of ops/pallas_grouped_mlp.py
and the slot sum's window kernel of ops/pallas_moe_rows.py, with
GLM-4.7-Flash's dense MLP (the cells' attention mixers:
``test_chip_compile_mixers.py``).
"""
import re

import pytest

import jax
import jax.numpy as jnp

from numerics import BF, described, mosaic_calls, sum32


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
        return sum32(y)

    args = described(one_chip, (t, hidden), (routed, hidden),
                     ((routed,), jnp.float32), (held, width, hidden),
                     (held, hidden, width))
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 3, 4))) \
        .lower(*args).compile()
    assert not mosaic_calls(compiled.as_text())
    # the sorted path's FLOPs follow the buffer (20 blocks of 512 rows),
    # not buffer x experts: 5 products of 10240 x 2688 x 1856 (forward
    # and backward, the last forward product dead under a sum), beside
    # the dense path's loop under the conditional, whose body (one
    # expert over the 8192 rows: forward, recomputation, backward) is
    # counted once
    one = 2 * 10240 * hidden * width
    flops = compiled.cost_analysis()["flops"]
    assert 5 * one < flops < (5 + 7 * 8192 / 10240) * one * 1.2


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
        return sum32(y)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        *_expert_mixer_args(one_chip, length, hidden, width, held, routed,
                            2)).compile()
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
def _expert_mixer_args(sharding, length, hidden, width, held, routed, mul,
                       shared=0):
    """The hidden state, the norm's weight, the router's and the held
    experts' two; with a ``shared`` width, the shared expert's two."""
    return described(
        sharding, (1, length, hidden), (hidden,), (routed, hidden),
        (held, mul * width, hidden), (held, hidden, width),
        *([(2 * shared, hidden), (hidden, shared)] if shared else []))


def _expert_mixer_gradient(held, operands=5, **attrs):
    """Value and gradient of the expert mixer to its ``operands`` (no
    score bias; from the sixth on, the shared expert's weights)."""
    from mxnet_tpu.ops import get_op
    op = get_op("_contrib_moe_mixer").impl

    def loss(x, g, r, w1, w2, *shared):
        y, _ = op(x, g, r, jnp.zeros((2, held), jnp.float32), w1, w2, None,
                  *shared, eps=1e-6, **attrs)
        return sum32(y)

    return jax.value_and_grad(loss, argnums=tuple(range(operands)))


def _cell_gradient(compiled, one_chip, cell):
    """The expert mixer's value and gradient at a cell's sizes and
    attributes, compiled for the described chip once for the tests that
    read it (each asks for ``compiled_mode``, and the key says so: the
    kernels, not their interpretation)."""
    sizes, shared, attrs, _, _ = ROWS_CELLS[cell]
    args = _expert_mixer_args(one_chip, *sizes, shared=shared)
    return compiled(("expert mixer", "compiled_mode", cell), lambda: jax.jit(
        _expert_mixer_gradient(sizes[3], len(args), **attrs))
        .lower(*args).compile())


EXPERT_CELLS = {
    # length, hidden, width, held, routed, rows of w1 a width, blocks
    "mellum2": ((16384, 2304, 896, 16, 64, 2), 144,
                dict(top_k=8, score_func="softmax", activation="swiglu")),
    "keye_vl": ((8192, 2048, 768, 16, 128, 2), 48,
                dict(top_k=8, score_func="softmax", activation="swiglu")),
}


@pytest.mark.parametrize("cell", sorted(EXPERT_CELLS))
def test_expert_mixer_takes_the_grouped_kernels_under_its_scope(
        one_chip, compiled_mode, compiled, cell):
    """Mosaic accepts the five kernels, the skipped tail's branches
    and clamped index maps and the activation and its ``jax.vjp`` traced
    in the bodies with them, at both cells' widths; a step's seven calls
    (``up`` and ``nt`` forward, ``up`` again, keeping ``pre``, in the
    mixer's recomputation, ``dh``, ``nn`` and two ``dw`` backward) are
    placed under the scope the benchmark reads; no gathered copy of a
    weight and no float32 gradient a block exists; between the products
    no float32 array of the buffer's length but ``pre``, which only the
    kernels write and read (``dh`` stays in VMEM; of the slot weights'
    gradient a float32 column a tile of the width leaves); and the
    Mellum 2 mixer's gradient needs 1.7 GB of temporaries where the
    chunked composition needs 3.63."""
    from mxbench import scopes
    sizes, blocks, _ = EXPERT_CELLS[cell]
    length, hidden, width, held, routed, mul = sizes
    program = _cell_gradient(compiled, one_chip, cell)
    text = program.as_text()
    calls = mosaic_calls(text)
    placed = scopes.scope_map(text, ["mx.moe.experts", "mx.moe"])
    # (a call traced inside the backward's ``jax.vjp`` is named
    # ``jvp_pallas_grouped_mlp_up_``)
    kernels = {name: s for name, s in placed.items()
               if "pallas_grouped_mlp_" in name}
    # (beside them the slot sum's two calls: the next test)
    assert len(kernels) == 7 and len(calls) == 9
    assert set(kernels.values()) == {"mx.moe.experts"}
    assert sorted(re.search("pallas_grouped_mlp_(dh|dw|nn|nt|up)", n).group(1)
                  for n in kernels) \
        == ["dh"] + ["dw"] * 2 + ["nn", "nt"] + ["up"] * 2
    # the two scalar prefetches lead each call's operands: the expert of
    # each block, and how many blocks hold a routed row (the rest are
    # skipped)
    prefetched = "operand_layout_constraints={s32[%d]{0}, s32[1]{0}, " % blocks
    assert sum(prefetched in c for c in calls) == 7
    for out, inner in ((mul * width, hidden), (hidden, width)):
        assert "bf16[%d,%d,%d]" % (blocks, out, inner) not in text
        assert "f32[%d,%d,%d]" % (blocks, out, inner) not in text
    # ``dh`` (rows, width) in float32 is nowhere, ``pre`` in neither
    # layout outside the kernels: the line that defines or reads it is a
    # grouped call's, or takes an element of the tuple one returned
    rows = blocks * 512
    for shape in ((rows, width), (rows, mul * width), (blocks, 512, width),
                  (blocks, 512, mul * width)):
        assert "f32[%s]" % ",".join(map(str, shape)) not in text
    kept = [line for line in text.splitlines()
            if "f32[%d,%d,%d]" % (mul, rows, width) in line]
    assert kept and all(
        "pallas_grouped_mlp_" in line.split(" = ")[0]
        or " get-tuple-element(%jvp_pallas_grouped_mlp_up_" in line
        for line in kept), kept
    memory = program.memory_analysis()
    # nothing but its inputs crosses the overflow ``cond`` (a copy of
    # the weights and a zero array of their size did: 168 MB of program
    # at the Keye-VL widths for 17)
    assert memory.generated_code_size_in_bytes < 40e6
    if cell == "mellum2":
        assert memory.temp_size_in_bytes < 1.75e9


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
    # by the Mosaic calls, never by the whole text: its table of source
    # files names whatever this process traced before
    # (tests/test_pallas_grouped_mlp.py, on a worker that ran it)
    calls = mosaic_calls(text)
    assert not any("pallas_grouped_mlp" in c for c in calls)
    assert len(calls) == 2 and all("pallas_moe_rows_sum" in c for c in calls)


# the expert mixer's gradient with the slot sum's window kernel in it:
# buffer rows, (tokens, top_k), hidden; and the temporaries of the same
# compile with the kernel stood down (PR 43's readings: 1,846,272,000 /
# 593,056,768 / 724,051,456 bytes; with it 1,832,087,040 / 491,890,176 /
# 695,194,112; since PR 57, the activation inside the grouped kernels,
# 1.699 / 0.492 / 0.670 GB)
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
def test_expert_mixer_sums_its_slots_by_the_window_kernel(
        one_chip, compiled_mode, compiled, cell):
    """Mosaic accepts ``pallas_moe_rows_sum`` at the cells' shapes; its
    two calls a layer (the forward's sum, which the recomputation does
    not need again, and the pullback of the gather in the backward, whose
    rule is traced after the caller's scopes have closed) are placed
    under the scope the benchmark reads; no (tokens, top_k, hidden)
    gather is left (the three gathers into the buffer are XLA's); and
    the gradient's temporaries stay under what the same compile took
    with the kernel stood down."""
    from mxbench import scopes
    sizes, _, attrs, cap, bound = ROWS_CELLS[cell]
    length, hidden, width, held, routed, mul = sizes
    program = _cell_gradient(compiled, one_chip, cell)
    text = program.as_text()
    placed = scopes.scope_map(text, ["mx.moe.experts", "mx.moe"])
    sums = {name: s for name, s in placed.items()
            if "pallas_moe_rows_sum" in name}
    assert len(sums) == 2 and set(sums.values()) == {"mx.moe.experts"}
    assert sum("transpose(jvp" in line for line in mosaic_calls(text)
               if "pallas_moe_rows_sum" in line) == 1
    top_k = attrs["top_k"]
    assert "bf16[%d,%d,%d]" % (length, top_k, hidden) not in text
    assert "bf16[%d,%d]" % (length * top_k, hidden) not in text
    gathers = [line for line in text.splitlines() if " gather(" in line
               and "bf16[%d,%d]" % (cap, hidden) in line.split(" gather(")[0]]
    assert len(gathers) == 3
    assert program.memory_analysis().temp_size_in_bytes < bound


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
    (args[0],) = described(NamedSharding(mesh, P("dp")),
                           (4,) + args[0].shape[1:])
    with auto_partitioned(mesh):
        text = jax.jit(_expert_mixer_gradient(sizes[3], **attrs)) \
            .lower(*args).compile().as_text()
    assert not mosaic_calls(text)


# ---------------------------------------------------------------------------
# GLM-4.7-Flash's experts and dense MLP at the published widths (hidden
# 2048, a dense MLP of 10,240, 8 of 64 experts of width 1,536 beside a
# shared one) and the cell's 8,192 tokens
# ---------------------------------------------------------------------------
def test_glm_expert_and_dense_mixers_compile_at_published_widths(
        one_chip, compiled_mode):
    """The expert op's fourth combination (sigmoid scores with a
    selection bias, SwiGLU experts, a SwiGLU shared expert, x 1.8) at 8
    of 64 experts of width 1,536 (the LFM2 cell's too: two tiles of 768
    a piece, the widest working set the kernels have beside Mellum
    2's): the grouped kernels' seven calls
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
        return sum32(y)

    args = _expert_mixer_args(one_chip, length, hidden, width, held, routed,
                              2) + described(
        one_chip, ((routed,), jnp.float32), (2 * width, hidden),
        (hidden, width))
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4, 6, 7))) \
        .lower(*args).compile().as_text()
    placed = scopes.scope_map(text, ["mx.moe.experts", "mx.moe"])
    kernels = {name: s for name, s in placed.items()
               if "pallas_grouped_mlp_" in name}
    assert len(kernels) == 7 and set(kernels.values()) == {"mx.moe.experts"}
    assert "s32[24]" in text
    assert "mx.moe" in placed.values()

    mlp = get_op("_contrib_glu_mlp_mixer").impl
    compiled = jax.jit(jax.value_and_grad(
        lambda *a: sum32(mlp(*a, eps=1e-5)), argnums=(0, 1, 2, 3))).lower(
        *described(one_chip, (1, length, hidden), (hidden,),
                   (2 * 10240, hidden), (hidden, 10240))).compile()
    assert set(scopes.scope_map(compiled.as_text(), ["mx.mlp"]).values()) \
        == {"mx.mlp"}
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


# ---------------------------------------------------------------------------
# Laguna-XS.2's experts at the published widths (hidden 2048, 32 of 256
# experts of width 512 beside a shared one) and the cell's 8,192 tokens
# ---------------------------------------------------------------------------
def test_laguna_expert_mixer_fills_a_quarter_of_its_blocks(
        one_chip, compiled_mode, compiled):
    """The expert op's fifth combination (softmax scores renormalised
    and x 2.5, SwiGLU experts, a SwiGLU shared expert) at 32 of 256
    experts of width 512: an expert's even share of 8,192 tokens at top
    8 is 256 rows, half a block, so the buffer is 64 blocks of 512
    (twice the share and a block an expert); the grouped kernels' seven
    calls under ``mx.moe.experts``, the shared expert outside it."""
    from mxbench import scopes
    program = _cell_gradient(compiled, one_chip, "laguna")
    text = program.as_text()
    placed = scopes.scope_map(text, ["mx.moe.experts", "mx.moe"])
    kernels = {name: s for name, s in placed.items()
               if "pallas_grouped_mlp_" in name}
    assert len(kernels) == 7 and set(kernels.values()) == {"mx.moe.experts"}
    assert "s32[64]" in text
    assert "mx.moe" in placed.values()
    assert program.memory_analysis().temp_size_in_bytes < 1.5e9
