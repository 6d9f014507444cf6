"""Chipless compiles, whole training steps of four decoders at toy
widths for a described v5e chip, as ``ShardedTrainStep`` traces them (see
tests/test_chip_compile_bert.py for what such a compile can and cannot
show; the decoders' kernels alone: ``test_chip_compile_decoder.py``).
"""
import re

import pytest

import jax
import jax.numpy as jnp

from numerics import BF, mosaic_calls, sum32

# the selector's kernels a layer (tests/test_chip_compile_decoder.py
# holds them to the same count in the mixer alone)
_INDEX_KERNELS = {"pallas_index_scores_fwd": 2, "pallas_index_scores_bwd": 1}

# temporaries, arguments, outputs of the toy Keye-VL step: arguments and
# outputs as on PR 52's parent; temporaries 15484416 there and until
# PR 60, whose expert mixers make the buffer's maps once and keep them
# with the routing (the attention mixer is as it was)
_KEYE_TOY_BYTES = (15032832, 3672064, 3673600)


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
    product (PR 52's parent, read by this test's own code there), but
    for what PR 60 took off the expert mixers' temporaries."""
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
