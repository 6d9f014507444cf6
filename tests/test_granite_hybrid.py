"""The zoo's Granite 4.0-H dense model (gluon/model_zoo/granite_hybrid.py)
on packed rows at toy widths on the CPU: that a packed row of three
documents gives, position for position, what the three documents give
alone (the conv's taps, the scan and the attention each, values and
input gradients, the XLA compositions in float32 and the kernels
interpreted in bf16); that ids of one document, or none, and the
default scale change nothing of what an op gave before it took them;
the whole model against the benchmark's plain float32 reference
(per-position logits, the loss, the gradient of every parameter, AdamW
steps through ``ShardedTrainStep``), as compositions in float32 and with
the scan and attention kernels engaged in bf16; the four multipliers one
by one; the count of documents as an auxiliary state and a gauge; and
what ``ssd_available`` decides for a group of 64 heads."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from decoder_harness import OPT, ids as _ids
from mxbench import manifest
from mxnet_tpu import autograd, telemetry
from mxnet_tpu.gluon.model_zoo import granite_hybrid as zoo
from mxnet_tpu.ops import (decoder_ops as D, get_op, pallas_causal_gqa as P,
                           pallas_ssd as S)
from mxnet_tpu.parallel import MeshConfig, P as Spec, ShardedTrainStep, \
    make_mesh
from numerics import (BF, F32, attention_ref, close, near, qkv, rand,
                      reference, value_and_grads)

NAME = "granite_4_0_h_micro"
REF = reference(NAME)
CFGMOD = manifest.load_module("configs", NAME + ".py")
MAMBA, ATTN = zoo.KINDS
# the file's toy: 8 scan heads of 16 in one group, 4 query heads of 16
# over 2 key-value heads, chunks of 16
CFG = CFGMOD.model_cfg(dict(
    manifest.load_json("configs", NAME + ".json"),
    **manifest.load_json("configs", NAME + ".json")["toy"]))
# widths the kernels serve: 2 scan heads of 64 (one lane tile a group),
# state and chunk 128, 4 query heads of 64 over 2 key-value heads
KERNEL_CFG = dict(CFG, hidden_size=256, mamba_n_heads=8, mamba_d_head=64,
                  mamba_d_state=128, scan_chunk=128, num_attention_heads=4,
                  num_key_value_heads=2, shared_intermediate_size=128,
                  layer_types=[MAMBA, ATTN], num_hidden_layers=2,
                  vocab_size=128)
DOCS = (100, 250, 162)          # three documents of a row of 512


def _segments(lengths, batch=1):
    row = np.repeat(np.arange(len(lengths)), lengths).astype(np.int32)
    return jnp.asarray(np.tile(row, (batch, 1)))


def _alone(fn, lengths, *arrays):
    """``fn`` of each document's own slice of the arrays (along axis
    1), laid end to end."""
    out, at = [], 0
    for n in lengths:
        out.append(fn(*(a[:, at:at + n] for a in arrays)))
        at += n
    return jnp.concatenate(out, axis=1)


def _packed_equals_alone(packed, alone, lengths, arrays, check, cot_seed=7):
    """Values and the gradient to every array, the packed row's against
    the documents' alone."""
    (cot,) = rand(cot_seed, jax.eval_shape(
        lambda *a: packed(*a), *arrays).shape)
    got = value_and_grads(packed, *arrays, cot=cot)
    want = value_and_grads(lambda *a: _alone(alone, lengths, *a), *arrays,
                           cot=cot)
    check(got, want)


# ---------------------------------------------------------------------------
# a packed row gives what its documents give alone
# ---------------------------------------------------------------------------
def test_the_conv_s_taps_stop_at_a_document_s_start():
    lengths = (5, 9, 2, 7)
    x, w, b = rand(1, (2, 23, 12), (12, 4), (12,))
    seg = _segments(lengths, 2)
    _packed_equals_alone(
        lambda x: D._causal_conv1d(x, w, b, segments=seg),
        lambda x: D._causal_conv1d(x, w, b), lengths, (x,),
        lambda g, w_: close(g, w_, 1e-6))
    # and the op by its name, ids given by keyword
    op = get_op("_contrib_causal_conv1d").impl
    close(op(x, w, b, segment_ids=seg), D._causal_conv1d(x, w, b,
                                                         segments=seg), 0)
    assert not np.allclose(op(x, w, b, segment_ids=seg), op(x, w, b))


def _scan_args(seed, length, heads, p, n, dtype=F32, batch=1):
    x, bm, cm = rand(seed, (batch, length, heads, p), (batch, length, 1, n),
                     (batch, length, 1, n), dtype=dtype, scale=0.5)
    (dt,) = rand(seed + 1, (batch, length, heads))
    a, d = -jnp.linspace(1.0, 8.0, heads), jnp.linspace(0.5, 1.5, heads)
    return x, jax.nn.softplus(dt - 2.0), bm, cm, a, d


def test_the_scan_s_state_is_reset_at_a_document_s_start():
    """The composition in float32: chunks of 8, documents that start
    inside chunks, at a chunk's first step and one token before a
    chunk's end."""
    lengths = (13, 3, 22, 9, 1)
    x, dt, bm, cm, a, d = _scan_args(2, sum(lengths), 4, 8, 8, batch=2)
    seg = _segments(lengths, 2)

    def scan(x, dt, bm, cm, segments=None):
        return D._scan(x, dt, a, bm, cm, d, 8, segments)

    _packed_equals_alone(
        lambda *t: scan(*t, segments=seg), scan, lengths, (x, dt, bm, cm),
        lambda g, w: close(g, w, 2e-5))
    assert not np.allclose(scan(x, dt, bm, cm, seg), scan(x, dt, bm, cm),
                           atol=1e-3)


def test_the_scan_kernels_reset_the_state_at_a_document_s_start():
    """The kernels interpreted, bf16, chunks of 128 with up to two
    starts a chunk, against the float32 composition of each document
    alone."""
    lengths = (100, 60, 96)
    x, dt, bm, cm, a, d = _scan_args(3, 256, 2, 64, 128, dtype=BF)
    seg = _segments(lengths)
    assert S.ssd_available(x, bm, cm, 128)

    def alone(x, dt, bm, cm):
        return D._ssd(x.astype(F32), dt, a, bm.astype(F32), cm.astype(F32),
                      d, 128)

    _packed_equals_alone(
        lambda *t: S.ssd_scan(t[0], t[1], a, t[2], t[3], d, 128,
                              D._document_starts(seg)).astype(F32),
        alone, lengths, (x, dt, bm, cm), lambda g, w: near(g, w, 2e-2))


def _attn_packed(lengths, heads=4, kv=2, d=16, dtype=F32, seed=4):
    q, k, v, _ = qkv(seed, sum(lengths), heads, kv, d, dtype=dtype)
    return q, k, v, _segments(lengths)


def test_attention_sees_the_keys_of_its_own_document_only():
    """The composition in float32, blocks of 8 queries (documents that
    start inside a block and span several), against each document's
    plain causal attention."""
    lengths = (5, 19, 2, 14)
    q, k, v, seg = _attn_packed(lengths)
    _packed_equals_alone(
        lambda *t: D._causal_gqa(*t, 8, segments=seg), attention_ref,
        lengths, (q, k, v), lambda g, w: close(g, w, 2e-5))
    # under a window too: the band's keys, of the document
    got = D._causal_gqa(q, k, v, 8, window=6, segments=seg)
    t = np.arange(sum(lengths))
    seen = (t[None] <= t[:, None]) & (t[:, None] - t[None] < 6) \
        & (np.asarray(seg)[0][None] == np.asarray(seg)[0][:, None])
    kk, vv = (jnp.repeat(x, 2, axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / 4.0
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(
        jnp.where(seen, s, -jnp.inf), -1), vv)
    close(got, want, 2e-5)


@pytest.mark.parametrize("d, window", [(64, None), (128, None), (64, 160)],
                         ids=["two_heads_a_step", "one_head_a_step",
                              "window"])
def test_the_attention_kernel_masks_other_documents_keys(d, window):
    """The kernels interpreted, bf16, tiles of 128: documents that
    start inside a tile, span tiles and leave a query tile whose
    earlier tiles hold none of its keys."""
    lengths = (100, 200, 84)
    q, k, v, seg = _attn_packed(lengths, d=d, dtype=BF, seed=5)
    assert P.causal_gqa_available(q, k, v, 128, seg)

    def alone(q, k, v):
        q, k, v = (t.astype(F32) for t in (q, k, v))
        if window is None:
            return attention_ref(q, k, v)
        return D._causal_gqa(q, k, v, 512, window=window)

    _packed_equals_alone(
        lambda *t: P.flash_causal_gqa(*t, 128, window, None, seg)
        .astype(F32), alone, lengths, (q, k, v),
        lambda g, w: near(g, w, 2e-2))


# ---------------------------------------------------------------------------
# nothing moves where no ids and no scale are given
# ---------------------------------------------------------------------------
def test_ids_of_one_document_and_the_default_scale_change_nothing():
    """Bit for bit: each changed op with ids that name one document, and
    the attention with its own ``1 / sqrt(d)`` given as ``scale``,
    gives what it gives with neither (the call the other models make;
    tests/test_decoder_ops.py and the other decoders' files hold that
    call to what it was)."""
    one = jnp.zeros((2, 24), jnp.int32)
    x, w, b = rand(6, (2, 24, 12), (12, 4), (12,))
    close(D._causal_conv1d(x, w, b, segments=one), D._causal_conv1d(x, w, b),
          0)
    xs, dt, bm, cm, a, d = _scan_args(7, 24, 4, 8, 8, batch=2)
    close(D._scan(xs, dt, a, bm, cm, d, 8, one),
          D._scan(xs, dt, a, bm, cm, d, 8), 0)
    q, k, v, _ = qkv(8, 24, 4, 2, 16, batch=2, dtype=F32)
    plain = D._attend(q, k, v)
    close(D._attend(q, k, v, segments=one), plain, 0)
    close(D._attend(q, k, v, scale=0.25), plain, 0)
    assert not np.allclose(D._attend(q, k, v, scale=0.0625), plain)
    # and the mixers by their names
    args = rand(9, (2, 24, 32), (32,), (64, 32), (32, 32), (32, 32),
                (32, 64), scale=0.3)
    mixer = get_op("_contrib_gqa_mixer").impl
    attrs = dict(num_heads=4, num_kv_heads=2, head_dim=16)
    close(mixer(*args, segment_ids=one, scale=0.25, **attrs),
          mixer(*args, **attrs), 0)


def test_a_scale_that_is_no_power_of_two_rounds_q_once():
    q, k, v, _ = qkv(10, 16, 4, 2, 16, dtype=F32)
    want = attention_ref(q * (0.3 * 4.0), k, v)
    close(D._attend(q, k, v, scale=0.3), want, 2e-5)


# ---------------------------------------------------------------------------
# the whole model
# ---------------------------------------------------------------------------
def _build(cfg=CFG, seed=3):
    mx.random.seed(seed)
    net = zoo.GraniteHybridModel(cfg, prefix="")
    head = zoo.GraniteHybridLMLoss(cfg, net, prefix="")
    net.initialize()
    return net, head


def _weights(net, head):
    return CFGMOD.named_weights(net, CFGMOD._HeadLoss(head))


def _batch(seed=0, shape=(2, 40), cfg=CFG, lengths=(11, 3, 17, 9)):
    rng = np.random.default_rng(seed)
    ids, labels = (rng.integers(0, cfg["vocab_size"], shape, dtype=np.int32)
                   for _ in range(2))
    seg = np.stack([np.asarray(_segments(np.roll(lengths, i))[0])
                    for i in range(shape[0])])
    return ids, seg, labels


def _trained(w):
    return {k: jnp.asarray(v) for k, v in w.items()
            if not k.endswith(REF.FROZEN)}


def test_logits_and_loss_match_the_reference_position_for_position():
    net, head = _build()
    ids, seg, labels = _batch()
    with autograd.pause():
        hidden = net(_ids(ids), _ids(seg))
        loss = head(hidden, _ids(labels)).asnumpy().item()
    w = _weights(net, head)
    assert "head_weight" not in w and "seq_documents" in w
    with jax.default_matmul_precision("highest"):
        want_logits, want_loss, crossed = jax.jit(lambda w: (
            REF.logits(w, ids, seg, CFG),
            REF.lm_loss(w, ids, seg, labels, CFG),
            REF.logits(w, ids, jnp.zeros_like(seg), CFG)))(_trained(w))
    logits = hidden.asnumpy() @ w["embed_weight"].T / CFG["logits_scaling"]
    np.testing.assert_allclose(logits, np.asarray(want_logits), rtol=1e-4,
                               atol=1e-5)
    assert loss == pytest.approx(float(want_loss), rel=1e-5)
    # the boundaries are in the values: with every row taken as one
    # document the logits differ after the first start, and not before
    off = np.abs(np.asarray(crossed) - np.asarray(want_logits)).max(-1)
    first = [int(np.argmax(row != row[0])) for row in seg]
    for row, at in enumerate(first):
        assert off[row, :at].max() == 0 and off[row, at:].max() > 1e-3


def test_the_gradient_of_every_parameter_matches_the_reference():
    """Hybridized (the symbolic path). 2e-4 of a gradient's largest
    entry: float32 sums in other orders."""
    net, head = _build()
    net.hybridize()
    head.hybridize()
    ids, seg, labels = _batch(1)
    params = dict(net.collect_params())
    with autograd.record():
        loss = head(net(_ids(ids), _ids(seg)), _ids(labels))
    loss.backward()
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.grad(lambda t: REF.lm_loss(
            t, ids, seg, labels, CFG)))(_trained(_weights(net, head)))
    assert set(want) == {n for n in params if not n.endswith(REF.FROZEN)}
    for name in sorted(want):
        got = params[name].grad().asnumpy()
        scale = float(np.abs(np.asarray(want[name])).max())
        assert scale > 0, name
        np.testing.assert_allclose(got, np.asarray(want[name]), rtol=0,
                                   atol=2e-4 * scale, err_msg=name)


def _step(net, head, dtype=None, **hp):
    mesh = make_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
    return ShardedTrainStep(net, CFGMOD._HeadLoss(head), mesh,
                            optimizer="adamw", dtype=dtype, n_data_inputs=3,
                            data_specs=[Spec()] * 3, **hp)


def test_two_adamw_steps_match_the_reference():
    net, head = _build()
    w = _weights(net, head)
    batch = _batch(4)
    step = _step(net, head, **{k: v for k, v in OPT.items() if k != "name"})
    got = [float(step.step(*map(_ids, batch))) for _ in range(3)]
    np.testing.assert_allclose(got, REF.train_losses(w, batch, CFG, OPT, 3),
                               rtol=2e-5)
    assert got[2] < got[1] < got[0]
    # the count of documents: an auxiliary state the step rewrites
    assert list(step.aux) == ["seq_documents"]
    assert zoo.publish_seq_documents(step.aux) == {"model": 4.0}
    assert telemetry.gauge("mx_seq_documents", block="model").get() == 4.0


def test_the_kernels_engaged_the_model_matches_the_reference_in_bf16():
    """Widths the scan and attention kernels serve, interpreted: the
    step's loss against the float32 reference on the same weights; both
    kernels and the ids' path counted (values and gradients mixer by
    mixer: the test below)."""
    was = telemetry.enabled()
    telemetry.enable(True)
    telemetry.reset()
    try:
        net, head = _build(KERNEL_CFG)
        w = _weights(net, head)
        ids, seg, labels = _batch(5, (1, 512), KERNEL_CFG, DOCS)
        step = _step(net, head, dtype="bfloat16", lr=1e-3)
        got = float(step.step(_ids(ids), _ids(seg), _ids(labels)))
        count = {name: telemetry.counter(name, path="pallas").get()
                 for name in ("mx_mamba2_ssd_path_total",
                              "mx_attn_causal_path_total",
                              "mx_attn_segments_path_total")}
        xla = [telemetry.counter(name, path="xla").get() for name in count]
    finally:
        telemetry.enable(was)
        telemetry.reset()
    assert min(count.values()) >= 1 and max(xla) == 0, (count, xla)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda w: REF.lm_loss(w, ids, seg, labels,
                                             KERNEL_CFG))(_trained(w))
    assert got == pytest.approx(float(want), rel=5e-3)
    assert zoo.publish_seq_documents(step.aux) == {"model": 3.0}


@pytest.mark.parametrize("kind", [MAMBA, ATTN])
def test_a_mixer_s_kernels_match_the_reference_on_a_packed_row(kind):
    """One mixer op at the kernels' widths in bf16, interpreted, on a
    packed row: values and the gradient to the input and to every
    weight against the reference's mixer in float32 on the same
    (bf16-rounded) numbers."""
    cfg = KERNEL_CFG
    net, _ = _build(dict(cfg, layer_types=[kind], num_hidden_layers=1))
    names = zoo._MAMBA if kind == MAMBA else zoo._ATTN
    layer = net.layers[0]
    weights = [jnp.asarray(getattr(layer, n).data().asnumpy(), BF)
               for n in names]
    (x,) = rand(11, (1, 512, cfg["hidden_size"]), dtype=BF)
    seg = _segments(DOCS)
    op = get_op("_contrib_mamba2_mixer" if kind == MAMBA
                else "_contrib_gqa_mixer").impl

    def system(x, *w):
        return op(x, *w, segment_ids=seg, **layer._mixer).astype(F32)

    def ref(x, *w):
        named = {"l_" + n: t.astype(F32) for n, t in zip(names, w)}
        u = REF._rms(x.astype(F32), named["l_op_norm_weight"],
                     cfg["rms_norm_eps"])
        return REF.MIXERS[kind](named, "l_", u, seg, cfg)

    (cot,) = rand(12, (1, 512, cfg["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        want = value_and_grads(ref, x, *weights, cot=cot)
    near(value_and_grads(system, x, *weights, cot=cot), want, 3e-2)


# ---------------------------------------------------------------------------
# the four multipliers, the list of layers, what cannot be built
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("key, other", [
    ("residual_multiplier", 1.0), ("embedding_multiplier", 1.0),
    ("attention_multiplier", 0.25), ("logits_scaling", 1.0)])
def test_each_multiplier_is_in_the_result(key, other):
    """The model built with another value follows the reference given
    that value, position for position, and leaves the reference of the
    published one (q and k scaled up: at the seeded 0.02 every score is
    near 0 and no factor on it shows)."""
    cfg = dict(CFG, **{key: other})
    net, head = _build(cfg)
    for name in ("q_weight", "k_weight"):
        p = getattr(net.layers[1], name)
        p.set_data(p.data() * 40.0)
    ids, seg, _ = _batch(2)
    with autograd.pause():
        hidden = net(_ids(ids), _ids(seg)).asnumpy()
    w = _weights(net, head)
    logits = hidden @ w["embed_weight"].T / cfg["logits_scaling"]
    with jax.default_matmul_precision("highest"):
        want, published = (np.asarray(jax.jit(lambda w, c=c: REF.logits(
            w, ids, seg, c))(_trained(w))) for c in (cfg, CFG))
    np.testing.assert_allclose(logits, want, rtol=1e-4, atol=1e-5)
    assert np.abs(logits - published).max() > 1e-3 * np.abs(published).max()


def test_parameter_names_and_shapes_follow_the_list():
    net, head = _build()
    shapes = {n: tuple(p.shape) for n, p in net.collect_params().items()}
    u, inner, conv = 64, 128, 128 + 2 * 16
    assert [layer.kind for layer in net.layers] == [MAMBA, ATTN, MAMBA]
    assert shapes["layers0_in_proj_weight"] == (inner + conv + 8, u)
    assert shapes["layers0_conv_weight"] == (conv, 4)
    assert shapes["layers1_q_weight"] == (u, u)
    assert shapes["layers1_k_weight"] == (32, u)
    assert shapes["layers2_gate_up_weight"] == (192, u)
    assert shapes["embed_weight"] == (256, u)
    assert shapes["seq_documents"] == (1,)
    assert "layers1_conv_weight" not in shapes
    assert list(head.collect_params()) == ["embed_weight"]
    assert net.layers[0]._mixer["chunk_size"] == 16
    # the scan's chunk is the schedule's: 128 whatever mamba_chunk_size
    plain = {k: v for k, v in CFG.items() if k != "scan_chunk"}
    assert zoo.GraniteHybridDecoderLayer(plain, 0)._mixer["chunk_size"] == 128


@pytest.mark.parametrize("change", [
    dict(layer_types=[MAMBA, "conv", MAMBA]),
    dict(layer_types=[MAMBA]),
    dict(num_local_experts=4),
    dict(mamba_expand=4),
    dict(position_embedding_type="rope"),
    dict(tie_word_embeddings=False),
    dict(mamba_conv_bias=False),
], ids=lambda c: "_".join(c))
def test_a_configuration_that_cannot_be_built_is_refused(change):
    with pytest.raises(ValueError):
        zoo.GraniteHybridModel(dict(CFG, **change), prefix="")


def test_which_chunk_the_scan_kernels_take_for_a_group_of_64_heads():
    """The published widths: 64 heads of 64 lanes in one group are a
    lane tile of 4,096; a backward step holds 66.5 MB at chunks of 128
    and 117.7 MB at the published 256, over the budget."""
    shape = lambda *s: jax.ShapeDtypeStruct(s, BF)   # noqa: E731
    x, bc = shape(1, 8192, 64, 64), shape(1, 8192, 1, 128)
    assert S._bwd_vmem_bytes(128, 4096, 128) == 66_453_504
    assert S._bwd_vmem_bytes(256, 4096, 128) == 117_702_656
    assert S._bwd_vmem_bytes(128, 4096, 128) <= S._VMEM_BUDGET \
        < S._bwd_vmem_bytes(256, 4096, 128)
    assert S.ssd_available(x, bc, bc, 128)
    assert not S.ssd_available(x, bc, bc, 256)
    # the attention's ids: 8 MB more of VMEM at 8,192 keys, inside it
    q, kv = shape(1, 8192, 32, 64), shape(1, 8192, 8, 64)
    seg = jax.ShapeDtypeStruct((1, 8192), jnp.int32)
    assert P._bwd_vmem_bytes(8192, 128, 512, True) \
        - P._bwd_vmem_bytes(8192, 128, 512) == 8 << 20
    assert P.causal_gqa_available(q, kv, kv, 512, seg)
