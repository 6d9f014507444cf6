"""The zoo's LFM2 expert model (gluon/model_zoo/lfm2.py) at toy widths on
the CPU: the gated short-convolution mixer alone and the whole model
against the benchmark's plain float32 reference (hidden states, loss,
the gradient of every parameter, AdamW steps through
``ShardedTrainStep``); that each new term is in the result (both gates,
the taps' order and number, causality, the q/k norms and the score's
scale, the leading dense layers, the selection bias); ``layer_types``
and ``num_dense_layers``; the head that is the embedding's parameter;
the eight expert-parallel shares adding up to the uncut layer; the
attention at 64 lanes a head, composition and interpreted kernel; and
``_causal_conv1d`` with and without a bias."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from decoder_harness import OPT, Toy, ids as _ids
from mxnet_tpu import autograd, nd, telemetry
from mxnet_tpu.gluon.model_zoo import lfm2 as zoo
from mxnet_tpu.ops import decoder_ops as D, get_op, pallas_causal_gqa as P
from numerics import (F32, attention_ref, close, near, qkv, rand,
                      same_values_and_grads, value_and_grads)

CONV, FULL = zoo.KINDS
# two leading dense layers, both mixers under an expert layer; 4 query
# heads of 16 lanes over 2 key-value heads; 4 of 16 experts held
CFG = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    norm_eps=1e-5, conv_L_cache=3, conv_bias=False,
    rope_parameters={"rope_theta": 1e6, "rope_type": "default"},
    layer_types=[CONV, CONV, FULL, CONV], num_dense_layers=2,
    num_hidden_layers=4, intermediate_size=96, moe_intermediate_size=24,
    num_experts=16, experts_held=4, expert_offset=4, num_experts_per_tok=3,
    norm_topk_prob=True, routed_scaling_factor=1, use_expert_bias=True,
    vocab_size=64)


class _Toy(Toy):
    """The head is handed the model's embedding."""

    def build(self, cfg=None, seed=3):
        import mxnet_tpu as mx
        cfg = self.cfg if cfg is None else cfg
        mx.random.seed(seed)
        net = self.model(cfg, prefix="")
        head = self.loss(cfg, net, prefix="")
        net.initialize()
        return net, head

    def sizes(self, cfg=None, **change):
        cfg = dict(self.cfg if cfg is None else cfg, **change)
        return dict(cfg, deployment={
            "expert_offset": cfg["expert_offset"],
            "layers_built": list(range(cfg["num_hidden_layers"]))})


TOY = _Toy("lfm2_24b_a2b", zoo.Lfm2MoeModel, zoo.Lfm2LMLoss, CFG)
REF, CFGMOD = TOY.ref, TOY.cfgmod
_build, _weights, _batch, _step, _sizes = (TOY.build, TOY.weights, TOY.batch,
                                           TOY.step, TOY.sizes)


# ---------------------------------------------------------------------------
# the causal depthwise conv with and without a bias
# ---------------------------------------------------------------------------
def _direct_conv(x, w, b):
    """``y[t] = b + sum_j w[:, j] x[t - (k-1) + j]`` by a loop over the
    positions."""
    x, w = np.asarray(x, np.float64), np.asarray(w, np.float64)
    k = w.shape[1]
    y = np.zeros_like(x) + (0.0 if b is None else np.asarray(b, np.float64))
    for t in range(x.shape[1]):
        for j in range(k):
            s = t - (k - 1) + j
            if s >= 0:
                y[:, t] += w[:, j] * x[:, s]
    return y


@pytest.mark.parametrize("taps", [3, 4])
@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "bias"])
def test_causal_conv1d_against_a_direct_sum(taps, bias):
    x, w, b = rand(taps, (2, 9, 5), (5, taps), (5,))
    b = b if bias else None
    got = jax.jit(lambda x, w: D._causal_conv1d(x, w, b))(x, w)
    np.testing.assert_allclose(got, _direct_conv(x, w, b), rtol=1e-5,
                               atol=1e-6)
    op = get_op("_contrib_causal_conv1d").impl
    np.testing.assert_allclose(op(x, w, b) if bias else op(x, w), got,
                               rtol=1e-5, atol=1e-6)
    # in another dtype the taps are summed there and nowhere else
    low = D._causal_conv1d(x.astype(jnp.bfloat16), w, b, dtype=jnp.bfloat16)
    assert low.dtype == jnp.bfloat16
    near(low, got, 2e-2)


# ---------------------------------------------------------------------------
# the short-convolution mixer alone
# ---------------------------------------------------------------------------
def _conv_args(seed, hidden=24, shape=(2, 21), taps=3):
    x, g, w_in, w_c, w_out = rand(
        seed, shape + (hidden,), (hidden,), (3 * hidden, hidden),
        (hidden, taps), (hidden, hidden), scale=0.5)
    return {"x": x, "op_norm_weight": 1 + g, "in_weight": w_in,
            "conv_weight": w_c, "out_weight": w_out}


def _conv_op(a):
    return get_op("_contrib_short_conv_mixer").impl(
        a["x"], a["op_norm_weight"], a["in_weight"], a["conv_weight"],
        a["out_weight"], eps=1e-5)


def _conv_ref(a, mixer=None):
    x = REF._rms(a["x"], a["op_norm_weight"], 1e-5)
    return (mixer or REF.short_conv)(a, "", x)


@pytest.mark.parametrize("taps", [3, 4])
def test_the_short_conv_mixer_matches_the_reference(taps):
    """Values and the gradient of every input. 1e-4: both are float32,
    the op's products at XLA's default precision on the CPU (float32),
    the reference's at ``highest``; the sums run in other orders."""
    a = _conv_args(11, taps=taps)
    with jax.default_matmul_precision("highest"):
        same_values_and_grads(_conv_op, _conv_ref, (a,), tol=1e-4)


def _split(a, x):
    return jnp.split(x @ a["in_weight"].T, 3, axis=-1)


def _mixer_without(fault):
    """The reference's conv branch with one term changed."""
    def mixer(a, p, x):
        b, c, u = _split(a, x)
        taps = a["conv_weight"]
        if fault == "the_b_gate_taken_out":
            y = c * REF.causal_filter(u, taps)
        elif fault == "the_c_gate_taken_out":
            y = REF.causal_filter(b * u, taps)
        elif fault == "the_taps_reversed":
            y = c * REF.causal_filter(b * u, taps[:, ::-1])
        elif fault == "the_filter_reduced_to_its_last_tap":
            y = c * REF.causal_filter(b * u, taps[:, -1:])
        elif fault == "the_gates_in_the_other_order":      # C before, B after
            y = b * REF.causal_filter(c * u, taps)
        elif fault == "the_filter_reading_later_tokens":
            # the mirror image in time: c_t = sum_j w_j z_{t+2-j}
            y = c * REF.causal_filter((b * u)[:, ::-1], taps)[:, ::-1]
        return y @ a["out_weight"].T
    return mixer


CONV_FAULTS = ["the_b_gate_taken_out", "the_c_gate_taken_out",
               "the_taps_reversed", "the_filter_reduced_to_its_last_tap",
               "the_gates_in_the_other_order",
               "the_filter_reading_later_tokens"]


@pytest.mark.parametrize("fault", CONV_FAULTS)
def test_a_wrong_short_conv_fails_the_mixer_s_comparison(fault):
    """Each term matters: the reference of a mixer without it is a
    hundred times further from the op than the 1e-4 of
    ``test_the_short_conv_mixer_matches_the_reference``."""
    a = _conv_args(15, shape=(2, 40))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(_conv_op)(a)
        wrong = jax.jit(lambda a: _conv_ref(a, _mixer_without(fault)))(a)
    assert float(jnp.abs(wrong - got).max()) \
        > 1e-2 * float(jnp.abs(got).max()), fault


def test_the_short_conv_is_causal_and_reaches_two_tokens_back():
    """Perturbing token ``t`` moves no output before ``t``, and moves
    outputs ``t``, ``t + 1`` and ``t + 2`` (three taps) and none
    after."""
    a = _conv_args(16, shape=(1, 12))
    t = 5
    moved = dict(a, x=a["x"].at[0, t].add(1.0))
    delta = np.abs(np.asarray(_conv_op(moved) - _conv_op(a))).max(-1)[0]
    assert (delta[:t] == 0).all() and (delta[t + 3:] == 0).all()
    assert (delta[t:t + 3] > 1e-4).all()


def test_the_short_conv_keeps_w_in_s_output_only_and_names_its_scopes(capsys):
    a = _conv_args(13)
    fn = lambda a: jnp.sum(_conv_op(a))
    jax.ad_checkpoint.print_saved_residuals(fn, a)
    kept = [line.split(" ")[0] for line in capsys.readouterr().out
            .splitlines() if "from the argument" not in line
            and "from a constant" not in line]
    assert kept == ["f32[2,21,72]"]      # [B ; C ; u], three runs of 24
    text = jax.jit(jax.grad(fn)).lower(a).as_text(debug_info=True)
    gate = [line for line in text.splitlines() if "mx.conv.gate" in line]
    assert [l for l in gate if "/mul" in l]
    assert [l for l in gate if "transpose(jvp(mx.conv))" in l]
    # the projections stand under the mixer's scope, not the gate's
    dots = [l for l in text.splitlines() if "dot_general" in l
            and "mx.conv" in l]
    assert dots and not [l for l in dots if "mx.conv.gate" in l]
    assert telemetry.innermost_scope(
        "jit(f)/mx.conv/checkpoint/mx.conv.gate/mul") == "mx.conv.gate"
    assert telemetry.innermost_scope(
        "jit(f)/transpose(jvp(mx.conv))/rematted_computation/dot_general") \
        == "mx.conv"


# ---------------------------------------------------------------------------
# the attention mixer under LFM2's parameterisation
# ---------------------------------------------------------------------------
def _attn_args(seed, hidden=32, heads=4, kv=2, d=16, shape=(2, 24)):
    x, g, q, k, v, o, qn, kn = rand(
        seed, shape + (hidden,), (hidden,), (heads * d, hidden),
        (kv * d, hidden), (kv * d, hidden), (hidden, heads * d), (d,), (d,),
        scale=0.5)
    return {"x": x, "op_norm_weight": 1 + g, "q_weight": q, "k_weight": k,
            "v_weight": v, "o_weight": o, "q_norm_weight": 1 + qn,
            "k_norm_weight": 1 + kn}


_ATTN_CFG = dict(CFG, hidden_size=64)      # 4 heads of 16 lanes


def _attn_op(a):
    return get_op("_contrib_rotary_gqa_mixer").impl(
        a["x"], a["op_norm_weight"], a["q_weight"], a["k_weight"],
        a["v_weight"], a["o_weight"], a["q_norm_weight"], a["k_norm_weight"],
        num_heads=4, num_kv_heads=2, head_dim=16, rope_theta=1e6, eps=1e-5)


def _attn_ref(a, **change):
    x = REF._rms(a["x"], a["op_norm_weight"], 1e-5)
    return REF.attention(a, "", x, dict(_ATTN_CFG, **change))


def test_the_attention_mixer_with_q_k_norms_matches_the_reference():
    a = _attn_args(21)
    with jax.default_matmul_precision("highest"):
        same_values_and_grads(_attn_op, _attn_ref, (a,), tol=1e-4)


@pytest.mark.parametrize("fault, change", [
    ("the_scale_of_heads_of_128_lanes", dict(score_lanes=128)),
    ("without_q_k_norms", dict(qk_norm=False)),
    ("another_rotary_base", dict(rope_parameters={"rope_theta": 1e4}))])
def test_a_wrong_attention_fails_the_mixer_s_comparison(fault, change):
    a = _attn_args(22, shape=(2, 40))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(_attn_op)(a)
        wrong = jax.jit(lambda a: _attn_ref(a, **change))(a)
    assert float(jnp.abs(wrong - got).max()) \
        > 1e-2 * float(jnp.abs(got).max()), fault


# ---------------------------------------------------------------------------
# the whole model
# ---------------------------------------------------------------------------
def test_hidden_states_logits_and_loss_match_the_reference():
    net, head = _build()
    ids, labels = _batch()
    with autograd.pause():
        hidden = net(_ids(ids))
        loss = head(hidden, _ids(labels)).asnumpy().item()
    w = _weights(net, head)
    assert "head_weight" not in w
    with jax.default_matmul_precision("highest"):
        want, want_logits, want_loss = jax.jit(lambda w: (
            REF.forward(w, ids, CFG), REF.logits(w, ids, CFG),
            REF.lm_loss(w, ids, labels, CFG)))(w)
    np.testing.assert_allclose(hidden.asnumpy(), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(hidden.asnumpy() @ w["embed_weight"].T,
                               np.asarray(want_logits), rtol=1e-4, atol=1e-4)
    assert loss == pytest.approx(float(want_loss), rel=1e-5)


def _trained(w):
    return {k: jnp.asarray(v) for k, v in w.items()
            if not k.endswith(REF.FROZEN)}


def _reference_grads(w, ids, labels, cfg=CFG):
    frozen = {k: jnp.asarray(v) for k, v in w.items()
              if k.endswith("expert_bias")}
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.grad(lambda t: REF.lm_loss(
            dict(t, **frozen), ids, labels, cfg)))(_trained(w))


def test_the_gradient_of_every_parameter_matches_the_reference():
    """Hybridized (the symbolic path: the loss block's graph reads the
    embedding's variable). 2e-4 of a gradient's largest entry: float32
    sums in other orders."""
    net, head = _build()
    net.hybridize()
    head.hybridize()
    ids, labels = _batch(1)
    params = dict(net.collect_params())
    with autograd.record():
        loss = head(net(_ids(ids)), _ids(labels))
    loss.backward()
    want = _reference_grads(_weights(net, head), ids, labels)
    assert set(want) == {n for n in params if not n.endswith(REF.FROZEN)}
    for name in sorted(want):
        got = params[name].grad().asnumpy()
        scale = float(np.abs(np.asarray(want[name])).max())
        assert scale > 0, name
        np.testing.assert_allclose(got, np.asarray(want[name]), rtol=0,
                                   atol=2e-4 * scale, err_msg=name)


def test_the_head_s_weight_is_the_embedding_s():
    """One ``Parameter``, one name, one master in a step; its gradient
    is the sum over the lookup and the head, each of which is there."""
    net, head = _build()
    assert head.embed_weight is net.embed.weight
    assert dict(head.collect_params()) == {"embed_weight": net.embed.weight}
    assert "head_weight" not in net.collect_params()
    step = _step(net, head)
    assert list(step.params).count("embed_weight") == 1
    assert len(step.params) + len(step.aux) == len(net.collect_params())
    ids, labels = _batch(1)
    w = _weights(net, head)
    other = {k: jnp.asarray(v) for k, v in w.items() if k != "embed_weight"}

    def loss(lookup, scorer):
        hidden = REF.forward(dict(other, embed_weight=lookup), ids, CFG)
        logp = jax.nn.log_softmax(hidden @ scorer.T, -1)
        return -jnp.mean(jnp.take_along_axis(
            logp, jnp.asarray(labels)[..., None], -1))

    e = jnp.asarray(w["embed_weight"])
    with jax.default_matmul_precision("highest"):
        by_lookup, by_head = jax.jit(jax.grad(loss, (0, 1)))(e, e)
    assert float(jnp.abs(by_lookup).max()) > 1e-4
    assert float(jnp.abs(by_head).max()) > 1e-4
    with autograd.record():
        out = head(net(_ids(ids)), _ids(labels))
    out.backward()
    got = net.embed.weight.grad().asnumpy()
    scale = float(jnp.abs(by_lookup + by_head).max())
    np.testing.assert_allclose(got, by_lookup + by_head, rtol=0,
                               atol=2e-4 * scale)
    assert np.abs(got - np.asarray(by_head)).max() > 1e-2 * scale
    # a head of another shape than the embedding handed in is refused
    with pytest.raises(ValueError):
        zoo.Lfm2LMLoss(dict(CFG, vocab_size=32), net, prefix="")


def test_parameter_names_and_shapes_follow_the_list_and_the_count():
    net, _ = _build()
    assert [(l.kind, l.mlp_kind) for l in net.layers] == [
        (CONV, "dense"), (CONV, "dense"), (FULL, "sparse"), (CONV, "sparse")]
    shapes = {k: v.shape for k, v in net.collect_params().items()}
    for i in (0, 1, 3):
        assert shapes["layers%d_in_weight" % i] == (192, 64)
        assert shapes["layers%d_conv_weight" % i] == (64, 3)
        assert shapes["layers%d_out_weight" % i] == (64, 64)
        assert "layers%d_q_weight" % i not in shapes
    assert shapes["layers2_q_weight"] == shapes["layers2_o_weight"] == (64, 64)
    assert shapes["layers2_k_weight"] == shapes["layers2_v_weight"] == (32, 64)
    assert shapes["layers2_q_norm_weight"] == shapes["layers2_k_norm_weight"] \
        == (16,)
    assert "layers2_in_weight" not in shapes
    for i in (0, 1):
        assert shapes["layers%d_gate_up_weight" % i] == (192, 64)
        assert shapes["layers%d_down_weight" % i] == (64, 96)
        assert "layers%d_router_weight" % i not in shapes
    for i in (2, 3):
        assert shapes["layers%d_router_weight" % i] == (16, 64)  # all routed
        assert shapes["layers%d_expert_bias" % i] == (16,)
        assert shapes["layers%d_expert_rows" % i] == (2, 4)
        assert shapes["layers%d_experts_gate_up_weight" % i] == (4, 48, 64)
        assert shapes["layers%d_experts_down_weight" % i] == (4, 64, 24)
        assert "layers%d_gate_up_weight" % i not in shapes
    assert not [n for n in shapes if "shared" in n or n.endswith("_bias")
                and not n.endswith("expert_bias")]
    assert net.layers[2]._attn == dict(num_heads=4, num_kv_heads=2,
                                       head_dim=16, rope_theta=1e6, eps=1e-5)
    p = {k: v.data().asnumpy() for k, v in net.collect_params().items()}
    assert p["layers0_out_weight"].std() < 0.6 * p["layers0_in_weight"].std()
    assert abs(p["layers0_conv_weight"].std()
               - 0.02 * np.sqrt(64 / 3)) < 0.02
    bias = p["layers2_expert_bias"]
    assert -0.01 <= bias.min() < bias.max() < 0.01
    # one more dense layer, one kind swapped: the names follow
    other = zoo.Lfm2MoeModel(dict(
        CFG, layer_types=[FULL, CONV, CONV, CONV], num_dense_layers=3),
        prefix="").collect_params()
    assert "layers0_q_weight" in other and "layers2_in_weight" in other
    assert "layers2_gate_up_weight" in other
    assert "layers2_router_weight" not in other
    assert "layers3_router_weight" in other


@pytest.mark.parametrize("change", [
    dict(layer_types=[CONV, CONV, FULL]),                   # a short list
    dict(layer_types=[CONV, "linear_attention", FULL, CONV]),
    dict(layer_types=[CONV, "sliding_attention", FULL, CONV]),
    dict(experts_held=8, expert_offset=12),
    dict(num_attention_heads=3),
    dict(num_key_value_heads=3),
    dict(conv_L_cache=0),
    dict(conv_bias=True),
    dict(use_expert_bias=False),
    dict(rope_parameters={"rope_theta": 1e6, "rope_type": "yarn"})])
def test_a_configuration_that_cannot_be_built_is_refused(change):
    with pytest.raises(ValueError):
        zoo.Lfm2MoeModel(dict(CFG, **change), prefix="")


def test_states_ride_as_auxiliary_states_and_paths_are_counted():
    telemetry.reset()
    was = telemetry.enabled()
    telemetry.enable(True)
    try:
        net, head = _build()
        step = _step(net, head)
        assert sorted(step.aux) == [
            "layers2_expert_bias", "layers2_expert_rows",
            "layers3_expert_bias", "layers3_expert_rows"]
        bias = np.asarray(step.aux["layers2_expert_bias"])
        ids, labels = _batch()
        first = float(step.step(_ids(ids), _ids(labels)))
        assert float(step.step(_ids(ids), _ids(labels))) < first
        # the selection bias is never updated
        np.testing.assert_array_equal(step.aux["layers2_expert_bias"], bias)
        assert sorted(CFGMOD.expert_rows(step.aux)) == ["layers2", "layers3"]
        assert telemetry.counter("mx_moe_dropped_rows_total").value == 0
        assert telemetry.counter("mx_attn_causal_path_total",
                                 path="xla").value >= 1
        assert telemetry.counter("mx_moe_experts_path_total",
                                 path="xla").value >= 2
    finally:
        telemetry.enable(was)
        telemetry.reset()


def test_sharded_step_matches_the_reference_in_bfloat16_within_reason():
    net, head = _build()
    w = _weights(net, head)
    step = _step(net, head, dtype="bfloat16")
    ids, labels = _batch(2)
    got = float(step.step(_ids(ids), _ids(labels)))
    with jax.default_matmul_precision("highest"):
        want = float(jax.jit(lambda w: REF.lm_loss(w, ids, labels, CFG))(w))
    assert got == pytest.approx(want, rel=5e-3)


@pytest.fixture(scope="module")
def right():
    """Seeded weights, a batch, the system's three losses (before any
    update, after one and two) and the reference's."""
    net, head = _build()
    w = _weights(net, head)
    batch = _batch(4)
    step = TOY.reference_step(net, head)
    got = [float(step.step(_ids(batch[0]), _ids(batch[1])))
           for _ in range(3)]
    return w, batch, got, REF.train_losses(w, batch, _sizes(), OPT, 3)


def test_two_adamw_steps_match_the_reference(right):
    """2e-5, float32 on both sides."""
    _, _, got, want = right
    np.testing.assert_allclose(got, want, rtol=2e-5)
    assert got[2] < got[1] < got[0]


def _experts_of_a_model_with(**change):
    """w -> the weights of a model built with ``change`` (more expert
    layers): the system's own wherever the name exists, the new expert
    layers' from a model built that way."""
    def weights(w):
        other = _weights(*_build(dict(CFG, **change), seed=4))
        return dict(other, **{k: v for k, v in w.items() if k in other})
    return weights


WRONG_MODELS = {
    "both_leading_layers_sparse": (
        dict(num_dense_layers=0), _experts_of_a_model_with(num_dense_layers=0)),
    "one_leading_dense_layer_too_few": (
        dict(num_dense_layers=1), _experts_of_a_model_with(num_dense_layers=1)),
    "the_c_gate_taken_out": (None, "the_c_gate_taken_out"),
    "the_b_gate_taken_out": (None, "the_b_gate_taken_out"),
    "the_filter_reduced_to_its_last_tap": (
        None, "the_filter_reduced_to_its_last_tap"),
    "the_router_s_weights_not_normalised": (dict(norm_topk_prob=False), None),
    "the_head_untied": (None, "untied"),
}


@pytest.mark.parametrize("fault", sorted(WRONG_MODELS))
def test_a_wrong_model_gives_other_losses(fault, right, monkeypatch):
    """The reference of another model on the same weights: at least ten
    times outside the 2e-5 to which the system's steps agree. (The
    attention's terms move a toy's loss too little at seeded weights:
    the mixer's own comparison holds them,
    ``test_a_wrong_attention_fails_the_mixer_s_comparison``; the
    selection bias likewise, below; and the taps' order moves the loss
    of independent random tokens by 4e-5, on the chip too (PERF.md
    section 6, PR 47): ``test_a_wrong_short_conv_fails_the_mixer_s_
    comparison`` holds it.)"""
    w, batch, _, want = right
    change, how = WRONG_MODELS[fault]
    sizes = _sizes(**(change or {}))
    if callable(how):
        w = how(w)
    elif how == "untied":
        # a head of its own, seeded: the first loss already differs
        (head_w,) = rand(5, w["embed_weight"].shape, scale=0.02)

        def untied(weights, ids, labels, cfg):
            hidden = REF.forward(weights, ids, cfg)
            logp = jax.nn.log_softmax(hidden @ head_w.T, -1)
            return -jnp.mean(jnp.take_along_axis(
                logp, labels[..., None], -1))
        monkeypatch.setattr(REF, "lm_loss", untied)
    elif how is not None:
        mixer = _mixer_without(how)
        monkeypatch.setattr(
            REF, "short_conv",
            lambda weights, p, x, cfg=None: mixer(
                {k[len(p):]: v for k, v in weights.items()
                 if k.startswith(p)}, "", x))
    wrong = REF.train_losses(w, batch, sizes, OPT, 3)
    assert max(abs(a - b) / b for a, b in zip(wrong, want)) > 2e-4, \
        (wrong, want)


def test_the_selection_bias_moves_the_choice_and_never_the_weight():
    """The expert op with LFM2's switches against the reference's
    expert layer, with a bias large enough to move choices; without the
    bias the reference chooses other experts; and a bias that moves no
    choice moves nothing (it is not in the weights)."""
    hidden, width, routed, held = 32, 12, 16, 16
    x, gamma, r, gate_up, down, bias = rand(
        31, (2, 21, hidden), (hidden,), (routed, hidden),
        (held, 2 * width, hidden), (held, hidden, width), (routed,))
    bias = 0.3 * bias
    w = {"router_weight": r, "experts_gate_up_weight": 0.3 * gate_up,
         "experts_down_weight": 0.3 * down, "expert_bias": bias}
    cfg = dict(CFG, expert_offset=0)
    normed = REF._rms(x, 1 + gamma, 1e-5)
    moe = get_op("_contrib_moe_mixer").impl
    attrs = dict(top_k=3, score_func="sigmoid", activation="swiglu",
                 routed_scaling_factor=1.0, norm_topk_prob=True, eps=1e-5)

    def op(bias):
        return jax.jit(lambda *a: moe(*a, **attrs)[0])(
            x, 1 + gamma, r, jnp.zeros((2, held), F32),
            w["experts_gate_up_weight"], w["experts_down_weight"], bias)

    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda w: REF.experts(w, "", normed, cfg))(w)
        unbiased = jax.jit(lambda w: REF.experts(
            w, "", normed, dict(cfg, use_expert_bias=False)))(w)
    got = op(bias)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    assert float(jnp.abs(unbiased - got).max()) \
        > 1e-2 * float(jnp.abs(got).max())
    chosen, _ = REF.route(w, "", normed, cfg)
    plain, _ = REF.route(w, "", normed, dict(cfg, use_expert_bias=False))
    assert 0 < int((jnp.sort(chosen) != jnp.sort(plain)).any(-1).sum())
    # the same on every expert: no choice moves, so nothing moves
    np.testing.assert_allclose(op(jnp.full((routed,), 0.25, F32)),
                               op(jnp.zeros((routed,), F32)), rtol=1e-6,
                               atol=1e-7)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Expert parallelism's share tied to the model: the routed terms of
    the eight shares (offsets 0, 2, .., 14 of the toy's 16 experts, each
    holding 2 and routing over all 16 with the whole bias) add up to
    what the uncut reference gives for the whole expert layer."""
    hidden, width, routed, held = 32, 12, 16, 2
    x, gamma, r, gate_up, down, bias = rand(
        7, (2, 21, hidden), (hidden,), (routed, hidden),
        (routed, 2 * width, hidden), (routed, hidden, width), (routed,))
    w = {"router_weight": r, "experts_gate_up_weight": 0.3 * gate_up,
         "experts_down_weight": 0.3 * down, "expert_bias": 0.1 * bias}
    with jax.default_matmul_precision("highest"):
        whole = jax.jit(lambda w, x: REF.experts(
            w, "", REF._rms(x, 1 + gamma, 1e-5),
            dict(CFG, expert_offset=0)))(w, x)
    moe = get_op("_contrib_moe_mixer").impl
    attrs = dict(top_k=3, score_func="sigmoid", activation="swiglu",
                 routed_scaling_factor=1.0, eps=1e-5)
    total, routed_rows = 0.0, 0.0
    for offset in range(0, routed, held):
        y, rows = jax.jit(lambda *a, offset=offset: moe(
            *a, expert_offset=offset, **attrs))(
            x, 1 + gamma, r, jnp.zeros((2, held), F32),
            w["experts_gate_up_weight"][offset:offset + held],
            w["experts_down_weight"][offset:offset + held], w["expert_bias"])
        total = total + np.asarray(y, np.float64)
        routed_rows += float(np.asarray(rows)[0].sum())
    assert routed_rows == 2 * 21 * 3        # every choice held somewhere
    np.testing.assert_allclose(total, np.asarray(whole), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# the attention at 64 lanes a head
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("d, heads, kv, served", [
    (64, 8, 2, True), (64, 32, 8, True), (128, 8, 2, True),
    (192, 8, 2, False), (64, 6, 2, False), (64, 8, 1, False),
    (64, 4, 4, False), (32, 8, 2, False), (256, 4, 4, True)])
def test_which_head_widths_the_kernel_serves(d, heads, kv, served):
    """Whole lane tiles; or half a tile where the groups are even and
    the key-value heads pair up. 192 lanes are no whole tile's
    multiple... of one head a block: refused as before."""
    q, k, v, _ = qkv(1, 256, heads, kv, d=d)
    assert P.causal_gqa_available(q, k, v, 128) == served
    assert not P.causal_gqa_available(*(t.astype(F32) for t in (q, k, v)),
                                      128)


@pytest.mark.parametrize("heads, kv", [(8, 2), (4, 2), (12, 2)],
                         ids=["groups_of_4", "groups_of_2", "groups_of_6"])
def test_the_kernel_at_64_lanes_two_heads_a_step(heads, kv):
    """Interpreted, three tiles of 128, two sequences: each pair of
    query heads against its half of a key-value pair's block, dk / dv
    accumulated over both groups of the block and written once,
    against the composition and a whole mask. 2e-2 of the largest
    entry: bf16 results of sums taken in different orders, as
    tests/test_pallas_causal_gqa.py."""
    q, k, v, cot = qkv(heads, 384, heads, kv, d=64, batch=2)
    got = value_and_grads(
        lambda *a: P.flash_causal_gqa(*a, 128, None), q, k, v, cot=cot)
    near(got, value_and_grads(
        lambda *a: D._causal_gqa(*a, 128), q, k, v, cot=cot), 2e-2)
    near(got, value_and_grads(
        attention_ref, *(t.astype(F32) for t in (q, k, v)), cot=cot), 2e-2)


def test_attend_at_64_lanes_with_groups_of_4():
    """``_attend`` itself at two blocks of ``QUERY_BLOCK``: bf16 takes
    the (interpreted) kernel, float32 the composition, both the whole
    mask's values; with a window the same kernel's band."""
    length = 2 * D.QUERY_BLOCK
    q, k, v, cot = qkv(5, length, 8, 2, d=64)
    assert P.causal_gqa_available(q, k, v, D.QUERY_BLOCK)
    f32 = [t.astype(F32) for t in (q, k, v)]
    want = value_and_grads(attention_ref, *f32, cot=cot)
    telemetry.reset()
    was = telemetry.enabled()
    telemetry.enable(True)
    try:
        near(value_and_grads(D._attend, q, k, v, cot=cot), want, 2e-2)
        assert telemetry.counter("mx_attn_causal_path_total",
                                 path="pallas").value == 1
        near(value_and_grads(D._attend, *f32, cot=cot), want, 1e-4)
        assert telemetry.counter("mx_attn_causal_path_total",
                                 path="xla").value == 1
    finally:
        telemetry.enable(was)
        telemetry.reset()
    window = D.QUERY_BLOCK
    near(value_and_grads(lambda *a: D._attend(*a, window=window), q, k, v,
                         cot=cot),
         value_and_grads(lambda *a: D._causal_gqa(*a, D.QUERY_BLOCK, window),
                         q, k, v, cot=cot), 2e-2)


def test_the_symbol_graph_of_the_conv_mixer_evaluates_as_the_eager_call():
    from mxnet_tpu import sym
    a = _conv_args(16)
    names = ["x", "op_norm_weight", "in_weight", "conv_weight", "out_weight"]
    node = sym._contrib_short_conv_mixer(*(sym.var(n) for n in names),
                                         eps=1e-5)
    feed = {n: nd.array(np.asarray(a[n])) for n in names}
    want = nd._contrib_short_conv_mixer(*(feed[n] for n in names), eps=1e-5)
    for graph in (node, sym.load_json(node.tojson())):
        np.testing.assert_allclose(graph.eval(**feed).asnumpy(),
                                   want.asnumpy(), rtol=1e-6, atol=1e-6)
    close(want.asnumpy(), _conv_op(a), 1e-5)
