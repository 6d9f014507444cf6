"""The zoo's Laguna-XS.2 model (gluon/model_zoo/laguna.py) at toy widths
on the CPU: the rotary mixer under Laguna's parameterisation (no q/k
norm, rotary over half a head, a gate a head) and the whole model
against the benchmark's plain float32 reference (hidden states, loss,
the gradient of every parameter, AdamW steps through
``ShardedTrainStep``); that each new term is in the result (the gate,
the half-head rotary, YaRN's ramp over the rotary lanes' pairs, the
head count a layer kind); the three per-layer lists; the eight
expert-parallel shares and the shared expert adding up to the uncut
layer; and the attention at groups of 6 and 8 with a window of one
tile, composition and interpreted kernel."""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from decoder_harness import OPT, Toy, ids as _ids
from mxbench import manifest
from mxnet_tpu import autograd, nd, telemetry
from mxnet_tpu.gluon.model_zoo import laguna as zoo
from mxnet_tpu.ops import decoder_ops as D, get_op, pallas_causal_gqa as P
from numerics import F32, near, qkv, value_and_grads, window_ref

SLIDING, FULL = zoo.KINDS
ROPE = {
    FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 64,
           "original_max_position_embeddings": 4096, "beta_fast": 64,
           "beta_slow": 1, "attention_factor": 1.4158883083359672,
           "partial_rotary_factor": 0.5},
    SLIDING: {"rope_type": "default", "rope_theta": 10000,
              "partial_rotary_factor": 1}}
# full layers of 6 query heads, sliding ones of 8, over 2 key-value
# heads of 16 lanes (groups of 3 and 4); 4 of 16 experts held
CFG = dict(
    hidden_size=48, num_key_value_heads=2, head_dim=16, rms_norm_eps=1e-6,
    sliding_window=5, rope_parameters=ROPE, gating=True,
    layer_types=[FULL, SLIDING, SLIDING, SLIDING, FULL],
    num_attention_heads_per_layer=[6, 8, 8, 8, 6],
    mlp_layer_types=["dense"] + ["sparse"] * 4,
    intermediate_size=96, num_experts=16, experts_held=4, expert_offset=4,
    moe_intermediate_size=24, shared_expert_intermediate_size=24,
    num_experts_per_tok=3, moe_routed_scaling_factor=2.5,
    moe_apply_router_weight_on_input=False, num_hidden_layers=3,
    vocab_size=64)


TOY = Toy("laguna_xs2_33b_a3b", zoo.LagunaModel, zoo.LagunaLMLoss, CFG)
REF, CFGMOD = TOY.ref, TOY.cfgmod
_build, _weights, _batch, _step, _sizes = (TOY.build, TOY.weights, TOY.batch,
                                           TOY.step, TOY.sizes)


# ---------------------------------------------------------------------------
# the mixer op under Laguna's parameterisation
# ---------------------------------------------------------------------------
def _mixer_args(seed, heads, hidden=24, kv=2, d=16, shape=(2, 21)):
    rng = np.random.default_rng(seed)
    draw = lambda *s: jnp.asarray(rng.normal(size=s) * 0.3, F32)
    return {"x": draw(*shape, hidden),
            "attn_norm_weight": 1 + draw(hidden),
            "q_weight": draw(heads * d, hidden),
            "k_weight": draw(kv * d, hidden),
            "v_weight": draw(kv * d, hidden),
            "o_weight": draw(hidden, heads * d),
            "attn_gate_weight": draw(heads, hidden) * 3}


def _mixer_op(a, kind, heads, gated=True, **change):
    attrs = dict(num_heads=heads, num_kv_heads=2, head_dim=16, eps=1e-6,
                 window=5 if kind == SLIDING else 0,
                 **zoo._rope_attrs(ROPE[kind], 16))
    attrs.update(change)
    return get_op("_contrib_rotary_gqa_mixer").impl(
        a["x"], a["attn_norm_weight"], a["q_weight"], a["k_weight"],
        a["v_weight"], a["o_weight"],
        gate_weight=a["attn_gate_weight"] if gated else None, **attrs)


def _mixer_ref(a, kind, heads, cfg=CFG):
    cfg = dict(cfg, layer_types=[kind], num_attention_heads_per_layer=[heads])
    x = REF._rms(a["x"], a["attn_norm_weight"], 1e-6)
    return REF.attention(a, "", x, 0, cfg)


@pytest.mark.parametrize("kind, heads", [(SLIDING, 8), (FULL, 6)],
                         ids=["sliding_8_heads", "full_6_heads_half_rotary"])
def test_the_mixer_under_laguna_s_parameterisation(kind, heads):
    """Values and the gradient of every input against the reference's
    attention branch. 1e-4 of the largest entry: both are float32, the
    op's products at XLA's default precision on the CPU (float32) and
    the reference's at ``highest``; the sums run in other orders."""
    a = _mixer_args(11, heads)
    assert zoo._rope_attrs(ROPE[FULL], 16)["rotary_dim"] == 8
    assert "rotary_dim" not in zoo._rope_attrs(ROPE[SLIDING], 16)
    def sum_of_sines(mixer):
        def of(a):
            y = mixer(a, kind, heads)
            return jnp.sum(jnp.sin(y)), y
        return jax.jit(jax.value_and_grad(of, has_aux=True))

    with jax.default_matmul_precision("highest"):
        (got, y), got_g = sum_of_sines(_mixer_op)(a)
        (want, want_y), want_g = sum_of_sines(_mixer_ref)(a)
        np.testing.assert_allclose(y, want_y, rtol=1e-4, atol=1e-4)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for name in a:
        scale = float(jnp.abs(want_g[name]).max())
        np.testing.assert_allclose(got_g[name], want_g[name], rtol=0,
                                   atol=1e-4 * scale, err_msg=name)


def test_each_optional_term_of_the_mixer_is_in_its_result():
    a = _mixer_args(12, 6)
    base = _mixer_op(a, FULL, 6)
    for other in (_mixer_op(a, FULL, 6, gated=False),
                  _mixer_op(a, FULL, 6, rotary_dim=16),
                  _mixer_op(a, FULL, 6, rotary_dim=4)):
        assert float(jnp.abs(other - base).max()) > 1e-3
    # the whole head named outright is the whole head by default
    np.testing.assert_array_equal(_mixer_op(a, SLIDING, 6, rotary_dim=16),
                                  _mixer_op(a, SLIDING, 6))
    with pytest.raises(ValueError):
        _mixer_op(a, FULL, 6, rotary_dim=18)
    with pytest.raises(ValueError):
        _mixer_op(a, FULL, 6, rotary_dim=7)
    with pytest.raises(ValueError):     # one norm without the other
        get_op("_contrib_rotary_gqa_mixer").impl(
            a["x"], a["attn_norm_weight"], a["q_weight"], a["k_weight"],
            a["v_weight"], a["o_weight"], jnp.ones((16,), F32),
            num_heads=6, num_kv_heads=2, head_dim=16)


def _ramp_over_the_head_s_pairs(rope, head_dim, length, real=REF.rope_table):
    """The reference's table with YaRN's ``low`` and ``high`` counted
    with the head's lanes (``c(t) = head_dim ln(..) / (2 ln theta)``)
    where only ``r`` of them turn: the ramp of the rule applied to the
    wrong count of pairs."""
    if rope.get("rope_type") != "yarn":
        return real(rope, head_dim, length)
    theta = float(rope["rope_theta"])
    pairs = REF.rotary_lanes(rope, head_dim) // 2
    j = np.arange(pairs)
    whole = np.asarray(D._yarn_ramp(head_dim // 2, theta, rope["factor"], rope[
        "original_max_position_embeddings"], rope["beta_fast"],
        rope["beta_slow"]))[:pairs]
    freq = theta ** (-j / pairs)
    freq = freq * (1 - whole) + freq / rope["factor"] * whole
    angle = np.arange(length)[:, None] * freq
    return (jnp.asarray(np.cos(angle) * rope["attention_factor"], F32),
            jnp.asarray(np.sin(angle) * rope["attention_factor"], F32))


def _first_six_heads(w, p=""):
    """A sliding layer (its weights under the prefix ``p``) given the
    full layers' head count: its first 6 query heads (of 8), a head
    reading key-value head i // 3 where it read i // 4."""
    return dict(w, **{p + "q_weight": w[p + "q_weight"][:6 * 16],
                      p + "o_weight": w[p + "o_weight"][:, :6 * 16],
                      p + "attn_gate_weight": w[p + "attn_gate_weight"][:6]})


MIXER_FAULTS = {
    # name: (kind, what the reference is given in the model's place)
    "the_gate_taken_out": (FULL, dict(gating=False)),
    "the_full_layers_turned_over_the_whole_head": (FULL, dict(
        rope_parameters={FULL: dict(ROPE[FULL], partial_rotary_factor=1)})),
    "yarn_s_ramp_over_the_head_s_pairs": (FULL, "ramp"),
    "attention_factor_1": (FULL, dict(
        rope_parameters={FULL: dict(ROPE[FULL], attention_factor=1.0)})),
    "plain_rotary_on_the_full_layer": (FULL, dict(
        rope_parameters={FULL: dict(ROPE[SLIDING], rope_theta=500000,
                                    partial_rotary_factor=0.5)})),
    "one_head_count_for_both_kinds": (SLIDING, "six_heads"),
}


@pytest.mark.parametrize("fault", sorted(MIXER_FAULTS))
def test_a_wrong_mixer_fails_the_mixer_s_comparison(fault, monkeypatch):
    """Each new term matters: the reference of a mixer without it is a
    hundred times further from the op than the 1e-4 of
    ``test_the_mixer_under_laguna_s_parameterisation``, at 40
    positions."""
    kind, how = MIXER_FAULTS[fault]
    heads = 8 if kind == SLIDING else 6
    a = _mixer_args(15, heads, shape=(2, 40))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda a: _mixer_op(a, kind, heads))(a)
        if how == "ramp":
            # (eager: the wrong table is worked out in numpy)
            monkeypatch.setattr(REF, "rope_table", _ramp_over_the_head_s_pairs)
            wrong = _mixer_ref(a, kind, heads)
        elif how == "six_heads":
            wrong = jax.jit(lambda a: _mixer_ref(a, kind, 6))(
                _first_six_heads(a))
        else:
            wrong = jax.jit(lambda a: _mixer_ref(
                a, kind, heads, dict(CFG, **how)))(a)
    assert float(jnp.abs(wrong - got).max()) \
        > 1e-2 * float(jnp.abs(got).max()), fault


def test_the_unturned_lanes_carry_no_position():
    """With rotary over half a head, a score's part over lanes 8..15 is
    the same wherever the pair stands: moving every token one place on
    (positions + 1) changes nothing, as with rotary over the whole
    head; absolute positions changed unevenly change the result only
    through the turned lanes."""
    x = jnp.asarray(np.random.default_rng(5).normal(size=(1, 7, 3, 16)), F32)
    pos = jnp.arange(7)[None]
    angles = D._rotary_angles(pos, 4, 5e5, yarn=(64., 8., 64., 1.))
    turned = D._rotate(x, angles, 1.4158883)
    np.testing.assert_array_equal(turned[..., 8:], x[..., 8:])
    assert float(jnp.abs(turned[:, 1:, :, :8] - x[:, 1:, :, :8]).max()) > 1e-2
    # position 0 turns by nothing and carries the factor on 8 lanes
    np.testing.assert_allclose(turned[:, 0, :, :8], x[:, 0, :, :8] * 1.4158883,
                               rtol=1e-6)


def test_the_mixer_keeps_v_and_its_context_only(capsys):
    """Beside its arguments the gated mixer's checkpoint keeps the v
    projection's output and the attention's context (before the gate):
    no q, no k, no gate, no score block."""
    a = _mixer_args(13, 6)
    fn = lambda a: jnp.sum(_mixer_op(a, FULL, 6))
    jax.ad_checkpoint.print_saved_residuals(fn, a)
    kept = [line.split(" ")[0] for line in capsys.readouterr().out
            .splitlines() if "from the argument" not in line
            and "from a constant" not in line]
    assert kept == ["f32[2,21,32]", "f32[2,21,6,16]"]


def test_the_gate_stands_under_its_own_scope_forward_and_backward():
    a = _mixer_args(14, 8)
    text = jax.jit(jax.grad(lambda a: jnp.sum(_mixer_op(a, SLIDING, 8)))) \
        .lower(a).as_text(debug_info=True)
    gate = [line for line in text.splitlines() if "mx.attn.gate" in line]
    assert [l for l in gate if "logistic" in l or "exponential" in l]
    assert [l for l in gate if "transpose(jvp(mx.attn.rotary))" in l]
    assert telemetry.innermost_scope(
        "jit(f)/mx.attn.rotary/checkpoint/mx.attn.gate/mul") == "mx.attn.gate"
    assert telemetry.innermost_scope(
        "jit(f)/mx.attn.rotary/checkpoint/mx.attn.window/dot_general") \
        == "mx.attn.window"


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
    with jax.default_matmul_precision("highest"):
        want, want_logits, want_loss = jax.jit(lambda w: (
            REF.forward(w, ids, CFG), REF.logits(w, ids, CFG),
            REF.lm_loss(w, ids, labels, CFG)))(w)
    np.testing.assert_allclose(hidden.asnumpy(), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(hidden.asnumpy() @ w["head_weight"].T,
                               np.asarray(want_logits), rtol=1e-4, atol=1e-4)
    assert loss == pytest.approx(float(want_loss), rel=1e-5)


def test_the_gradient_of_every_parameter_matches_the_reference():
    """Hybridized (the symbolic path: the gate given by name past the
    norms that are left out, the shared expert past the score bias).
    2e-4 of a gradient's largest entry: float32 sums in other orders."""
    net, head = _build()
    net.hybridize()
    head.hybridize()
    ids, labels = _batch(1)
    params = {**net.collect_params(), **head.collect_params()}
    with autograd.record():
        loss = head(net(_ids(ids)), _ids(labels))
    loss.backward()
    w = _weights(net, head)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.grad(
            lambda w: REF.lm_loss(w, ids, labels, CFG)))(
            {k: jnp.asarray(v) for k, v in w.items()
             if not k.endswith(REF.FROZEN)})
    assert set(want) == {n for n in params if not n.endswith("expert_rows")}
    for name in sorted(want):
        got = params[name].grad().asnumpy()
        scale = float(np.abs(np.asarray(want[name])).max())
        assert scale > 0, name
        np.testing.assert_allclose(got, np.asarray(want[name]), rtol=0,
                                   atol=2e-4 * scale, err_msg=name)


def test_parameter_shapes_follow_the_three_lists():
    net, head = _build()
    assert [(l.kind, l.heads, l.mlp_kind) for l in net.layers] == [
        (FULL, 6, "dense"), (SLIDING, 8, "sparse"), (SLIDING, 8, "sparse")]
    full, sliding = net.layers[0]._attn, net.layers[1]._attn
    assert (full["num_heads"], full["window"], full["rotary_dim"]) == (6, 0, 8)
    assert (sliding["num_heads"], sliding["window"]) == (8, 5)
    assert "rotary_dim" not in sliding and "rope_yarn" not in sliding
    assert sliding["rope_theta"] == 1e4 and full["rope_theta"] == 5e5
    assert full["rope_yarn"] == (64.0, 4096.0, 64.0, 1.0)
    assert full["attention_factor"] == pytest.approx(0.1 * np.log(64) + 1)
    shapes = {k: v.shape for k, v in net.collect_params().items()}
    assert shapes["layers0_q_weight"] == (6 * 16, 48)
    assert shapes["layers0_o_weight"] == (48, 6 * 16)
    assert shapes["layers0_attn_gate_weight"] == (6, 48)
    assert shapes["layers1_q_weight"] == (8 * 16, 48)
    assert shapes["layers1_o_weight"] == (48, 8 * 16)
    assert shapes["layers1_attn_gate_weight"] == (8, 48)
    assert shapes["layers0_k_weight"] == shapes["layers1_v_weight"] == (32, 48)
    assert shapes["layers0_gate_up_weight"] == (192, 48)
    assert shapes["layers0_down_weight"] == (48, 96)
    assert shapes["layers1_router_weight"] == (16, 48)      # all routed
    assert shapes["layers1_experts_gate_up_weight"] == (4, 48, 48)
    assert shapes["layers1_experts_down_weight"] == (4, 48, 24)
    assert shapes["layers1_shared_gate_up_weight"] == (48, 48)
    assert shapes["layers1_shared_down_weight"] == (48, 24)
    assert not [n for n in shapes if n.endswith("bias") or "q_norm" in n
                or n.startswith("layers0_experts")
                or n.startswith("layers1_gate_up")]
    p = {k: v.data().asnumpy() for k, v in net.collect_params().items()}
    assert p["layers0_o_weight"].std() < 0.6 * p["layers0_q_weight"].std()
    assert abs(p["layers1_attn_gate_weight"].std() - 0.02) < 0.004
    # without gating no gate is built
    plain = zoo.LagunaModel(dict(CFG, gating=False), prefix="")
    assert not [n for n in plain.collect_params() if "attn_gate" in n]


@pytest.mark.parametrize("change", [
    dict(num_attention_heads_per_layer=[6, 8]),         # a short list
    dict(layer_types=[FULL, SLIDING]),
    dict(mlp_layer_types=["dense"]),
    dict(num_attention_heads_per_layer=[6, 7, 8]),      # 2 does not divide 7
    dict(num_attention_heads_per_layer=[6, 1, 8]),
    dict(layer_types=[FULL, "linear_attention", SLIDING]),
    dict(mlp_layer_types=["dense", "shared", "sparse"]),
    dict(experts_held=8, expert_offset=12),
    dict(sliding_window=0),
    dict(moe_apply_router_weight_on_input=True),
    dict(rope_parameters=dict(ROPE, **{FULL: {"rope_type": "llama3",
                                              "rope_theta": 5e5}}))])
def test_a_configuration_that_cannot_be_built_is_refused(change):
    with pytest.raises(ValueError):
        zoo.LagunaModel(dict(CFG, **change), prefix="")


def test_expert_rows_ride_as_auxiliary_states_and_paths_are_counted():
    telemetry.reset()
    was = telemetry.enabled()
    telemetry.enable(True)
    try:
        net, head = _build()
        step = _step(net, head)
        assert sorted(step.aux) == ["layers1_expert_rows",
                                    "layers2_expert_rows"]
        ids, labels = _batch()
        first = float(step.step(_ids(ids), _ids(labels)))
        assert float(step.step(_ids(ids), _ids(labels))) < first
        assert sorted(CFGMOD.expert_rows(step.aux)) == ["layers1", "layers2"]
        assert telemetry.counter("mx_moe_dropped_rows_total").value == 0
        assert telemetry.counter("mx_attn_window_path_total",
                                 path="xla").value >= 2
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


def test_two_adamw_steps_match_the_reference():
    """The loss before any update and after one and two: 2e-5, float32
    on both sides."""
    net, head = _build()
    w = _weights(net, head)
    step = TOY.reference_step(net, head)
    ids, labels = _batch(4)
    got = [float(step.step(_ids(ids), _ids(labels))) for _ in range(3)]
    want = REF.train_losses(w, (ids, labels), _sizes(), OPT, 3)
    np.testing.assert_allclose(got, want, rtol=2e-5)
    assert got[2] < got[1] < got[0]


# ---------------------------------------------------------------------------
# each new term matters: the reference of another model on the same
# weights gives other losses
# ---------------------------------------------------------------------------
def _wrong(**change):
    cfg = copy.deepcopy(CFG)
    cfg.update(change)
    return _sizes(cfg)


def _rope(kind, **change):
    return dict(ROPE, **{kind: dict(ROPE[kind], **change)})


def _sliding_layers_of_six_heads(w):
    return _first_six_heads(_first_six_heads(w, "layers1_"), "layers2_")


WRONG_MODELS = {
    "the_gate_taken_out": (_wrong(gating=False), None),
    "the_full_layers_turned_over_the_whole_head": (_wrong(
        rope_parameters=_rope(FULL, partial_rotary_factor=1)), None),
    "one_head_count_for_both_kinds": (_wrong(
        num_attention_heads_per_layer=[6] * 5), _sliding_layers_of_six_heads),
    "attention_factor_1": (_wrong(
        rope_parameters=_rope(FULL, attention_factor=1.0)), None),
    "every_layer_full": (_wrong(layer_types=[FULL] * 5,
                                rope_parameters=dict(ROPE, **{
                                    FULL: ROPE[SLIDING]})), None),
    "the_shared_expert_left_out": (_sizes(), "no_shared"),
    "the_router_s_weights_unscaled": (_wrong(moe_routed_scaling_factor=1.0),
                                      None),
}


@pytest.fixture(scope="module")
def right():
    """Seeded weights with q and k made 25 times larger, so that scores
    are of order one and where a key stands is in the result (at
    N(0, 0.02) every softmax is nearly flat)."""
    w = _weights(*_build())
    for name in w:
        if name.endswith(("q_weight", "k_weight")):
            w[name] = w[name] * 25
    batch = _batch(4)
    return w, batch, REF.train_losses(w, batch, _sizes(), OPT, 2)


@pytest.mark.parametrize("fault", sorted(WRONG_MODELS))
def test_a_wrong_model_gives_other_losses(fault, right, monkeypatch):
    """At least ten times outside the 2e-5 to which the system's steps
    agree. (YaRN's ramp counted over the head's pairs moves one pair
    of a toy head's four: the mixer's own comparison holds that fault,
    ``test_a_wrong_mixer_fails_the_mixer_s_comparison``.)"""
    w, batch, want = right
    sizes, how = WRONG_MODELS[fault]
    if how == "no_shared":
        monkeypatch.setattr(REF, "shared_expert", lambda w, p, x: 0.0)
    elif how is not None:
        w = how(w)
    wrong = REF.train_losses(w, batch, sizes, OPT, 2)
    assert max(abs(a - b) / b for a, b in zip(wrong, want)) > 2e-4, \
        (wrong, want)


def test_yarn_s_ramp_is_counted_over_the_rotary_lanes_pairs():
    """The published table: 32 pairs on a full layer, pairs 0..5 as
    trained, 16..31 slowed 64 times, as the configuration's equations
    say; the op's ramp and the reference's table agree."""
    sizes = manifest.load_json("configs", "laguna_xs2_33b_a3b.json")
    rope = sizes["rope_parameters"][FULL]
    ramp = np.asarray(D._yarn_ramp(32, 5e5, 64., 4096., 64., 1.))
    assert (ramp[:6] == 0).all() and (ramp[16:] == 1).all()
    assert 0 < ramp[6] < ramp[15] < 1
    over_64 = np.asarray(D._yarn_ramp(64, 5e5, 64., 4096., 64., 1.))
    assert (over_64[:12] == 0).all() and over_64[16] < 1
    cos, sin = REF.rope_table(rope, 128, 3)
    assert cos.shape == (3, 32)
    attrs = zoo._rope_attrs(rope, 128)
    assert attrs["rotary_dim"] == 64
    angles = D._rotary_angles(jnp.arange(3)[None], 32, attrs["rope_theta"],
                              yarn=attrs["rope_yarn"])[0]
    np.testing.assert_allclose(jnp.cos(angles) * attrs["attention_factor"],
                               cos, rtol=1e-5, atol=1e-6)
    inv = np.asarray(angles[1])
    np.testing.assert_allclose(inv[:6], 5e5 ** (-np.arange(6) / 32), rtol=1e-5)
    np.testing.assert_allclose(inv[16:], 5e5 ** (-np.arange(16, 32) / 32) / 64,
                               rtol=1e-5)


def test_the_eight_shares_and_the_shared_expert_add_up_to_the_uncut_layer():
    """Expert parallelism's share tied to the model: the routed terms of
    the eight shares (offsets 0, 2, .., 14 of the toy's 16 experts, each
    holding 2 and routing over all 16) plus the shared expert, which
    every chip computes alike, counted once, add up to what the uncut
    reference gives for the whole expert layer."""
    rng = np.random.default_rng(7)
    hidden, width, routed, held = 48, 24, 16, 2
    x = rng.normal(size=(2, 21, hidden)).astype(np.float32)
    gamma = 1 + 0.1 * rng.normal(size=(hidden,)).astype(np.float32)
    w = {"router_weight": rng.normal(size=(routed, hidden)),
         "experts_gate_up_weight": rng.normal(
             size=(routed, 2 * width, hidden)) * 0.2,
         "experts_down_weight": rng.normal(size=(routed, hidden, width)) * 0.2,
         "shared_gate_up_weight": rng.normal(size=(2 * width, hidden)) * 0.2,
         "shared_down_weight": rng.normal(size=(hidden, width)) * 0.2}
    w = {k: jnp.asarray(v, F32) for k, v in w.items()}
    cfg = dict(CFG, expert_offset=0)
    normed = REF._rms(jnp.asarray(x), gamma, 1e-6)
    with jax.default_matmul_precision("highest"):
        whole, shared = jax.jit(lambda w, x: (
            REF.experts(w, "", x, cfg), REF.shared_expert(w, "", x)))(
            w, normed)
    moe = get_op("_contrib_moe_mixer").impl
    attrs = dict(top_k=3, score_func="softmax", activation="swiglu",
                 routed_scaling_factor=2.5, eps=1e-6)

    def op(*share, expert_offset):      # one compiled program a share
        return jax.jit(lambda *a: moe(
            *a[:6], *([None] + list(a[6:]) if a[6:] else []),
            expert_offset=expert_offset, **attrs))(*share)

    total, routed_rows = np.asarray(shared, np.float64), 0.0
    for offset in range(0, routed, held):
        share = (jnp.asarray(x), jnp.asarray(gamma), w["router_weight"],
                 jnp.zeros((2, held), F32),
                 w["experts_gate_up_weight"][offset:offset + held],
                 w["experts_down_weight"][offset:offset + held])
        y, rows = op(*share, expert_offset=offset)
        total = total + np.asarray(y, np.float64)
        routed_rows += float(np.asarray(rows)[0].sum())
        # the mixer with its shared expert is that share plus the
        # shared expert's term
        both, _ = op(*share, w["shared_gate_up_weight"],
                     w["shared_down_weight"], expert_offset=offset)
        np.testing.assert_allclose(both, y + shared, rtol=1e-4, atol=1e-4)
    assert routed_rows == 2 * 21 * 3        # every choice held somewhere
    np.testing.assert_allclose(total, np.asarray(whole), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# the attention at groups of 6 and 8 and a window of one tile
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("heads, kv", [(6, 1), (12, 2), (8, 1)],
                         ids=["group_of_6", "two_groups_of_6", "group_of_8"])
@pytest.mark.parametrize("window", [128, None], ids=["one_tile", "causal"])
def test_the_kernel_at_laguna_s_groups_and_a_window_of_one_tile(heads, kv,
                                                                window):
    """Interpreted, three tiles of 128: a group that is no power of two
    (dk / dv accumulate over 6 heads and are written once a group) and
    a window equal to the tile (the diagonal tile and one edge tile a
    query tile, nothing between) against the composition and a whole
    mask. 2e-2 of the largest entry: bf16 results of sums taken in
    different orders, as tests/test_pallas_causal_gqa.py."""
    q, k, v, cot = qkv(heads + (window or 0), 384, heads, kv)
    got = value_and_grads(
        lambda *a: P.flash_causal_gqa(*a, 128, window), q, k, v, cot=cot)
    near(got, value_and_grads(
        lambda *a: D._causal_gqa(*a, 128, window), q, k, v, cot=cot), 2e-2)
    near(got, value_and_grads(
        lambda *a: window_ref(*a, window or 384),
        *(t.astype(F32) for t in (q, k, v)), cot=cot), 2e-2)


def test_a_window_of_one_tile_visits_two_key_tiles():
    """The diagonal tile and the one the band's edge crosses: one
    ``cond`` forward and one backward beside the causal program's, no
    loop over whole tiles between them."""
    q, k, v, _ = qkv(3, 384, 6, 1)

    def grad_text(window):
        fn = lambda *a: jnp.sum(P.flash_causal_gqa(*a, 128, window)
                                .astype(F32))
        return str(jax.make_jaxpr(jax.grad(fn, (0, 1, 2)))(q, k, v))

    assert grad_text(128).count("cond[") == grad_text(None).count("cond[") + 2
    assert grad_text(130).count("cond[") == grad_text(None).count("cond[") + 4


@pytest.mark.parametrize("heads, kv", [(6, 1), (8, 1)],
                         ids=["group_of_6", "group_of_8"])
def test_attend_with_a_window_of_one_query_block(heads, kv):
    """``_attend`` itself at two blocks of ``QUERY_BLOCK`` and a window
    of one: float32 takes the composition, bf16 the (interpreted)
    kernel, both the whole mask's values."""
    window = D.QUERY_BLOCK
    q, k, v, cot = qkv(heads, 2 * window, heads, kv)
    assert P.causal_gqa_available(q, k, v, window)
    got = value_and_grads(lambda *a: D._attend(*a, window=window),
                          q, k, v, cot=cot)
    f32 = [t.astype(F32) for t in (q, k, v)]
    assert not P.causal_gqa_available(*f32, window)
    want = value_and_grads(lambda *a: window_ref(*a, window), *f32, cot=cot)
    near(got, want, 2e-2)
    near(value_and_grads(lambda *a: D._attend(*a, window=window), *f32,
                         cot=cot), want, 1e-4)


# ---------------------------------------------------------------------------
# the symbol graph keeps an input's slot where an optional one before it
# is left out
# ---------------------------------------------------------------------------
def test_a_symbol_node_keeps_the_slots_of_inputs_given_past_a_gap():
    """The gate given by name past the q/k norms and the positions that
    are left out: the node records its inputs' slots, evaluates as the
    eager call does, and the slots survive ``tojson`` / ``load_json``."""
    from mxnet_tpu import sym
    a = _mixer_args(16, 6)
    names = ["x", "attn_norm_weight", "q_weight", "k_weight", "v_weight",
             "o_weight", "attn_gate_weight"]
    attrs = dict(num_heads=6, num_kv_heads=2, head_dim=16, eps=1e-6,
                 **zoo._rope_attrs(ROPE[FULL], 16))
    variables = [sym.var(n) for n in names]
    node = sym._contrib_rotary_gqa_mixer(*variables[:6],
                                         gate_weight=variables[6], **attrs)
    assert tuple(node._node.attrs["_input_slots"]) == (0, 1, 2, 3, 4, 5, 9)
    feed = {n: nd.array(np.asarray(a[n])) for n in names}
    want = nd._contrib_rotary_gqa_mixer(
        *(feed[n] for n in names[:6]), gate_weight=feed[names[6]], **attrs)
    for graph in (node, sym.load_json(node.tojson())):
        got = graph.eval(**feed)
        np.testing.assert_allclose(got.asnumpy(), want.asnumpy(), rtol=1e-6,
                                   atol=1e-6)
    np.testing.assert_allclose(want.asnumpy(), _mixer_op(a, FULL, 6),
                               rtol=1e-5, atol=1e-6)
    # without a gap nothing is recorded
    plain = sym._contrib_rotary_gqa_mixer(*variables[:6], **attrs)
    assert "_input_slots" not in plain._node.attrs
    # an input the op writes back cannot stand after a gap: its place
    # among the node's inputs is how the write finds it
    x, g, r, rows, w1, w2 = (sym.var(n) for n in "xgrswv")
    with pytest.raises(TypeError):
        sym._contrib_moe_mixer(x, g, expert_rows=rows, w1=w1, w2=w2,
                               top_k=2)          # no router weight
    shared = sym._contrib_moe_mixer(x, g, r, rows, w1, w2, shared_w1=w1,
                                    shared_w2=w2, top_k=2)
    assert tuple(shared._node.attrs["_input_slots"]) == (0, 1, 2, 3, 4, 5,
                                                         7, 8)
