"""The zoo's Mellum 2 model (gluon/model_zoo/mellum.py) at toy widths
on the CPU: the blocks against the benchmark's plain float32 reference
(logits, loss, gradients), layer kinds following ``layer_types``, the
step through ``ShardedTrainStep`` and its AdamW steps against the
reference's with wrong-model controls (every layer full, half the
window, plain rotary on the full layer, no attention factor), and the
four expert-parallel shares adding up to the uncut layer."""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from decoder_harness import OPT, Toy, ids as _ids
from mxnet_tpu import autograd, telemetry
from mxnet_tpu.gluon.model_zoo import mellum as zoo

SLIDING, FULL = zoo.KINDS
ROPE = {
    FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
           "original_max_position_embeddings": 16, "beta_fast": 32,
           "beta_slow": 1, "attention_factor": 1.2772588722239782},
    SLIDING: {"rope_type": "default", "rope_theta": 500000}}
CFG = dict(
    hidden_size=48, num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    rms_norm_eps=1e-6, sliding_window=5, rope_parameters=ROPE,
    layer_types=[SLIDING, SLIDING, FULL, SLIDING, FULL],
    mlp_layer_types=["sparse"] * 5,
    num_experts=16, experts_held=4, expert_offset=4,
    moe_intermediate_size=24, num_experts_per_tok=3, norm_topk_prob=True,
    num_hidden_layers=3, vocab_size=64)


TOY = Toy("mellum2_12b_a2_5b", zoo.MellumModel, zoo.MellumLMLoss, CFG)
REF, CFGMOD = TOY.ref, TOY.cfgmod
_build, _weights, _batch, _step, _sizes = (TOY.build, TOY.weights, TOY.batch,
                                           TOY.step, TOY.sizes)


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


def test_gradients_match_the_reference():
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
    for name in ("layers0_q_weight", "layers0_k_norm_weight",
                 "layers1_router_weight", "layers2_k_weight",
                 "layers2_experts_gate_up_weight", "layers1_o_weight",
                 "embed_weight", "head_weight"):
        got = params[name].grad().asnumpy()
        scale = float(np.abs(np.asarray(want[name])).max())
        assert scale > 0, name
        np.testing.assert_allclose(got, np.asarray(want[name]), rtol=0,
                                   atol=2e-4 * scale, err_msg=name)


def test_layer_kinds_follow_layer_types():
    net, head = _build()
    assert net.layer_types == (SLIDING, SLIDING, FULL)
    assert [layer.kind for layer in net.layers] == [SLIDING, SLIDING, FULL]
    sliding, full = net.layers[0]._attn, net.layers[2]._attn
    assert sliding["window"] == 5 and full["window"] == 0
    assert "rope_yarn" not in sliding and sliding["rope_theta"] == 5e5
    assert full["rope_yarn"] == (16.0, 16.0, 32.0, 1.0)
    assert full["attention_factor"] == pytest.approx(0.1 * np.log(16) + 1)
    # a list handed in takes the configuration's place
    other = zoo.MellumModel(CFG, layer_types=[FULL, SLIDING, SLIDING],
                            prefix="")
    assert [layer.kind for layer in other.layers] == [FULL, SLIDING, SLIDING]
    params = net.collect_params()
    assert params["layers1_router_weight"].shape == (16, 48)    # all routed
    assert params["layers1_experts_gate_up_weight"].shape == (4, 48, 48)
    assert params["layers1_experts_down_weight"].shape == (4, 48, 24)
    assert params["layers0_q_norm_weight"].shape == (8,)
    assert params["layers0_k_weight"].shape == (16, 48)
    assert not [n for n in params if n.endswith("bias") or "shared" in n]
    p = {k: v.data().asnumpy() for k, v in params.items()}
    np.testing.assert_array_equal(p["layers0_attn_norm_weight"], 1.0)
    assert p["layers0_o_weight"].std() < 0.6 * p["layers0_q_weight"].std()


def test_the_two_kinds_differ_and_positions_matter():
    """The same weights under another list of kinds give other hidden
    states: the window and the rotary table are in the result."""
    net, head = _build()
    ids, _ = _batch(2)
    w = _weights(net, head)
    def forward(kinds):
        return np.asarray(jax.jit(lambda w: REF.forward(
            w, ids, dict(CFG, layer_types=kinds)))(w))

    with jax.default_matmul_precision("highest"):
        base = forward(CFG["layer_types"])
        for kinds in ([FULL, SLIDING, FULL], [SLIDING, SLIDING, SLIDING]):
            assert np.abs(forward(kinds) - base).max() > 1e-3
    mx.random.seed(3)
    swapped = zoo.MellumModel(CFG, layer_types=[FULL, SLIDING, FULL],
                              prefix="")
    swapped.initialize()
    with autograd.pause():
        got = swapped(_ids(ids)).asnumpy()
    with jax.default_matmul_precision("highest"):
        want = forward([FULL, SLIDING, FULL])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("change", [
    dict(experts_held=8, expert_offset=12),
    dict(mlp_layer_types=["sparse", "dense", "sparse"]),
    dict(layer_types=[SLIDING, "linear_attention", FULL]),
    dict(layer_types=[SLIDING, FULL]),
    dict(sliding_window=0),
    dict(rope_parameters=dict(ROPE, **{FULL: {"rope_type": "llama3",
                                              "rope_theta": 5e5}}))])
def test_a_configuration_that_cannot_be_built_is_refused(change):
    with pytest.raises(ValueError):
        zoo.MellumModel(dict(CFG, **change), prefix="")


def test_expert_rows_ride_as_auxiliary_states_and_paths_are_counted():
    telemetry.reset()
    was = telemetry.enabled()
    telemetry.enable(True)
    try:
        net, head = _build()
        step = _step(net, head)
        aux = sorted(step.aux)
        assert aux == ["layers%d_expert_rows" % i for i in range(3)]
        assert not set(aux) & set(step.params)
        ids, labels = _batch()
        first = float(step.step(_ids(ids), _ids(labels)))
        second = float(step.step(_ids(ids), _ids(labels)))
        assert second < first
        rows = CFGMOD.expert_rows(step.aux)
        assert sorted(rows) == ["layers0", "layers1", "layers2"]
        assert telemetry.counter("mx_moe_dropped_rows_total").value == 0
        # two window layers, one full one, each counted in its own series
        assert telemetry.counter("mx_attn_window_path_total",
                                 path="xla").value >= 2
        assert telemetry.counter("mx_attn_causal_path_total",
                                 path="xla").value >= 1
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


def _wrong(**change):
    cfg = copy.deepcopy(CFG)
    cfg.update(change)
    return _sizes(cfg)


WRONG_MODELS = {
    "every_layer_full": _wrong(layer_types=[FULL, FULL, FULL]),
    "half_the_window": _wrong(sliding_window=2),
    "plain_rotary_on_the_full_layer": _wrong(
        rope_parameters=dict(ROPE, **{FULL: ROPE[SLIDING]})),
    "attention_factor_1": _wrong(
        rope_parameters=dict(ROPE, **{FULL: dict(ROPE[FULL],
                                                 attention_factor=1.0)})),
}


@pytest.fixture(scope="module")
def right():
    """The seeded weights, a batch, and the reference's losses on it
    before any update and after one and two."""
    w = _weights(*_build())
    batch = _batch(4)
    return w, batch, REF.train_losses(w, batch, _sizes(), OPT, 3)


def test_two_adamw_steps_match_the_reference(right):
    _, (ids, labels), want = right
    step = TOY.reference_step(*_build())
    got = [float(step.step(_ids(ids), _ids(labels))) for _ in range(3)]
    np.testing.assert_allclose(got, want, rtol=2e-5)
    assert got[2] < got[1] < got[0]


@pytest.mark.parametrize("fault", sorted(WRONG_MODELS))
def test_a_wrong_model_gives_other_losses(fault, right):
    """Far outside the 2e-5 to which the system's steps agree."""
    w, batch, want = right
    wrong = REF.train_losses(w, batch, WRONG_MODELS[fault], OPT, 2)
    assert max(abs(a - b) / b for a, b in zip(wrong, want[:2])) > 1e-3, \
        (wrong, want)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Expert parallelism's share tied to the model: the expert-layer
    outputs of the four shares (offsets 0, 4, 8, 12 of the toy's 16
    experts, each holding 4 and routing over all 16) add up to what the
    uncut reference gives for the whole layer."""
    from mxnet_tpu.ops import get_op
    rng = np.random.default_rng(7)
    hidden, width, routed, held = 48, 24, 16, 4
    x = rng.normal(size=(2, 21, hidden)).astype(np.float32)
    gamma = 1 + 0.1 * rng.normal(size=(hidden,)).astype(np.float32)
    w = {"router_weight": rng.normal(size=(routed, hidden)),
         "experts_gate_up_weight": rng.normal(
             size=(routed, 2 * width, hidden)) * 0.2,
         "experts_down_weight": rng.normal(
             size=(routed, hidden, width)) * 0.2}
    w = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
    cfg = dict(CFG, expert_offset=0)
    with jax.default_matmul_precision("highest"):
        whole = jax.jit(lambda w, x: REF.experts(
            w, "", REF._rms(x, gamma, 1e-6), cfg))(w, jnp.asarray(x))
    op = get_op("_contrib_moe_mixer").impl
    total, routed_rows = 0.0, 0.0
    for offset in range(0, routed, held):
        y, rows = jax.jit(lambda x, gamma, r, up, down, offset=offset: op(
            x, gamma, r, jnp.zeros((2, held), jnp.float32), up, down,
            top_k=3, expert_offset=offset, score_func="softmax",
            activation="swiglu", eps=1e-6))(
                jnp.asarray(x), jnp.asarray(gamma), w["router_weight"],
                w["experts_gate_up_weight"][offset:offset + held],
                w["experts_down_weight"][offset:offset + held])
        total = total + np.asarray(y, np.float64)
        routed_rows += float(np.asarray(rows)[0].sum())
    assert routed_rows == 2 * 21 * 3        # every choice held somewhere
    np.testing.assert_allclose(total, np.asarray(whole), rtol=1e-4, atol=1e-4)
