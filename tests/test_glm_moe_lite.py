"""The zoo's GLM-4.7-Flash model (gluon/model_zoo/glm_moe_lite.py) at
toy widths on the CPU: the latent-attention mixer and the whole model
against the benchmark's plain float32 reference (two hidden states,
both loss terms, the gradient of every parameter, AdamW steps through
``ShardedTrainStep``); that the one shared rotary key is in the result
(the reference without ``Rot``, or with a rotary key a head, gives
another); the multi-token-prediction term (its targets, the position
left out of its mean, the pad, the embedding's and the head's gradients
as sums over both uses); the eight expert-parallel shares adding up to
the uncut layer with the shared expert counted once; and causal
attention at 256-wide heads with a group of one, the flash kernel
interpreted against the composition."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from decoder_harness import OPT, Toy, ids as _ids
from mxnet_tpu import autograd, nd, telemetry
from mxnet_tpu.gluon.model_zoo import glm_moe_lite as zoo
from mxnet_tpu.ops import decoder_ops as D, get_op, pallas_causal_gqa
from numerics import BF, F32, jitted, near, normal, qkv, value_and_grads

CFG = dict(
    hidden_size=48, num_attention_heads=3, num_key_value_heads=3,
    q_lora_rank=20, kv_lora_rank=12, qk_nope_head_dim=10, qk_rope_head_dim=6,
    v_head_dim=16, rope_theta=1000000, rope_scaling=None,
    partial_rotary_factor=1, rms_norm_eps=1e-5, intermediate_size=80,
    moe_intermediate_size=24, n_routed_experts=16, experts_held=4,
    expert_offset=4, num_experts_per_tok=3, n_shared_experts=1,
    routed_scaling_factor=1.8, norm_topk_prob=True, topk_method="noaux_tc",
    n_group=1, topk_group=1, first_k_dense_replace=1, num_hidden_layers=3,
    num_nextn_predict_layers=1, vocab_size=64, mtp_loss_weight=0.1)
ATTN = dict(num_heads=3, qk_nope_head_dim=10, qk_rope_head_dim=6,
            v_head_dim=16, rope_theta=1e6, eps=1e-5)


def _sharper_scores(net):
    """The seeded queries and rotary keys made 12 times larger: at 48
    hidden lanes N(0, 0.02) weights give scores of 1e-3 and attention
    that looks at nothing, so that no position could matter (at the
    published widths the same init gives scores of 0.3)."""
    for name, p in net.collect_params().items():
        if name.endswith(("q_b_weight", "kv_a_weight")):
            p.set_data(p.data() * 12)


TOY = Toy("glm_4_7_flash_30b_a3b", zoo.Glm4MoeLiteModel,
          zoo.Glm4MoeLiteLMLoss, CFG, prepare=_sharper_scores)
REF, CFGMOD = TOY.ref, TOY.cfgmod
_build, _batch, _step, _sizes = TOY.build, TOY.batch, TOY.step, TOY.sizes


def _device_weights(net, head):
    return {k: jnp.asarray(v) for k, v in TOY.weights(net, head).items()}


def _trained(w):
    return {k: v for k, v in w.items()
            if not k.endswith(REF.STATES + REF.FROZEN)}


# ---------------------------------------------------------------------------
# (a) the mixer op and the whole model against the reference
# ---------------------------------------------------------------------------
MLA_NAMES = ("attn_norm_weight", "q_a_weight", "q_a_norm_weight",
             "q_b_weight", "kv_a_weight", "kv_a_norm_weight", "kv_b_weight",
             "o_weight")


def _mla_weights(seed=11, rope_keys=1):
    """Seeded weights of one latent-attention layer; ``rope_keys``
    rotary key heads a token (1 as published)."""
    rng = np.random.default_rng(seed)
    u, h = CFG["hidden_size"], CFG["num_attention_heads"]
    qr, kvr = CFG["q_lora_rank"], CFG["kv_lora_rank"]
    n, r, v = (CFG["qk_nope_head_dim"], CFG["qk_rope_head_dim"],
               CFG["v_head_dim"])
    shapes = dict(attn_norm_weight=(u,), q_a_weight=(qr, u),
                  q_a_norm_weight=(qr,), q_b_weight=(h * (n + r), qr),
                  kv_a_weight=(kvr + r, u), kv_a_norm_weight=(kvr,),
                  kv_b_weight=(h * (n + v), kvr), o_weight=(u, h * v))
    w = {k: (1 + 0.1 * rng.normal(size=s) if len(s) == 1
             else rng.normal(size=s) / np.sqrt(s[1])).astype(np.float32)
         for k, s in shapes.items()}
    if rope_keys > 1:       # the first head's rotary key is the shared one
        more = rng.normal(size=((rope_keys - 1) * r, u)) / np.sqrt(u)
        w["kv_a_weight"] = np.concatenate(
            [w["kv_a_weight"], more.astype(np.float32)])
    return {k: jnp.asarray(a) for k, a in w.items()}


def _mla_op(x, w):
    return get_op("_contrib_mla_mixer").impl(
        x, *(w[k] for k in MLA_NAMES), **ATTN)


def _mla_ref(x, w, cfg=CFG):
    return REF.attention(w, "", REF._rms(x, w["attn_norm_weight"],
                                         cfg["rms_norm_eps"]), cfg)


def test_mla_mixer_matches_the_reference_in_value_and_gradient():
    """float32 on both sides, the same sums in another order: 1e-4 of
    the largest entry."""
    w = _mla_weights()
    x = normal(jax.random.key(0), (2, 37, 48))
    cot = normal(jax.random.key(1), (2, 37, 48))
    with jax.default_matmul_precision("highest"):
        got = value_and_grads(_mla_op, x, w, cot=cot)
        want = value_and_grads(_mla_ref, x, w, cot=cot)
    assert len(want) == 2 + len(w)      # the value, d x, d every weight
    assert all(float(jnp.abs(r).max()) > 0 for r in want)
    near(got, want, 1e-4)


def test_mla_mixer_refuses_heads_of_two_widths():
    w = _mla_weights()
    with pytest.raises(ValueError, match="one head width"):
        get_op("_contrib_mla_mixer").impl(
            jnp.zeros((1, 8, 48)), *(w[k] for k in MLA_NAMES),
            **dict(ATTN, v_head_dim=8))


def test_hidden_states_and_both_loss_terms_match_the_reference():
    net, head = _build()
    ids, labels = _batch()
    with autograd.pause():
        hidden, mtp_hidden = net(_ids(ids))
        loss = head(hidden, mtp_hidden, _ids(labels)).asnumpy().item()
    w = _device_weights(net, head)
    with jax.default_matmul_precision("highest"):
        (want, want_mtp), (lm, mtp) = jax.jit(lambda w: (
            REF.forward(w, ids, CFG), REF.loss_terms(w, ids, labels, CFG)))(w)
    np.testing.assert_allclose(hidden.asnumpy(), want, rtol=1e-4, atol=1e-4)
    # the reference slices the module's last position off; the program
    # pads it
    assert mtp_hidden.shape == (2, 21, 48) and want_mtp.shape == (2, 20, 48)
    np.testing.assert_allclose(mtp_hidden.asnumpy()[:, :-1], want_mtp,
                               rtol=1e-4, atol=1e-4)
    assert loss == pytest.approx(float(lm + 0.1 * mtp), rel=1e-5)
    np.testing.assert_allclose(head.loss_terms.data().asnumpy(),
                               [float(lm), float(mtp)], rtol=1e-5)


def _program_grads(net, head, ids, labels):
    net.hybridize()
    head.hybridize()
    with autograd.record():
        loss = head(*net(_ids(ids)), _ids(labels))
    loss.backward()
    params = {**net.collect_params(), **head.collect_params()}
    return {k: p.grad().asnumpy() for k, p in params.items()
            if p.grad_req != "null"}


def test_gradients_of_every_parameter_match_the_reference():
    net, head = _build()
    ids, labels = _batch(1)
    got = _program_grads(net, head, ids, labels)
    w = _device_weights(net, head)
    fixed = {k: v for k, v in w.items() if k.endswith(REF.FROZEN)}
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.grad(lambda t: REF.lm_loss(
            dict(t, **fixed), ids, labels, CFG)))(_trained(w))
    assert set(got) == set(want) and len(want) == 60
    for name, ref in want.items():
        scale = float(jnp.abs(ref).max())
        assert scale > 0, name
        np.testing.assert_allclose(got[name], ref, rtol=0,
                                   atol=2e-4 * scale, err_msg=name)


def test_mlp_kinds_follow_first_k_dense_replace():
    net, head = _build()
    assert net.mlp_kinds == ("dense", "sparse", "sparse")
    assert [layer.kind for layer in net.layers] == list(net.mlp_kinds)
    assert net.mtp.block.kind == "sparse"
    params = net.collect_params()
    assert params["layers0_gate_up_weight"].shape == (160, 48)
    assert "layers0_router_weight" not in params
    assert params["layers1_router_weight"].shape == (16, 48)    # all routed
    assert params["layers1_experts_gate_up_weight"].shape == (4, 48, 48)
    assert params["layers1_shared_gate_up_weight"].shape == (48, 48)
    assert params["layers1_kv_a_weight"].shape == (12 + 6, 48)  # one key head
    assert params["layers1_kv_b_weight"].shape == (3 * 26, 12)
    assert params["mtp_combine_weight"].shape == (48, 96)
    assert not [n for n in params if n.endswith("bias")
                and "e_score_correction" not in n]
    two = zoo.Glm4MoeLiteModel(dict(CFG, first_k_dense_replace=2), prefix="")
    assert two.mlp_kinds == ("dense", "dense", "sparse")
    p = {k: v.data().asnumpy() for k, v in params.items()}
    np.testing.assert_array_equal(p["layers0_attn_norm_weight"], 1.0)
    assert p["layers0_o_weight"].std() < 0.6 * p["layers0_q_a_weight"].std()
    assert np.abs(p["layers1_e_score_correction_bias"]).max() <= 0.01


@pytest.mark.parametrize("change", [
    dict(experts_held=8, expert_offset=12),
    dict(num_key_value_heads=1),
    dict(num_nextn_predict_layers=0),
    dict(num_nextn_predict_layers=2),
    dict(rope_scaling={"rope_type": "yarn", "factor": 4}),
    dict(n_group=4, topk_group=2),
    dict(topk_method="greedy"),
    dict(v_head_dim=8)])
def test_a_configuration_that_cannot_be_built_is_refused(change):
    with pytest.raises(ValueError):
        net = zoo.Glm4MoeLiteModel(dict(CFG, **change), prefix="")
        net.initialize()
        net(_ids(_batch()[0]))


def test_states_ride_as_auxiliary_states_and_paths_are_counted():
    telemetry.reset()
    was = telemetry.enabled()
    telemetry.enable(True)
    try:
        net, head = _build()
        step = _step(net, head)
        blocks = ["layers1", "layers2", "mtp_block"]
        assert sorted(step.aux) == sorted(
            ["%s_%s" % (b, s) for b in blocks
             for s in ("expert_rows", "e_score_correction_bias")]
            + ["loss_terms"])
        assert not set(step.aux) & set(step.params)
        assert "embed_weight" in step.params and "head_weight" in step.params
        ids, labels = _batch()
        bias = np.asarray(step.aux["layers1_e_score_correction_bias"])
        first = float(step.step(_ids(ids), _ids(labels)))
        second = float(step.step(_ids(ids), _ids(labels)))
        assert second < first
        np.testing.assert_array_equal(       # seeded, never updated
            bias, np.asarray(step.aux["layers1_e_score_correction_bias"]))
        rows = CFGMOD.expert_rows(step.aux)
        assert sorted(rows) == blocks
        assert telemetry.counter("mx_moe_dropped_rows_total").value == 0
        lm, mtp = (telemetry.gauge(n).value
                   for n in ("mx_lm_loss", "mx_mtp_loss"))
        assert second == pytest.approx(lm + 0.1 * mtp, rel=1e-6)
        assert 0 < lm < first and 0 < mtp
        # three blocks of the stack and the module's, one trace
        assert telemetry.counter("mx_attn_causal_path_total",
                                 path="xla").value >= 4
        assert zoo.publish_loss_terms({}) is None
    finally:
        telemetry.enable(was)
        telemetry.reset()


def test_sharded_step_matches_the_reference_in_bfloat16_within_reason():
    net, head = _build()
    w = _device_weights(net, head)
    step = _step(net, head, dtype="bfloat16")
    ids, labels = _batch(2)
    got = float(step.step(_ids(ids), _ids(labels)))
    with jax.default_matmul_precision("highest"):
        want = float(jax.jit(lambda w: REF.lm_loss(w, ids, labels, CFG))(w))
    assert got == pytest.approx(want, rel=5e-3)


@pytest.fixture(scope="module")
def right():
    """The seeded weights, a batch, and the reference's losses on it
    before any update and after one and two."""
    w = TOY.weights(*_build())
    batch = _batch(4)
    return w, batch, REF.train_losses(w, batch, _sizes(), OPT, 3)


def test_adamw_steps_match_the_reference(right):
    """Three losses, the last after two updates: float32 on both sides,
    2e-5 as the other decoders' steps agree."""
    _, (ids, labels), want = right
    step = TOY.reference_step(*_build())
    got = [float(step.step(_ids(ids), _ids(labels))) for _ in range(3)]
    np.testing.assert_allclose(got, want, rtol=2e-5)
    assert got[2] < got[1] < got[0]


# ---------------------------------------------------------------------------
# (b) the shared rotary key, and the other terms, are in the result
# ---------------------------------------------------------------------------
def test_the_shared_rotary_key_matters():
    """The op agrees with the reference to 1e-4; the reference with
    ``Rot`` taken out (no lane turns), and the reference with a rotary
    key a head (heads past the first read keys of their own), are
    hundreds of times further from it."""
    x = normal(jax.random.key(2), (2, 37, 48))
    w = _mla_weights()
    with jax.default_matmul_precision("highest"):
        got = jitted(_mla_op)(x, w)
        right = jitted(_mla_ref)(x, w)
        no_rot = jax.jit(lambda x, w: _mla_ref(
            x, w, dict(CFG, partial_rotary_factor=0)))(x, w)
        per_head = jitted(_mla_ref)(x, _mla_weights(rope_keys=3))
        # the per-head reference is the shared one when every head's
        # key rows are the first head's: the fault is the keys, not the
        # code path
        tiled = dict(w, kv_a_weight=jnp.concatenate(
            [w["kv_a_weight"]] + [w["kv_a_weight"][-6:]] * 2))
        same = jitted(_mla_ref)(x, tiled)
    scale = float(jnp.abs(right).max())
    assert float(jnp.abs(got - right).max()) < 1e-4 * scale
    assert float(jnp.abs(same - right).max()) < 1e-5 * scale
    for wrong in (no_rot, per_head):
        assert float(jnp.abs(got - wrong).max()) > 3e-2 * scale


WRONG_MODELS = {
    "rot_taken_out": _sizes(partial_rotary_factor=0),
    "another_theta": _sizes(rope_theta=100),
    "no_routed_scaling": _sizes(routed_scaling_factor=1.0),
    "weights_not_renormalised": _sizes(norm_topk_prob=False),
    "no_shared_expert": _sizes(n_shared_experts=0),
    "no_mtp_term": _sizes(mtp_loss_weight=0.0),
    "mtp_weight_0_3": _sizes(mtp_loss_weight=0.3),
}


@pytest.mark.parametrize("fault", sorted(WRONG_MODELS))
def test_a_wrong_model_gives_other_losses(fault, right):
    """Far outside the 2e-5 to which the system's steps agree."""
    w, batch, want = right
    wrong = REF.train_losses(w, batch, WRONG_MODELS[fault], OPT, 2)
    drop = abs((wrong[0] - wrong[1]) - (want[0] - want[1])) \
        / (want[0] - want[1])
    assert max(abs(wrong[0] - want[0]) / want[0], drop) > 1e-3, (wrong, want)


def test_a_rotary_key_a_head_gives_other_losses(right):
    """The whole model's reference with rotary keys of their own for
    the heads past the first, in every block: the same comparison."""
    w, batch, want = right
    rng = np.random.default_rng(5)
    wrong_w = dict(w)
    for name in [k for k in w if k.endswith("kv_a_weight")]:
        more = rng.normal(size=(2 * 6, 48)) * 0.24
        wrong_w[name] = np.concatenate([w[name], more.astype(np.float32)])
    wrong = REF.train_losses(wrong_w, batch, _sizes(), OPT, 2)
    drop = abs((wrong[0] - wrong[1]) - (want[0] - want[1])) \
        / (want[0] - want[1])
    assert max(abs(wrong[0] - want[0]) / want[0], drop) > 1e-3, (wrong, want)


# ---------------------------------------------------------------------------
# (c) the multi-token-prediction term
# ---------------------------------------------------------------------------
def _terms(net, head, ids, labels):
    with autograd.pause():
        total = head(*net(_ids(ids)), _ids(labels)).asnumpy().item()
    lm, mtp = head.loss_terms.data().asnumpy()
    assert total == pytest.approx(lm + 0.1 * mtp, rel=1e-6)
    return float(lm), float(mtp)


def test_the_modules_targets_are_the_tokens_two_ahead():
    """Position t of the module is scored against ``labels[t + 1]`` =
    ``x[t + 2]``: the first label is no target of the module's, every
    other is, and the last position has no target at all."""
    net, head = _build()
    ids, labels = _batch(6)
    lm, mtp = _terms(net, head, ids, labels)
    w = _device_weights(net, head)
    with jax.default_matmul_precision("highest"):
        hidden, mtp_hidden = jax.jit(lambda w: REF.forward(w, ids, CFG))(w)
        logp = jax.nn.log_softmax(mtp_hidden @ w["head_weight"].T, -1)
        by_hand = -jnp.take_along_axis(
            logp, jnp.asarray(labels)[:, 1:, None], -1).mean()
    assert mtp == pytest.approx(float(by_hand), rel=1e-5)
    first = labels.copy()
    first[:, 0] = (first[:, 0] + 1) % 64
    lm_first, mtp_first = _terms(net, head, ids, first)
    assert mtp_first == mtp and lm_first != lm
    last = labels.copy()
    last[:, -1] = (last[:, -1] + 1) % 64
    lm_last, mtp_last = _terms(net, head, ids, last)
    assert mtp_last != mtp and lm_last != lm


def test_the_last_position_is_out_of_the_mean_and_the_pad_changes_nothing():
    net, head = _build()
    ids, labels = _batch(7)
    with autograd.pause():
        hidden, mtp_hidden = net(_ids(ids))
        x = net.embed(_ids(ids))
        for layer in net.layers:
            x = layer(x)
        want = head(hidden, mtp_hidden, _ids(labels)).asnumpy().item()
        # the module fed the same next tokens under two other pads
        for pad in (0, 63):
            nxt = np.concatenate([ids[:, 1:], np.full((2, 1), pad, np.int32)],
                                 axis=1)
            other = net.mtp(net.embed(_ids(nxt)), x)
            np.testing.assert_array_equal(other.asnumpy()[:, :-1],
                                          mtp_hidden.asnumpy()[:, :-1])
            assert not np.array_equal(other.asnumpy()[:, -1],
                                      mtp_hidden.asnumpy()[:, -1])
            assert head(hidden, other, _ids(labels)).asnumpy().item() == want
        # and whatever stands at the last position gets no say
        junk = mtp_hidden.asnumpy().copy()
        junk[:, -1] = 1e3
        assert head(hidden, nd.array(junk),
                    _ids(labels)).asnumpy().item() == want
    # the program's pad is the row's first token
    shifted = zoo._shift_left(nd, _ids(ids)).asnumpy()
    np.testing.assert_array_equal(shifted[:, :-1], ids[:, 1:])
    np.testing.assert_array_equal(shifted[:, -1], ids[:, 0])


def test_embedding_and_head_gradients_are_the_sums_of_both_uses():
    net, head = _build()
    ids, labels = _batch(8)
    got = _program_grads(net, head, ids, labels)
    w = _device_weights(net, head)
    fixed = {k: v for k, v in w.items() if k.endswith(REF.FROZEN)}
    with jax.default_matmul_precision("highest"):
        lm, mtp = (jax.jit(jax.grad(lambda t, i=i: REF.loss_terms(
            dict(t, **fixed), ids, labels, CFG)[i]))(_trained(w))
            for i in (0, 1))
    for name in ("embed_weight", "head_weight"):
        a, b = np.asarray(lm[name]), np.asarray(mtp[name])
        scale = np.abs(a).max()
        assert np.abs(b).max() > 0.05 * scale, name     # both uses count
        np.testing.assert_allclose(got[name], a + 0.1 * b, rtol=0,
                                   atol=2e-4 * scale, err_msg=name)
        assert np.abs(got[name] - a).max() > 1e-3 * scale, name
    # the stack's own weights hear the module through g_t; the module's
    # hear nothing from the first term
    assert np.abs(np.asarray(mtp["layers2_o_weight"])).max() > 0
    assert np.abs(np.asarray(lm["mtp_combine_weight"])).max() == 0


# ---------------------------------------------------------------------------
# (d) the share ties to the model
# ---------------------------------------------------------------------------
def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Expert parallelism's share tied to the model: the routed terms
    of the eight shares (offsets 0, 8, .., 56 of 64 experts, each
    holding 8 and routing over all 64, top 4, sigmoid scores with the
    selection bias, x 1.8) plus the shared expert, which every chip
    computes alike, counted once, add up to what the uncut reference
    gives for the whole layer."""
    rng = np.random.default_rng(7)
    hidden, width, routed, held = 48, 24, 64, 8
    x = jnp.asarray(rng.normal(size=(2, 21, hidden)), F32)
    gamma = jnp.asarray(1 + 0.1 * rng.normal(size=(hidden,)), F32)
    w = {"router_weight": rng.normal(size=(routed, hidden)) * 0.3,
         "e_score_correction_bias": rng.uniform(-0.05, 0.05, (routed,)),
         "experts_gate_up_weight": rng.normal(
             size=(routed, 2 * width, hidden)) * 0.2,
         "experts_down_weight": rng.normal(
             size=(routed, hidden, width)) * 0.2,
         "shared_gate_up_weight": rng.normal(size=(2 * width, hidden)) * 0.2,
         "shared_down_weight": rng.normal(size=(hidden, width)) * 0.2}
    w = {k: jnp.asarray(v, F32) for k, v in w.items()}
    cfg = dict(CFG, expert_offset=0, num_experts_per_tok=4)
    with jax.default_matmul_precision("highest"):
        whole = jax.jit(lambda w, x: REF.experts(
            w, "", REF._rms(x, gamma, 1e-5), cfg))(w, x)
    op = get_op("_contrib_moe_mixer").impl

    def share(offset, shared):
        y, rows = jax.jit(lambda x, up, down, *shared: op(
            x, gamma, w["router_weight"], jnp.zeros((2, held), F32), up,
            down, w["e_score_correction_bias"], *(shared or (None, None)),
            top_k=4, expert_offset=offset, routed_scaling_factor=1.8,
            norm_topk_prob=True, score_func="sigmoid", activation="swiglu",
            eps=1e-5))(
                x, w["experts_gate_up_weight"][offset:offset + held],
                w["experts_down_weight"][offset:offset + held],
                *((w["shared_gate_up_weight"], w["shared_down_weight"])
                  if shared else ()))
        return np.asarray(y, np.float64), float(np.asarray(rows)[0].sum())

    total, routed_rows = 0.0, 0.0
    with jax.default_matmul_precision("highest"):
        for offset in range(0, routed, held):
            y, n = share(offset, shared=False)
            total, routed_rows = total + y, routed_rows + n
        with_shared, _ = share(0, shared=True)
        total = total + (with_shared - share(0, shared=False)[0])
    assert routed_rows == 2 * 21 * 4        # every choice held somewhere
    np.testing.assert_allclose(total, np.asarray(whole), rtol=1e-4, atol=1e-4)
    # and the shared expert is a visible part of it
    assert np.abs(with_shared - share(0, False)[0]).max() \
        > 0.1 * np.abs(np.asarray(whole)).max()


# ---------------------------------------------------------------------------
# (e) causal attention at 256-wide heads, a group of one
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("length, tile", [(256, 128), (512, 512)],
                         ids=["two_tiles_of_128", "one_tile_of_512"])
def test_the_kernel_at_256_lanes_matches_the_composition(length, tile):
    """The flash kernel interpreted, as tests/test_pallas_causal_gqa.py
    interprets it at 128 lanes: every query head its own key head, 256
    lanes a head; bf16 results of sums taken in two orders, 2e-2 of the
    largest entry."""
    q, k, v, cot = qkv(length, length, 3, 3, d=256)
    got = value_and_grads(
        lambda *a: pallas_causal_gqa.flash_causal_gqa(*a, tile), q, k, v,
        cot=cot)
    near(got, value_and_grads(lambda *a: D._causal_gqa(*a, tile),
                              q, k, v, cot=cot), 2e-2)


def test_attend_takes_256_lanes_and_a_group_of_one():
    """``_attend``'s ladder at the cell's head shape: bf16, whole
    512-tiles, 256 lanes and as many key heads as query heads are a
    call the kernel serves, at 8,192 tokens too; at 16,384 the
    backward's resident k, v, dk, dv no longer fit its VMEM budget and
    the composition takes it. Float32 takes the composition, and the
    two agree."""
    shape = lambda n: jax.ShapeDtypeStruct((1, n, 20, 256), BF)
    assert pallas_causal_gqa.causal_gqa_available(*[shape(8192)] * 3, 512)
    assert not pallas_causal_gqa.causal_gqa_available(*[shape(16384)] * 3,
                                                      512)
    assert pallas_causal_gqa._bwd_vmem_bytes(8192, 256, 512) == 60_817_408
    q, k, v, cot = qkv(9, 512, 2, 2, d=256)
    assert pallas_causal_gqa.causal_gqa_available(q, k, v, D.QUERY_BLOCK)
    got = value_and_grads(D._attend, q, k, v, cot=cot)
    f32 = [t.astype(F32) for t in (q, k, v)]
    assert not pallas_causal_gqa.causal_gqa_available(*f32, D.QUERY_BLOCK)
    near(got, value_and_grads(D._attend, *f32, cot=cot), 2e-2)
