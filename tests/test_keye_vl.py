"""The zoo's Keye-VL language model (gluon/model_zoo/keye_vl.py) at toy
widths on the CPU: the blocks against the benchmark's plain float32
reference, M-RoPE position ids through the model, the second loss
through ``ShardedTrainStep``, the auxiliary states and their gauges, and
AdamW steps against the reference's, with a wrong-model control."""
import copy

import jax
import numpy as np
import pytest

from decoder_harness import OPT, Toy, ids as _ids
from mxbench import manifest
from mxnet_tpu import autograd, telemetry
from mxnet_tpu.gluon.model_zoo import keye_vl as zoo

CFG = dict(
    hidden_size=48, num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    rms_norm_eps=1e-6, rope_theta=1e7,
    rope_scaling={"mrope_section": [1, 2, 1]},
    sa_config={"indexer_head_dim": 8, "indexer_num_heads": 4,
               "indexer_num_kv_heads": 1, "topk": 6},
    num_experts=16, experts_held=4, expert_offset=4,
    moe_intermediate_size=24, num_experts_per_tok=3, norm_topk_prob=True,
    num_hidden_layers=3, vocab_size=64, decoder_sparse_step=1,
    mlp_only_layers=[])


TOY = Toy("keye_vl2_30b_a3b", zoo.KeyeVLTextModel, zoo.KeyeVLLMLoss, CFG)
REF, CFGMOD = TOY.ref, TOY.cfgmod
_build, _weights, _batch, _step, _sizes = (TOY.build, TOY.weights, TOY.batch,
                                           TOY.step, TOY.sizes)


def test_blocks_and_both_losses_match_the_reference():
    net, head = _build()
    ids, labels = _batch()
    with autograd.pause():
        hidden, index_loss = net(_ids(ids))
        loss = head(hidden, index_loss, _ids(labels)).asnumpy().item()
    w = _weights(net, head)
    with jax.default_matmul_precision("highest"):
        (want, want_index), want_loss = jax.jit(lambda w: (
            REF.forward(w, ids, CFG), REF.lm_loss(w, ids, labels, CFG)))(w)
    want_loss = float(want_loss)
    np.testing.assert_allclose(hidden.asnumpy(), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    assert index_loss.shape == (1,)
    assert index_loss.asnumpy().item() == pytest.approx(float(want_index),
                                                        rel=1e-4)
    assert float(want_index) > 0.01       # three layers' worth, not noise
    assert loss == pytest.approx(want_loss, rel=1e-5)


def test_position_ids_with_three_distinct_axes_reach_every_layer():
    net, head = _build()
    ids, _ = _batch(1)
    pos = np.random.default_rng(2).integers(0, 300, (3,) + ids.shape,
                                            dtype=np.int32)
    with autograd.pause():
        hidden, index_loss = net(_ids(ids), _ids(pos))
        text, _ = net(_ids(ids))
    w = _weights(net, head)
    with jax.default_matmul_precision("highest"):
        want, want_index = jax.jit(lambda w: REF.forward(
            w, ids, CFG, positions=pos))(w)
    np.testing.assert_allclose(hidden.asnumpy(), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    assert index_loss.asnumpy().item() == pytest.approx(float(want_index),
                                                        rel=1e-4)
    assert np.abs(hidden.asnumpy() - text.asnumpy()).max() > 1e-3


def test_layers_and_the_share():
    net, head = _build()
    assert len(net.layers) == 3
    params = net.collect_params()
    assert params["layers1_router_weight"].shape == (16, 48)    # all routed
    assert params["layers1_experts_gate_up_weight"].shape == (4, 48, 48)
    assert params["layers1_experts_down_weight"].shape == (4, 48, 24)
    assert params["layers2_index_q_weight"].shape == (32, 48)
    assert params["layers2_index_k_weight"].shape == (8, 48)
    assert params["layers2_index_w_weight"].shape == (4, 48)
    assert params["layers0_q_norm_weight"].shape == (8,)
    assert params["layers0_k_weight"].shape == (16, 48)
    biases = [n for n in params if n.endswith("bias")]
    assert biases == ["layers%d_index_k_norm_bias" % i for i in range(3)]
    assert not [n for n in params if "shared" in n or "score" in n]
    # seeded, and the same again from the same seed
    p = {k: v.data().asnumpy() for k, v in params.items()}
    np.testing.assert_array_equal(p["layers0_attn_norm_weight"], 1.0)
    np.testing.assert_array_equal(p["layers0_index_k_norm_bias"], 0.0)
    np.testing.assert_array_equal(p["layers1_dsa_state"], 0.0)
    assert p["layers0_o_weight"].std() < 0.6 * p["layers0_q_weight"].std()
    again, _ = _build()
    np.testing.assert_array_equal(
        p["layers2_router_weight"],
        again.collect_params()["layers2_router_weight"].data().asnumpy())


@pytest.mark.parametrize("change", [
    dict(experts_held=8, expert_offset=12),
    dict(mlp_only_layers=[0]),
    dict(decoder_sparse_step=2),
    dict(sa_config=dict(CFG["sa_config"], indexer_num_kv_heads=2))])
def test_a_configuration_that_cannot_be_built_is_refused(change):
    with pytest.raises(ValueError):
        zoo.KeyeVLTextModel(dict(CFG, **change), prefix="")


def test_counts_and_selector_states_ride_as_auxiliary_states():
    """Not trainable: no gradient, no optimizer state; rewritten by the
    step; the second loss goes through ``trace_block`` with the net's
    whole output."""
    net, head = _build()
    step = _step(net, head)
    aux = sorted(step.aux)
    assert aux == sorted("layers%d_%s" % (i, n) for i in range(3)
                         for n in ("dsa_state", "expert_rows"))
    assert not set(aux) & set(step.params) and not set(aux) & set(step.states)
    ids, labels = _batch()
    first = float(step.step(_ids(ids), _ids(labels)))
    state = np.asarray(step.aux["layers1_dsa_state"])
    second = float(step.step(_ids(ids), _ids(labels)))
    assert second < first
    keys = sum(min(t + 1, 6) for t in range(21)) / 21
    assert state[0] == pytest.approx(keys) and state[1] > 0
    rows = np.asarray(step.aux["layers1_expert_rows"])
    assert rows.shape == (2, 4) and rows[0].sum() > 0
    np.testing.assert_array_equal(rows[0], rows[1])
    # the index loss is in the step's loss: the reference's total
    w = _weights(*_build())
    with jax.default_matmul_precision("highest"):
        assert first == pytest.approx(float(jax.jit(
            lambda w: REF.lm_loss(w, ids, labels, CFG))(w)), rel=1e-5)


def test_selector_states_and_expert_rows_are_published():
    telemetry.reset()
    was = telemetry.enabled()
    telemetry.enable(True)      # the path counter is an event: off, none
    try:
        _published()
    finally:
        telemetry.enable(was)


def _published():
    net, head = _build()
    step = _step(net, head)
    ids, labels = _batch(1)
    step.step(_ids(ids), _ids(labels))
    states = zoo.publish_selector_state(step.aux)
    assert sorted(states) == ["layers0", "layers1", "layers2"]
    want = np.asarray(step.aux["layers2_dsa_state"])
    assert states["layers2"] == pytest.approx(tuple(want))
    assert telemetry.gauge("mx_attn_keys_per_query",
                           block="layers2").value == pytest.approx(want[0])
    assert telemetry.gauge("mx_attn_index_loss",
                           block="layers2").value == pytest.approx(want[1])
    rows = CFGMOD.expert_rows(step.aux)     # both, as the benchmark reads
    assert sorted(rows) == ["layers0", "layers1", "layers2"]
    assert telemetry.counter("mx_moe_dropped_rows_total").value == 0
    assert telemetry.counter("mx_attn_sparse_path_total",
                             path="masked").value >= 3
    reader = manifest.layer_metric("sparse_keys_per_query.train")
    assert reader.read(None) == pytest.approx(want[0])
    telemetry.reset()
    assert reader.read(None) is None


def test_sharded_step_matches_the_reference_in_bfloat16_within_reason():
    net, head = _build()
    w = _weights(net, head)
    step = _step(net, head, dtype="bfloat16")
    ids, labels = _batch(2)
    got = float(step.step(_ids(ids), _ids(labels)))
    with jax.default_matmul_precision("highest"):
        want = float(jax.jit(lambda w: REF.lm_loss(w, ids, labels, CFG))(w))
    assert got == pytest.approx(want, rel=5e-3)


def test_two_adamw_steps_match_the_reference_and_a_wrong_model_does_not():
    """Both losses through the shared ``_apply_update`` rule; the same
    comparison calls a model with half the top-k, one without the q/k
    norms and one whose selector is trained by nothing wrong."""
    net, head = _build()
    w = _weights(net, head)
    step = TOY.reference_step(net, head)
    ids, labels = _batch(4)
    got = [float(step.step(_ids(ids), _ids(labels))) for _ in range(3)]
    want = REF.train_losses(w, (ids, labels), _sizes(), OPT, 3)
    np.testing.assert_allclose(got, want, rtol=2e-5)
    assert got[2] < got[1] < got[0]

    half = copy.deepcopy(CFG)
    half["sa_config"]["topk"] = 3
    wrong = REF.train_losses(w, (ids, labels), _sizes(half), OPT, 3)
    assert abs(wrong[0] - want[0]) / want[0] > 1e-3
    no_norm = dict(w, **{k: 3.0 * v for k, v in w.items()
                         if k.endswith(("q_norm_weight", "k_norm_weight"))})
    wrong = REF.train_losses(no_norm, (ids, labels), _sizes(), OPT, 3)
    assert abs(wrong[0] - want[0]) / want[0] > 1e-3
    # the index loss moves the selector: without it its weights stay
    before = np.asarray(step.params["layers0_index_q_weight"])
    assert np.abs(before - w["layers0_index_q_weight"]).max() > 1e-3
