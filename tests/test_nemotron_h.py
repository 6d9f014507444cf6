"""The zoo's Nemotron-H decoder (gluon/model_zoo/nemotron_h.py) at toy
widths on the CPU: the blocks against the benchmark's plain float32
reference, the auxiliary states through ``ShardedTrainStep``, the
expert rows' gauges, and integer inputs through the sharded step."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from decoder_harness import OPT, Toy, ids as _ids
from mxnet_tpu import autograd, gluon, nd, telemetry
from mxnet_tpu.gluon.model_zoo import nemotron_h as zoo
from mxnet_tpu.parallel import MeshConfig, P, ShardedTrainStep, make_mesh

CFG = dict(
    hidden_size=48, hybrid_override_pattern="MEMEM*EMEMEM", num_hidden_layers=9,
    layer_norm_epsilon=1e-5, mamba_num_heads=4, mamba_head_dim=8, n_groups=2,
    ssm_state_size=16, conv_kernel=4, chunk_size=8, time_step_min=0.001,
    time_step_max=0.1, time_step_floor=1e-4, n_routed_experts=16,
    experts_held=4, expert_offset=4, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=40, n_shared_experts=1,
    num_experts_per_tok=3, routed_scaling_factor=2.5, norm_topk_prob=True,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8, vocab_size=64)


TOY = Toy("nemotron_twotower_30b_a3b", zoo.NemotronHModel,
          zoo.NemotronHLMLoss, CFG)
REF = TOY.ref
_build, _weights, _batch = TOY.build, TOY.weights, TOY.batch


def test_blocks_and_loss_match_the_reference():
    net, head = _build()
    ids, labels = _batch()
    with autograd.pause():
        hidden = net(_ids(ids))
        loss = head(hidden, _ids(labels)).mean() \
            .asnumpy().item()
    w = _weights(net, head)
    with jax.default_matmul_precision("highest"):
        want, want_loss = jax.jit(lambda w: (
            REF.forward(w, ids, CFG), REF.lm_loss(w, ids, labels, CFG)))(w)
    want, want_loss = np.asarray(want), float(want_loss)
    np.testing.assert_allclose(hidden.asnumpy(), want, rtol=1e-4, atol=1e-4)
    assert loss == pytest.approx(want_loss, rel=1e-5)


def test_layers_follow_the_pattern_and_the_share():
    net, _ = _build()
    kinds = [type(layer).__name__ for layer in net.layers]
    assert kinds == ["Mamba2Layer", "ExpertLayer", "Mamba2Layer",
                     "ExpertLayer", "Mamba2Layer", "AttentionLayer",
                     "ExpertLayer", "Mamba2Layer", "ExpertLayer"]
    params = net.collect_params()
    assert params["layers1_router_weight"].shape == (16, 48)    # all routed
    assert params["layers1_experts_up_weight"].shape == (4, 24, 48)  # held
    assert params["layers0_in_proj_weight"].shape == (32 + 96 + 4, 48)
    assert params["layers0_conv_weight"].shape == (96, 4)
    assert params["layers5_k_weight"].shape == (16, 48)
    assert not [n for n in params if n.endswith("bias")
                and "conv" not in n and "dt_" not in n
                and "e_score_correction" not in n]


@pytest.mark.parametrize("change", [
    dict(hybrid_override_pattern="MEMX", num_hidden_layers=4),
    dict(hybrid_override_pattern="ME", num_hidden_layers=4),
    dict(experts_held=8, expert_offset=12)])
def test_a_configuration_that_cannot_be_built_is_refused(change):
    with pytest.raises(ValueError):
        zoo.NemotronHModel(dict(CFG, **change), prefix="")


def test_seeded_initial_values():
    net, _ = _build()
    p = {k: v.data().asnumpy() for k, v in net.collect_params().items()}
    a = np.exp(p["layers0_a_log"])
    assert (a >= 1).all() and (a <= 16).all()
    dt = np.log1p(np.exp(p["layers0_dt_bias"]))
    assert (dt >= 1e-4).all() and (dt <= 0.1001).all()
    np.testing.assert_array_equal(p["layers0_d"], 1.0)
    np.testing.assert_array_equal(p["layers0_norm_weight"], 1.0)
    assert np.abs(p["layers0_conv_bias"]).max() > 0
    assert 0 < np.abs(p["layers1_e_score_correction_bias"]).max() <= 0.01
    np.testing.assert_array_equal(p["layers1_expert_rows"], 0.0)
    again, _ = _build()
    np.testing.assert_array_equal(
        p["layers2_in_proj_weight"],
        again.collect_params()["layers2_in_proj_weight"].data().asnumpy())


def _a_step(net, head, dtype=None):
    return TOY.step(net, head, dtype, lr=3e-4, wd=3e-5)


def test_bias_and_row_counts_ride_as_auxiliary_states():
    """Not trainable: no gradient, no optimizer state; the bias keeps
    its seeded value, the counts are rewritten by the step."""
    net, head = _build()
    step = _a_step(net, head)
    aux = sorted(step.aux)
    assert aux == sorted("layers%d_%s" % (i, n) for i in (1, 3, 6, 8)
                         for n in ("e_score_correction_bias", "expert_rows"))
    assert not set(aux) & set(step.params) and not set(aux) & set(step.states)
    bias = np.asarray(step.aux["layers1_e_score_correction_bias"])
    ids, labels = _batch()
    first = float(step.step(_ids(ids),
                            _ids(labels)))
    second = float(step.step(_ids(ids),
                             _ids(labels)))
    assert second < first
    np.testing.assert_array_equal(
        np.asarray(step.aux["layers1_e_score_correction_bias"]), bias)
    rows = np.asarray(step.aux["layers1_expert_rows"])
    assert rows.shape == (2, 4) and rows[0].sum() > 0
    np.testing.assert_array_equal(rows[0], rows[1])


def test_expert_rows_are_published_and_nothing_is_dropped():
    telemetry.reset()
    net, head = _build()
    step = _a_step(net, head)
    ids, labels = _batch(1)
    step.step(_ids(ids), _ids(labels))
    rows = zoo.publish_expert_rows(step.aux)
    assert sorted(rows) == ["layers1", "layers3", "layers6", "layers8"]
    want = np.asarray(step.aux["layers3_expert_rows"])[0]
    np.testing.assert_array_equal(rows["layers3"], want)
    for e in range(4):
        assert telemetry.gauge("mx_moe_expert_rows", block="layers3",
                               expert=str(e)).value == want[e]
    assert telemetry.counter("mx_moe_dropped_rows_total").value == 0
    # from the Gluon parameters too (an eager forward writes them)
    with autograd.pause():
        net(_ids(ids))
    eager = zoo.publish_expert_rows(
        {k: v.data() for k, v in net.collect_params().items()})
    assert eager["layers1"].sum() > 0
    assert telemetry.counter("mx_moe_dropped_rows_total").value == 0


@pytest.mark.parametrize("shape_recorded", [True, False])
def test_buffer_blocks_are_published_from_the_routed_rows(shape_recorded):
    """The op records its buffer's shape as it is traced, where
    telemetry is on; ``publish_expert_rows`` (one argument, as the
    benchmark calls it) turns each layer's routed rows into the blocks
    that hold a row, beside the blocks the buffer has. Traced with
    telemetry off there is no shape and no such gauge; the rows' gauges
    and the dropped rows' counter are as before either way."""
    from mxnet_tpu.ops import decoder_ops as D
    was = telemetry.enabled()
    telemetry.reset()
    telemetry.enable(shape_recorded)
    try:
        net, head = _build()
        step = _a_step(net, head)
        ids, labels = _batch(1)
        step.step(_ids(ids), _ids(labels))
    finally:
        telemetry.enable(was)
    rows = zoo.publish_expert_rows(step.aux)
    assert sorted(rows) == ["layers1", "layers3", "layers6", "layers8"]
    assert telemetry.counter("mx_moe_dropped_rows_total").value == 0
    gauges = telemetry.snapshot()["gauges"]
    assert gauges['mx_moe_expert_rows{block="layers3",expert="2"}'] \
        == rows["layers3"][2]
    published = [k for k in gauges if k.startswith("mx_moe_buffer_blocks")]
    if not shape_recorded:
        assert not published
        return
    block, blocks, _ = D._buffer(ids.size, CFG["num_experts_per_tok"],
                                 CFG["experts_held"], CFG["n_routed_experts"])
    assert len(published) == 2 * len(rows) and blocks > 0
    for name, routed in rows.items():
        computed, held = (telemetry.gauge("mx_moe_buffer_blocks", block=name,
                                          state=state).value
                          for state in ("computed", "held"))
        assert held == blocks
        assert computed == sum(-(-int(r) // block) for r in routed) > 0


def test_two_buffer_shapes_under_one_count_publish_no_blocks():
    """Two layers that hold the same number of experts over buffers of
    different shapes share the one series the op can record: the second
    marks it, and ``publish_expert_rows`` then publishes the rows as
    ever and no ``mx_moe_buffer_blocks`` for such layers (not one
    layer's blocks under another's name)."""
    from mxnet_tpu.ops import decoder_ops as D
    was = telemetry.enabled()
    telemetry.reset()
    telemetry.enable(True)
    try:
        net, head = _build()
        step = _a_step(net, head)
        ids, labels = _batch(1)
        step.step(_ids(ids), _ids(labels))
        held = CFG["experts_held"]
        key = jax.random.key(0)
        x = jax.random.normal(key, (ids.size // 2, CFG["hidden_size"]))
        jax.eval_shape(
            lambda *a: D._moe_experts(
                *a, top_k=CFG["num_experts_per_tok"], offset=0, scale=1.0,
                norm_topk=True),
            x, jnp.zeros((CFG["n_routed_experts"], x.shape[1])), None,
            jnp.zeros((held, CFG["moe_intermediate_size"], x.shape[1])),
            jnp.zeros((held, x.shape[1], CFG["moe_intermediate_size"])))
    finally:
        telemetry.enable(was)
    assert telemetry.gauge("mx_moe_buffer_shape", held=str(held),
                           dim="blocks").value == -1
    rows = zoo.publish_expert_rows(step.aux)
    assert sorted(rows) == ["layers1", "layers3", "layers6", "layers8"]
    gauges = telemetry.snapshot()["gauges"]
    assert not [k for k in gauges if k.startswith("mx_moe_buffer_blocks")]
    assert gauges['mx_moe_expert_rows{block="layers3",expert="2"}'] \
        == rows["layers3"][2]


def test_sharded_step_matches_the_reference_in_bfloat16_within_reason():
    net, head = _build()
    w = _weights(net, head)
    step = _a_step(net, head, dtype="bfloat16")
    ids, labels = _batch(2)
    got = float(step.step(_ids(ids),
                          _ids(labels)))
    with jax.default_matmul_precision("highest"):
        want = float(jax.jit(lambda w: REF.lm_loss(w, ids, labels, CFG))(w))
    assert got == pytest.approx(want, rel=5e-3)


class _OddRows(gluon.HybridBlock):
    """Embedding whose row i holds i % 2: a bf16-rounded odd id (every
    odd id over 256 rounds to an even one) reads 0 where it holds 1."""

    def __init__(self, vocab, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.embed = gluon.nn.Embedding(vocab, 8, prefix="embed_")

    def hybrid_forward(self, F, ids):
        return self.embed(ids)


class _MeanLoss:
    def __call__(self, out, labels):
        return [(out.mean(axis=-1) - labels).mean()]


def test_integer_inputs_reach_the_embedding_unrounded():
    """``ShardedTrainStep`` casts floating data inputs to the compute
    dtype; integer ids must pass as they are, at a vocabulary (16,384)
    whose odd ids bf16 cannot hold."""
    vocab = 16384
    net = _OddRows(vocab, prefix="")
    net.initialize()
    net.embed.weight.set_data(nd.array(
        np.repeat((np.arange(vocab) % 2)[:, None], 8, 1).astype(np.float32)))
    mesh = make_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
    step = ShardedTrainStep(net, _MeanLoss(), mesh, optimizer="sgd", lr=0.0,
                            momentum=0.0, dtype="bfloat16", n_data_inputs=2,
                            data_specs=[P(), P()])
    ids = np.array([[16383, 16381, 257, 8191, 1, 12345]], np.int32)
    assert (ids % 2 == 1).all()
    loss = float(step.step(_ids(ids),
                           nd.array(np.zeros(ids.shape, np.float32))))
    assert loss == 1.0


def test_three_adamw_steps_at_a_constant_rate_match_the_reference():
    """The shared ``_apply_update`` rule under the decoder: bias
    corrections folded into the rate, a decay the rate does not scale,
    the router's bias never updated."""
    net, head = _build()
    w = _weights(net, head)
    step = TOY.reference_step(net, head)
    ids, labels = _batch(4)
    got = [float(step.step(_ids(ids),
                           _ids(labels)))
           for _ in range(3)]
    want = REF.train_losses(w, (ids, labels), TOY.sizes(), OPT, 3)
    np.testing.assert_allclose(got, want, rtol=2e-5)
    assert got[2] < got[1] < got[0]
