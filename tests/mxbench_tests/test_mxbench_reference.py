"""Each configuration's plain float32 reference against its Gluon
block at toy widths on the CPU, in float32 (so the agreement is
arithmetic, not bf16 room): the forward's outputs, the loss, and the
loss after one and two updates through the cell's own loop."""
import numpy as np
import pytest

from mxbench import run as mxrun


def _ctx(cell, loss_rtol=2e-5):
    ctx, gen, _ = mxrun.context(cell, seed=5, seconds=0.0, trace=False,
                                rehearse=True)
    ctx.sizes = dict(ctx.sizes, compute_dtype="float32")
    ctx.sizes["check"] = dict(ctx.sizes["check"], loss_rtol=loss_rtol,
                              drop_rtol=2e-2)
    return ctx, gen


def test_bert_forward_and_loss_match_the_block(pallas_interpret):
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, nd
    ctx, _ = _ctx("bert_base_pretrain_s128")
    sizes, seq = ctx.sizes, ctx.traffic["seq"]
    mx.random.seed(5)
    net, loss, _n = ctx.cfgmod.sharded_parts(sizes, 0.0, seq)
    w = ctx.cfgmod.named_weights(net, loss)
    rng = np.random.default_rng(5)
    ids = rng.integers(0, sizes["vocab_size"], (3, seq), dtype=np.int32)
    labels = rng.integers(0, sizes["vocab_size"], (3, seq), dtype=np.int32)
    types = np.zeros_like(ids)
    with autograd.pause():
        out = net(nd.array(ids, dtype="int32"), nd.array(types, dtype="int32"))
        seq_out = out[0] if isinstance(out, (list, tuple)) else out
        got_loss = loss.head(seq_out, nd.array(labels, dtype="int32")) \
            .mean().asnumpy().item()
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ctx.refmod.forward(w, ids, types, sizes))
        want_loss = float(ctx.refmod.mlm_loss(w, ids, types, labels, sizes))
    np.testing.assert_allclose(seq_out.asnumpy(), want, rtol=1e-4, atol=1e-4)
    assert got_loss == pytest.approx(want_loss, rel=1e-5)


def test_resnet_logits_and_loss_match_the_block():
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, nd
    ctx, _ = _ctx("resnet50_v1_train_recordio")
    sizes = ctx.sizes
    mx.random.seed(5)
    net, loss_fn = ctx.cfgmod.gluon_parts(sizes)
    w = ctx.cfgmod.named_weights(net)
    rng = np.random.default_rng(5)
    side = sizes["image_size"]
    x = rng.random((4, 3, side, side), dtype=np.float32)
    y = rng.integers(0, sizes["num_classes"], 4)
    with autograd.train_mode():     # batch statistics, as in a step
        got = net(nd.array(x))
        got_loss = loss_fn(got, nd.array(y.astype(np.float32))).mean() \
            .asnumpy().item()
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ctx.refmod.logits(
            {k: v for k, v in w.items()}, x, sizes))
        want_loss = float(ctx.refmod.loss_of(w, x, y, sizes))
    np.testing.assert_allclose(got.asnumpy(), want, rtol=1e-3, atol=1e-3)
    assert got_loss == pytest.approx(want_loss, rel=1e-4)


@pytest.mark.parametrize("cell, loss_rtol", [
    ("bert_base_pretrain_s128", 2e-5),
    # one SGD step at lr 0.02 takes the loss of 8 memorised samples from
    # 4.6 to 0.46: float32 rounding through 20 batch norms shows at 1e-3
    ("resnet50_v1_train_recordio", 5e-3)])
def test_losses_after_updates_match(cell, loss_rtol, pallas_interpret):
    """The cell's own check, in float32 with tight tolerances: loss
    before any update, and after one update (backward + optimizer)."""
    ctx, gen = _ctx(cell, loss_rtol)
    assert gen.check_against_reference(ctx, ctx.traffic.get("seq"))


@pytest.mark.parametrize("key, wrong", [("epsilon", 1.0), ("lr", 0.002)])
def test_a_wrong_optimizer_fails_the_check(key, wrong, pallas_interpret):
    """The second step's loss is what catches the backward and the
    optimizer: a reference with another epsilon or rate must not pass."""
    ctx, gen = _ctx("bert_base_pretrain_s128")
    real = ctx.refmod.train_losses

    def skewed(weights, batch, sizes, optimizer, steps):
        return real(weights, batch, sizes, dict(optimizer, **{key: wrong}),
                    steps)

    ctx.refmod.train_losses = skewed
    assert not gen.check_against_reference(ctx, ctx.traffic["seq"])


@pytest.mark.parametrize("config, seq, want", [
    # 12 x (4 x 768^2 + 2 x 768 x 3072 + 2 x 128 x 768) + 768 x 30522
    # + 768^2 multiply-adds a token, x 2 x 3, x 128 tokens
    ("bert_base", 128, 667_948_032 * 128),
    # He et al. Table 1: 3.8e9 multiply-adds for the 50-layer net; the
    # model zoo's stride on the first 1x1 makes it about 8% more
    ("resnet50_v1", None, None),
])
def test_model_flops(config, seq, want):
    from mxbench import manifest
    sizes, cfgmod, _ = manifest.config(config)
    got = cfgmod.train_flops_per_sample(sizes, seq)
    if want is not None:
        assert got == want
    else:
        assert 3.8e9 < got / 6 < 4.3e9
