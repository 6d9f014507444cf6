"""Flash self-attention kernel numerics vs the unfused interleaved ops
(interpret mode on CPU; Mosaic-compiled on a real chip via
tools/bert_bench.py)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops.pallas_attention import (flash_selfatt,
                                            flash_selfatt_available,
                                            selfatt_plan)
from mxnet_tpu.ops.contrib_ops import (interleaved_matmul_selfatt_qk,
                                       interleaved_matmul_selfatt_valatt)
from numerics import value_and_grads


def _ref(qkv, heads):
    sc = interleaved_matmul_selfatt_qk(qkv, heads=heads)
    att = jax.nn.softmax(sc, axis=-1)
    return interleaved_matmul_selfatt_valatt(qkv, att, heads=heads)


@pytest.mark.parametrize("L,N,H,d", [(16, 4, 4, 8), (32, 2, 8, 16)])
def test_flash_selfatt_matches_unfused(L, N, H, d):
    rng = np.random.RandomState(0)
    qkv = jnp.asarray(rng.randn(L, N, H * 3 * d).astype(np.float32))
    assert flash_selfatt_available(L, H, N)
    plan = selfatt_plan(L, H, N, 0.0)
    seeds = jnp.zeros((plan["n_blocks"],), jnp.int32)
    r = jnp.asarray(rng.randn(L, N, H * d).astype(np.float32))
    o1, g1 = value_and_grads(
        lambda q: flash_selfatt(q, seeds, heads=H, block_heads=plan["bbh"]),
        qkv, cot=r)
    o2, g2 = value_and_grads(lambda q: _ref(q, H), qkv, cot=r)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-2, atol=2e-2)
    denom = float(jnp.max(jnp.abs(g2))) + 1e-9
    assert float(jnp.max(jnp.abs(g1 - g2))) / denom < 3e-2


def test_sdp_selfatt_op_fallback_and_eval_mode():
    """The registry op: eval mode has no dropout; CPU+dropout falls
    back to the unfused path and still matches the dropout-free value
    in eval mode."""
    from mxnet_tpu.ops import get_op
    rng = np.random.RandomState(1)
    L, N, H, d = 16, 4, 4, 8
    qkv = jnp.asarray(rng.randn(L, N, H * 3 * d).astype(np.float32))
    op = get_op("_contrib_sdp_selfatt")
    key = jax.random.PRNGKey(0)
    out_eval = op.impl(key, qkv, heads=H, dropout=0.5, _train=False)
    np.testing.assert_allclose(np.asarray(out_eval), np.asarray(_ref(qkv, H)),
                               rtol=2e-2, atol=2e-2)
    # train mode with dropout on CPU: unfused fallback, still finite
    out_train = op.impl(key, qkv, heads=H, dropout=0.5, _train=True)
    assert np.isfinite(np.asarray(out_train)).all()
    assert not np.allclose(np.asarray(out_train), np.asarray(out_eval))


def test_bert_cell_uses_fused_path_and_learns():
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, autograd
    from mxnet_tpu.gluon.model_zoo.bert import BERTEncoderCell
    cell = BERTEncoderCell(32, 64, 4, dropout=0.0)
    cell.initialize()
    rng = np.random.RandomState(0)
    x = nd.array(rng.randn(16, 4, 32).astype(np.float32))
    trainer = gluon.Trainer(cell.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    first = None
    for _ in range(10):
        with autograd.record():
            out = cell(x)
            loss = (out * out).mean()
        loss.backward()
        trainer.step(1)
        v = float(loss.asnumpy())
        if first is None:
            first = v
    assert v < first
