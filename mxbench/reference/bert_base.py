"""Plain float32 jax.numpy reference of bert_base: the encoder's
forward, the MLM loss over every position, and LAMB steps through
``jax.grad``. No kernels, no bf16; callers run it under
``jax.default_matmul_precision("highest")``. Follows Devlin et al.
(post-LN transformer, erf GeLU, learned positions); departures are in
configs/bert_base.json under ``assumed``. Weights come by name from
the Gluon parameters (configs/bert_base.py::named_weights)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def _ln(x, g, b):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * g + b


def _dense(x, w, b):
    return x @ w.T + b


def _gelu(x):
    return 0.5 * x * (1.0 + jax.scipy.special.erf(x / jnp.sqrt(2.0)))


def forward(w, ids, types, sizes):
    """(batch, seq) ids and token types -> (batch, seq, hidden)."""
    heads = sizes["num_attention_heads"]
    b, l = ids.shape
    x = w["word_embed_weight"][ids] + w["token_type_embed_weight"][types]
    x = x + w["encoder_position_weight"][:l][None]
    x = _ln(x, w["encoder_layer_norm0_gamma"], w["encoder_layer_norm0_beta"])
    for i in range(sizes["num_hidden_layers"]):
        p = "encoder_transformer%d_" % i
        u = x.shape[-1]
        d = u // heads
        # the QKV projection is packed per head: (heads, 3, head size)
        qkv = _dense(x, w[p + "attn_qkv_weight"], w[p + "attn_qkv_bias"])
        qkv = qkv.reshape(b, l, heads, 3, d)
        q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(d))
        ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        ctx = ctx.reshape(b, l, u)
        x = _ln(x + _dense(ctx, w[p + "proj_weight"], w[p + "proj_bias"]),
                w[p + "layer_norm0_gamma"], w[p + "layer_norm0_beta"])
        f = p + "positionwise_ffn0_"
        h = _gelu(_dense(x, w[f + "ffn_1_weight"], w[f + "ffn_1_bias"]))
        h = _dense(h, w[f + "ffn_2_weight"], w[f + "ffn_2_bias"])
        x = _ln(x + h, w[f + "layer_norm0_gamma"], w[f + "layer_norm0_beta"])
    return x


def mlm_loss(w, ids, types, labels, sizes):
    """Mean cross-entropy over every position."""
    x = forward(w, ids, types, sizes)
    h = _ln(_dense(x, w["decoder_transform_weight"],
                   w["decoder_transform_bias"]),
            w["decoder_layer_norm0_gamma"], w["decoder_layer_norm0_beta"])
    logits = _dense(h, w["decoder_head_weight"], w["decoder_head_bias"])
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp, labels[..., None], -1).mean()


def _lamb(w, g, m, v, t, o):
    m = o["beta1"] * m + (1 - o["beta1"]) * g
    v = o["beta2"] * v + (1 - o["beta2"]) * g * g
    upd = (m / (1 - o["beta1"] ** t)) / (
        jnp.sqrt(v / (1 - o["beta2"] ** t)) + o["epsilon"]) + o["wd"] * w
    r1, r2 = jnp.linalg.norm(w), jnp.linalg.norm(upd)
    ratio = jnp.where((r1 > 0) & (r2 > 0), r1 / r2, 1.0)
    return w - o["lr"] * ratio * upd, m, v


def train_losses(weights, batch, sizes, optimizer, steps):
    """The losses of ``steps`` LAMB steps on one batch (ids, token
    types, labels as integer arrays), each loss taken before its
    update."""
    if optimizer["name"] != "lamb":
        raise ValueError("reference/bert_base.py implements LAMB, not %r"
                         % optimizer["name"])
    ids, types, labels = (jnp.asarray(a, jnp.int32) for a in batch)
    w = {k: jnp.asarray(a, jnp.float32) for k, a in weights.items()}
    m = {k: jnp.zeros_like(a) for k, a in w.items()}
    v = {k: jnp.zeros_like(a) for k, a in w.items()}

    # the batch is an argument, not a constant of the program: one
    # compiled reference serves every seed from the persistent cache
    @jax.jit
    def step(w, m, v, t, ids, types, labels):
        loss, g = jax.value_and_grad(mlm_loss)(w, ids, types, labels, sizes)
        new = {k: _lamb(w[k], g[k], m[k], v[k], t, optimizer) for k in w}
        return (loss, {k: n[0] for k, n in new.items()},
                {k: n[1] for k, n in new.items()},
                {k: n[2] for k, n in new.items()})

    losses = []
    with jax.default_matmul_precision("highest"):
        for t in range(1, steps + 1):
            loss, w, m, v = step(w, m, v, float(t), ids, types, labels)
            losses.append(float(loss))
    return losses
