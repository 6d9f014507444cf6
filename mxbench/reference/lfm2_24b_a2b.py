"""Plain float32 jax.numpy reference of LFM2-24B-A2B: every layer ``x
<- x + Op_l(RMSNorm(x))``, ``x <- x + F_l(RMSNorm(x))``; ``Op_l`` by
``layer_types[l]`` a gated short convolution (``[B ; C ; u] = W_in h``,
the depthwise causal filter of ``B * u`` written out as a sum over its
taps of shifted copies, times ``C``, then ``W_out``) or causal
grouped-query attention with an RMSNorm over each head of q and k
before rotary positions over the whole head, the mask written out by
``jnp.where`` on index arithmetic; ``F_l`` a dense SwiGLU MLP for ``l <
num_dense_layers``, after that a sigmoid top-k router with a selection
bias (renormalised, scaled) over SwiGLU experts as a loop over the
experts held here, no shared expert; the next-token loss through the
embedding's own matrix; AdamW steps through ``jax.grad``. No kernels, no
bf16, no tile is skipped, no sorting of rows by expert; callers run it
under ``jax.default_matmul_precision("highest")``.

Layer equations: configs/lfm2_24b_a2b.json ``equations``; what the
published ``config.json`` does not settle is in that file under
``assumed``, what was cut under ``reduced``. Weights come by name from
the Gluon parameters (configs/lfm2_24b_a2b.py::named_weights). It
imports nothing of the program's.

For memory only, so that it fits the chip beside its own optimizer
state at four sequences of 8,192 tokens: each layer is recomputed in
the backward (``jax.checkpoint``), attention takes its queries
``QUERY_BLOCK`` at a time against all the keys (``lax.map``, each block
recomputed), the dense MLP takes the tokens ``MLP_BLOCK`` at a time,
the held experts are taken one after another (``lax.scan``, an expert's
hidden layer recomputed), and the cross-entropy takes the positions
``CE_BLOCK`` at a time. None of these changes what is computed: a block
of queries still scores every key and masks by position.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

QUERY_BLOCK = 128       # queries a block of whole score rows (memory only)
MLP_BLOCK = 4096        # tokens a block of the dense MLP (memory only)
CE_BLOCK = 2048         # positions a block of logits (memory only)


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


# ---------------------------------------------------------------------------
# the gated short convolution
# ---------------------------------------------------------------------------
def causal_filter(z, taps):
    """z (batch, length, channels), taps (channels, k): ``c_t = sum_j
    taps[:, j] z_{t-(k-1)+j}``, zeros before the sequence's start: the
    last tap reads the token itself, the first the one ``k - 1``
    before."""
    k, length = taps.shape[1], z.shape[1]
    out = jnp.zeros_like(z)
    for j in range(k):
        back = k - 1 - j                # how many tokens before t
        shifted = jnp.concatenate(
            [jnp.zeros_like(z[:, :back]), z[:, :length - back]], axis=1)
        out = out + shifted * taps[:, j]
    return out


def short_conv(w, p, x, cfg=None):
    """The conv branch's output: ``W_out (C * filter(B * u))`` with
    ``[B ; C ; u] = W_in x`` in that order."""
    b, c, u = jnp.split(x @ w[p + "in_weight"].T, 3, axis=-1)
    return (c * causal_filter(b * u, w[p + "conv_weight"])) \
        @ w[p + "out_weight"].T


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
def rope_table(rope, head_dim, length):
    """(cos, sin), each (length, head_dim / 2): pair j turns by ``p
    theta^(-2j / head_dim)``."""
    pairs = head_dim // 2
    freq = float(rope["rope_theta"]) ** (
        -jnp.arange(pairs, dtype=jnp.float32) / pairs)
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * freq
    return jnp.cos(angle), jnp.sin(angle)


def rotate(x, table):
    """x (batch, length, heads, d): lane j and lane j + d / 2 turn
    together."""
    cos, sin = (t[None, :, None, :] for t in table)
    half = cos.shape[-1]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def attention(w, p, x, cfg):
    """The attention branch's output."""
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    head_dim(cfg))
    eps = cfg["norm_eps"]
    b, length, _ = x.shape
    table = rope_table(cfg["rope_parameters"], d, length)
    q = (x @ w[p + "q_weight"].T).reshape(b, length, heads, d)
    k = (x @ w[p + "k_weight"].T).reshape(b, length, kv, d)
    v = (x @ w[p + "v_weight"].T).reshape(b, length, kv, d)
    # (``qk_norm`` and ``score_lanes`` are no keys of the model's: the
    # check's controls set them to take a term out; the defaults are
    # the model's)
    if cfg.get("qk_norm", True):
        # over a head's lanes, one weight a lane for every head
        q = _rms(q, w[p + "q_norm_weight"], eps)
        k = _rms(k, w[p + "k_norm_weight"], eps)
    q, k = rotate(q, table), rotate(k, table)
    # query head i reads key-value head i // (heads / kv): a group at a
    # time, so no repeated copy of k and v exists (memory only)
    qg = q.reshape(b, length, kv, heads // kv, d)
    scale = float(cfg.get("score_lanes", d)) ** -0.5

    @jax.checkpoint
    def block(xs):
        qb, first = xs
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qb, k) * scale
        t = first + jnp.arange(qb.shape[1])[:, None]
        seen = jnp.arange(length)[None, :] <= t
        att = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        return jnp.einsum("bgrqk,bkgd->bqgrd", att, v)

    size = min(QUERY_BLOCK, length)
    blocks = -(-length // size)
    pad = blocks * size - length
    qp = jnp.pad(qg, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    qp = jnp.moveaxis(qp.reshape((b, blocks, size) + qg.shape[2:]), 1, 0)
    ctx = lax.map(block, (qp, jnp.arange(blocks) * size))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, blocks * size, heads * d)
    return ctx[:, :length] @ w[p + "o_weight"].T


# ---------------------------------------------------------------------------
# the two MLPs
# ---------------------------------------------------------------------------
def route(w, p, x, cfg):
    """(chosen experts (..., k), their weights (..., k)): a sigmoid of
    every logit, the top k of the scores plus the selection bias, the
    chosen scores (without the bias) renormalised to sum 1, times
    ``routed_scaling_factor``."""
    s = jax.nn.sigmoid(x @ w[p + "router_weight"].T)
    picked = s + w[p + "expert_bias"] if cfg.get("use_expert_bias", True) \
        else s
    _, chosen = lax.top_k(picked, cfg["num_experts_per_tok"])
    wk = jnp.take_along_axis(s, chosen, -1)
    if cfg["norm_topk_prob"]:
        wk = wk / (wk.sum(-1, keepdims=True) + 1e-20)
    return chosen, wk * cfg["routed_scaling_factor"]


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate.T) * (x @ up.T)) @ down.T


def glu(x, gate_up, down):
    """``gate_up`` holds the gate's rows, then the up projection's."""
    width = gate_up.shape[0] // 2
    return swiglu(x, gate_up[:width], gate_up[width:], down)


def experts(w, p, x, cfg):
    """The terms of the experts held here, ``expert_offset`` on; the
    other chosen experts' terms are left out, as on the chip that lacks
    them. No shared expert."""
    chosen, wk = route(w, p, x, cfg)
    first = cfg.get("expert_offset", 0)

    # memory only: one expert after another, its hidden layer recomputed
    @jax.checkpoint
    def add(y, held):
        e, gate_up, down = held
        we = jnp.sum(jnp.where(chosen == first + e, wk, 0.0), -1)
        return y + we[..., None] * glu(x, gate_up, down), None

    gate_up = w[p + "experts_gate_up_weight"]
    return lax.scan(add, jnp.zeros_like(x),
                    (jnp.arange(gate_up.shape[0]), gate_up,
                     w[p + "experts_down_weight"]))[0]


def dense_mlp(w, p, x, cfg=None):
    """``W_down (silu(W_gate x) * W_up x)``, the tokens a block at a
    time (memory only)."""
    flat = x.reshape(-1, x.shape[-1])
    size = min(MLP_BLOCK, flat.shape[0])
    blocks = -(-flat.shape[0] // size)
    pad = blocks * size - flat.shape[0]
    block = jax.checkpoint(lambda rows: glu(rows, w[p + "gate_up_weight"],
                                            w[p + "down_weight"]))
    y = lax.map(block, jnp.pad(flat, ((0, pad), (0, 0)))
                .reshape(blocks, size, -1))
    return y.reshape(blocks * size, -1)[:flat.shape[0]].reshape(x.shape)


# ---------------------------------------------------------------------------
# the stack, the loss, the optimizer
# ---------------------------------------------------------------------------
def forward(w, ids, cfg):
    """ids (batch, length) -> hidden states after the last norm."""
    eps = cfg["norm_eps"]
    x = w["embed_weight"][ids]
    for i in range(cfg["num_hidden_layers"]):
        p = "layers%d_" % i
        op = short_conv if cfg["layer_types"][i] == "conv" else attention
        mlp = dense_mlp if i < cfg["num_dense_layers"] else experts

        @jax.checkpoint
        def layer(x, lw, p=p, op=op, mlp=mlp):
            x = x + op(lw, p, _rms(x, lw[p + "op_norm_weight"], eps), cfg)
            return x + mlp(lw, p, _rms(x, lw[p + "ffn_norm_weight"], eps),
                           cfg)

        x = layer(x, {k: v for k, v in w.items() if k.startswith(p)})
    return _rms(x, w["norm_f_weight"], eps)


def logits(w, ids, cfg):
    """The head is the embedding's matrix."""
    return forward(w, ids, cfg) @ w["embed_weight"].T


def lm_loss(w, ids, labels, cfg):
    """Mean cross-entropy over every position against ``labels`` (the
    feed's next tokens)."""
    head = w["embed_weight"]
    hidden = forward(w, ids, cfg).reshape(-1, head.shape[1])
    flat = labels.reshape(-1)
    size = min(CE_BLOCK, flat.shape[0])
    blocks = -(-flat.shape[0] // size)
    pad = blocks * size - flat.shape[0]

    @jax.checkpoint
    def block(xs):
        h, y, real = xs
        logp = jax.nn.log_softmax(h @ head.T, -1)
        nll = -jnp.take_along_axis(logp, y[:, None], -1)[:, 0]
        return jnp.where(real, nll, 0.0).sum()

    real = jnp.arange(blocks * size) < flat.shape[0]
    total = lax.map(block, (
        jnp.pad(hidden, ((0, pad), (0, 0))).reshape(blocks, size, -1),
        jnp.pad(flat, (0, pad)).reshape(blocks, size),
        real.reshape(blocks, size)))
    return total.sum() / flat.shape[0]


def _adamw(w, g, m, v, t, o):
    """MXNet's AdamW: the bias corrections folded into the rate (so
    epsilon is added to the uncorrected sqrt(v), Kingma & Ba sec. 2's
    efficient form), and a decoupled decay ``wd * w`` that the rate
    does not scale."""
    m = o["beta1"] * m + (1 - o["beta1"]) * g
    v = o["beta2"] * v + (1 - o["beta2"]) * g * g
    lr_t = o["lr"] * jnp.sqrt(1 - o["beta2"] ** t) / (1 - o["beta1"] ** t)
    return (w - lr_t * m / (jnp.sqrt(v) + o["epsilon"])
            - o["wd"] * w, m, v)


FROZEN = ("expert_rows", "expert_bias")     # counts; a bias never updated


def model_cfg(sizes):
    """The configuration file's keys as this file reads them: the
    layers built are those the deployment names of the published
    ``layer_types``."""
    cfg = {k: x for k, x in sizes.items()
           if isinstance(x, (int, float, str, bool))}
    cfg["rope_parameters"] = sizes["rope_parameters"]
    cfg["layer_types"] = [sizes["layer_types"][i]
                          for i in sizes["deployment"]["layers_built"]]
    cfg["expert_offset"] = sizes["deployment"]["expert_offset"]
    return cfg


def train_losses(weights, batch, sizes, optimizer, steps, lower=False):
    """The losses of ``steps`` AdamW steps on one batch (ids, labels as
    integer arrays), each loss taken before its update.

    ``lower`` is the check's control, not a reference: the same steps
    in the nearest precision below the one the configuration states,
    bf16 masters (the weights rounded to bf16 at the start and after
    every update) and products at the device's default precision (one
    bf16 pass on the chip). The cell's check has to call it wrong."""
    if optimizer["name"] != "adamw":
        raise ValueError("reference/lfm2_24b_a2b.py implements AdamW, "
                         "not %r" % optimizer["name"])
    ids, labels = (jnp.asarray(a, jnp.int32) for a in batch)
    held = (lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)) if lower \
        else (lambda a: a)
    frozen = {k: jnp.asarray(a, jnp.float32) for k, a in weights.items()
              if k.endswith("expert_bias")}
    w = {k: held(jnp.asarray(a, jnp.float32)) for k, a in weights.items()
         if not k.endswith(FROZEN)}
    m = {k: jnp.zeros_like(a) for k, a in w.items()}
    v = {k: jnp.zeros_like(a) for k, a in w.items()}
    cfg = model_cfg(sizes)

    def step(w, m, v, t, ids, labels):
        loss, g = jax.value_and_grad(
            lambda w: lm_loss(dict(w, **frozen), ids, labels, cfg))(w)
        new = {k: _adamw(w[k], g[k], m[k], v[k], t, optimizer) for k in w}
        return (loss, {k: held(n[0]) for k, n in new.items()},
                {k: n[1] for k, n in new.items()},
                {k: n[2] for k, n in new.items()})

    step = jax.jit(step, donate_argnums=(0, 1, 2))
    losses = []
    with jax.default_matmul_precision("default" if lower else "highest"):
        for t in range(1, steps + 1):
            loss, w, m, v = step(w, m, v, float(t), ids, labels)
            losses.append(float(loss))
    return losses
