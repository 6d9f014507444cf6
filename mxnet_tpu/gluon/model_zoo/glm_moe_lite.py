"""GLM-4.7-Flash: a causal decoder whose every layer is ``x <- x +
MLA(RMSNorm(x))``, ``x <- x + F_l(RMSNorm(x))`` (zai-org, ``model_type``
``glm4_moe_lite``; the keys below are its ``config.json``'s). Attention
is latent (MLA): queries and keys / values each go through a low-rank
bottleneck with an RMSNorm inside it (``q_lora_rank``,
``kv_lora_rank``), a head's q . k is over ``qk_nope_head_dim`` lanes
without position and ``qk_rope_head_dim`` rotary lanes, and the rotary
key is one head a token, shared by every query head. ``F_l`` is a dense
SwiGLU MLP in the first ``first_k_dense_replace`` layers and, after
them, a sigmoid top-k router (``noaux_tc``: a selection bias, the
chosen scores renormalised and scaled by ``routed_scaling_factor``)
over SwiGLU experts beside one shared expert. A
multi-token-prediction module (``num_nextn_predict_layers`` 1,
DeepSeek-V3's: arXiv:2412.19437 sec. 2.2) follows the stack: the
embedding of the next token and the stack's last hidden state, each
under a norm of its own, combined by one product, one more block of the
expert kind, a last norm, and then the main model's head, so the loss
has a second term. No bias anywhere, untied head.

The zoo's fourth decoder; the first whose layers differ in their MLP
(one class of layer, the MLP's kind by the layer's index), the first
with latent attention, and the first whose net uses a parameter twice:
the embedding feeds the stack and the module, the head's weight scores
both hidden states (one parameter, two uses, one gradient). Built like
the other three: one mixer op of ``ops/decoder_ops.py`` a residual
branch, traced by ``parallel.trace_block`` into the one program
``ShardedTrainStep`` compiles; recomputation lives in the mixer ops.
The model returns ``(hidden states, the module's hidden states)``,
which the loss block takes with the labels (docs/TRAINING.md "A second
loss").

Expert parallelism's share is told as in ``nemotron_h.py``:
``experts_held`` from ``expert_offset`` on, of the router's
``n_routed_experts``. ``e_score_correction_bias`` (seeded, never
updated), ``expert_rows`` (rows routed to each held expert) and the
loss block's ``loss_terms`` (the two means) are auxiliary states, never
differentiated.
"""
from __future__ import annotations

import math

import numpy as np

from ... import initializer as init
from .. import nn
from ..block import HybridBlock
from .nemotron_h import _Draw, publish_expert_rows

__all__ = ["Glm4MoeLiteModel", "Glm4MoeLiteLMLoss", "Glm4MoeLiteMTP",
           "Glm4MoeLiteDecoderLayer", "publish_expert_rows",
           "publish_loss_terms", "KINDS"]

KINDS = ("dense", "sparse")

# a layer's parameters in the order its mixer ops take them
_MLA = ("attn_norm_weight", "q_a_weight", "q_a_norm_weight", "q_b_weight",
        "kv_a_weight", "kv_a_norm_weight", "kv_b_weight", "o_weight")
_DENSE = ("mlp_norm_weight", "gate_up_weight", "down_weight")
_SPARSE = ("mlp_norm_weight", "router_weight", "expert_rows",
           "experts_gate_up_weight", "experts_down_weight",
           "e_score_correction_bias", "shared_gate_up_weight",
           "shared_down_weight")


def _shift_left(F, tokens):
    """(batch, length) -> each row's tokens one place earlier; the last
    place takes the row's first token (any id does: the position is
    left out of the loss, and causal attention lets it reach no other)."""
    return F.concat(F.slice_axis(tokens, axis=1, begin=1, end=None),
                    F.slice_axis(tokens, axis=1, begin=0, end=1), dim=1)


class Glm4MoeLiteDecoderLayer(HybridBlock):
    """x -> x after both residual branches; ``kind`` (one of
    :data:`KINDS`) picks the MLP: the dense gated one of width
    ``intermediate_size``, or the routed experts and the shared one."""

    def __init__(self, cfg, kind, **kwargs):
        super().__init__(**kwargs)
        if kind not in KINDS:
            raise ValueError("MLP kind %r is not one of %s" % (kind, KINDS))
        self.kind = kind
        hidden = int(cfg["hidden_size"])
        heads = int(cfg["num_attention_heads"])
        if int(cfg["num_key_value_heads"]) != heads:
            raise ValueError("latent attention expands a key head a query "
                             "head: %s key heads, %d query heads"
                             % (cfg["num_key_value_heads"], heads))
        if cfg.get("rope_scaling"):
            raise ValueError("rope_scaling %r is not built (the published "
                             "config has none)" % (cfg["rope_scaling"],))
        q_rank, kv_rank = int(cfg["q_lora_rank"]), int(cfg["kv_lora_rank"])
        nope, rope = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
        vd = int(cfg["v_head_dim"])
        eps = float(cfg["rms_norm_eps"])
        self._attn = dict(
            num_heads=heads, qk_nope_head_dim=nope, qk_rope_head_dim=rope,
            v_head_dim=vd, rope_theta=float(cfg["rope_theta"]), eps=eps)
        self._eps = eps
        # matrices N(0, 0.02); the two that write into the residual
        # stream shrunk by sqrt(2 x layers)
        w_in = init.Normal(0.02)
        w_out = init.Normal(
            0.02 / math.sqrt(2 * int(cfg["num_hidden_layers"])))
        get = self.params.get
        with self.name_scope():
            self.attn_norm_weight = get("attn_norm_weight", shape=(hidden,),
                                        init="ones")
            self.q_a_weight = get("q_a_weight", shape=(q_rank, hidden),
                                  init=w_in)
            self.q_a_norm_weight = get("q_a_norm_weight", shape=(q_rank,),
                                       init="ones")
            self.q_b_weight = get("q_b_weight",
                                  shape=(heads * (nope + rope), q_rank),
                                  init=w_in)
            self.kv_a_weight = get("kv_a_weight",
                                   shape=(kv_rank + rope, hidden), init=w_in)
            self.kv_a_norm_weight = get("kv_a_norm_weight", shape=(kv_rank,),
                                        init="ones")
            self.kv_b_weight = get("kv_b_weight",
                                   shape=(heads * (nope + vd), kv_rank),
                                   init=w_in)
            self.o_weight = get("o_weight", shape=(hidden, heads * vd),
                                init=w_out)
            self.mlp_norm_weight = get("mlp_norm_weight", shape=(hidden,),
                                       init="ones")
            if kind == "dense":
                width = int(cfg["intermediate_size"])
                # the gate's rows, then the up projection's
                self.gate_up_weight = get(
                    "gate_up_weight", shape=(2 * width, hidden), init=w_in)
                self.down_weight = get("down_weight", shape=(hidden, width),
                                       init=w_out)
            else:
                self._experts(cfg, hidden, w_in, w_out)

    def _experts(self, cfg, hidden, w_in, w_out):
        routed = int(cfg["n_routed_experts"])
        held = int(cfg.get("experts_held", routed))
        offset = int(cfg.get("expert_offset", 0))
        if not 0 <= offset <= routed - held:
            raise ValueError("experts %d..%d are not among the router's %d"
                             % (offset, offset + held, routed))
        if (cfg.get("topk_method", "noaux_tc"), int(cfg.get("n_group", 1)),
                int(cfg.get("topk_group", 1))) != ("noaux_tc", 1, 1):
            raise ValueError("the router is noaux_tc over one group; "
                             "topk_method %r, n_group %r, topk_group %r"
                             % (cfg.get("topk_method"), cfg.get("n_group"),
                                cfg.get("topk_group")))
        width = int(cfg["moe_intermediate_size"])
        shared = width * int(cfg["n_shared_experts"])
        self._moe = dict(
            top_k=int(cfg["num_experts_per_tok"]), expert_offset=offset,
            routed_scaling_factor=float(cfg["routed_scaling_factor"]),
            norm_topk_prob=bool(cfg["norm_topk_prob"]), score_func="sigmoid",
            activation="swiglu", eps=self._eps)

        def state(name, shape, fill):
            p = self.params.get(name, shape=shape, grad_req="null", init=fill,
                                differentiable=False)
            p._is_aux = True
            return p

        get = self.params.get
        self.router_weight = get("router_weight", shape=(routed, hidden),
                                 init=w_in)
        self.e_score_correction_bias = state(
            "e_score_correction_bias", (routed,),
            _Draw(lambda u: 0.02 * u - 0.01))
        self.expert_rows = state("expert_rows", (2, held), "zeros")
        self.shared_gate_up_weight = get(
            "shared_gate_up_weight", shape=(2 * shared, hidden), init=w_in)
        self.shared_down_weight = get(
            "shared_down_weight", shape=(hidden, shared), init=w_out)
        # an expert's gate rows, then its up projection's
        self.experts_gate_up_weight = get(
            "experts_gate_up_weight", shape=(held, 2 * width, hidden),
            init=w_in)
        self.experts_down_weight = get(
            "experts_down_weight", shape=(held, hidden, width), init=w_out)

    def hybrid_forward(self, F, x, **w):
        # ``w``: this layer's parameters by name, those of its kind only
        x = x + F._contrib_mla_mixer(x, *(w[n] for n in _MLA), **self._attn)
        if self.kind == "dense":
            return x + F._contrib_glu_mlp_mixer(
                x, *(w[n] for n in _DENSE), eps=self._eps)
        return x + F._contrib_moe_mixer(x, *(w[n] for n in _SPARSE),
                                        **self._moe)


class Glm4MoeLiteMTP(HybridBlock):
    """The multi-token-prediction module, depth 1: (the next tokens'
    embeddings, the stack's hidden states before its final norm), both
    (batch, length, hidden) -> the module's hidden states after its own
    last norm, which the main model's head scores against the tokens
    two ahead. The checkpoints carry it at layer index
    ``num_hidden_layers`` (``enorm``, ``hnorm``, ``eh_proj``, a decoder
    layer, ``shared_head.norm``)."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        hidden = int(cfg["hidden_size"])
        self._eps = float(cfg["rms_norm_eps"])
        get = self.params.get
        with self.name_scope():
            self.embed_norm_weight = get("embed_norm_weight", shape=(hidden,),
                                         init="ones")
            self.hidden_norm_weight = get("hidden_norm_weight",
                                          shape=(hidden,), init="ones")
            # reads the embedding's lanes first, then the hidden state's
            self.combine_weight = get("combine_weight",
                                      shape=(hidden, 2 * hidden),
                                      init=init.Normal(0.02))
            self.block = Glm4MoeLiteDecoderLayer(cfg, "sparse",
                                                 prefix="block_")
            self.norm_weight = get("norm_weight", shape=(hidden,),
                                   init="ones")

    def hybrid_forward(self, F, next_embedding, hidden, *, embed_norm_weight,
                       hidden_norm_weight, combine_weight, norm_weight):
        u = F._contrib_mtp_combine(
            next_embedding, hidden, embed_norm_weight, hidden_norm_weight,
            combine_weight, eps=self._eps)
        return F._contrib_rms_norm(self.block(u), norm_weight, eps=self._eps)


class Glm4MoeLiteModel(HybridBlock):
    """ids (batch, length) -> (hidden states (batch, length, hidden)
    after the final norm, the multi-token-prediction module's hidden
    states, the same shape). ``cfg`` holds ``config.json``'s keys: the
    first ``first_k_dense_replace`` of the ``num_hidden_layers`` layers
    built are dense, the rest hold experts; ``n_routed_experts`` is the
    router's width, ``experts_held`` and ``expert_offset`` (default:
    all, 0) this chip's share of each expert layer; ``vocab_size`` is
    the rows held of the vocabulary. The module's position ``t`` reads
    the embedding of token ``t + 1`` (the ids one place earlier; the
    last position, which has no next token here, is padded and belongs
    to no loss) through the stack's own embedding."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        if int(cfg["num_nextn_predict_layers"]) != 1:
            raise ValueError("one multi-token-prediction module is built, "
                             "not num_nextn_predict_layers %r"
                             % cfg["num_nextn_predict_layers"])
        depth, dense = (int(cfg["num_hidden_layers"]),
                        int(cfg["first_k_dense_replace"]))
        self.mlp_kinds = tuple("dense" if i < dense else "sparse"
                               for i in range(depth))
        hidden = int(cfg["hidden_size"])
        self._eps = float(cfg["rms_norm_eps"])
        with self.name_scope():
            self.embed = nn.Embedding(int(cfg["vocab_size"]), hidden,
                                      weight_initializer=init.Normal(0.02),
                                      prefix="embed_")
            self.layers = nn.HybridSequential(prefix="")
            for i, kind in enumerate(self.mlp_kinds):
                self.layers.add(Glm4MoeLiteDecoderLayer(
                    cfg, kind, prefix="layers%d_" % i))
            self.norm_f_weight = self.params.get(
                "norm_f_weight", shape=(hidden,), init="ones")
            self.mtp = Glm4MoeLiteMTP(cfg, prefix="mtp_")

    def hybrid_forward(self, F, ids, *, norm_f_weight):
        x = self.embed(ids)
        for layer in self.layers:
            x = layer(x)
        return (F._contrib_rms_norm(x, norm_f_weight, eps=self._eps),
                self.mtp(self.embed(_shift_left(F, ids)), x))


class Glm4MoeLiteLMLoss(HybridBlock):
    """The untied, bias-free head, used twice, and both cross-entropies
    through the streaming chunked-CE op: (hidden states, the module's
    hidden states, labels) -> ``mean_t CE(head(hidden_t), labels_t) +
    mtp_loss_weight * mean_{t < length - 1} CE(head(module_t),
    labels_{t+1})``, shape (1,), float32. ``labels`` are the feed's next
    tokens; the module's targets are those one place earlier (the
    tokens two ahead), and its last position has none."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self._weight = float(cfg["mtp_loss_weight"])
        with self.name_scope():
            self.head_weight = self.params.get(
                "head_weight", init=init.Normal(0.02),
                shape=(int(cfg["vocab_size"]), int(cfg["hidden_size"])))
            self.loss_terms = self.params.get(
                "loss_terms", shape=(2,), grad_req="null", init="zeros",
                differentiable=False)
            self.loss_terms._is_aux = True

    def hybrid_forward(self, F, hidden, mtp_hidden, labels, head_weight,
                       loss_terms):
        lm = F._contrib_chunked_lm_head_ce_nobias(hidden, head_weight, labels)
        mtp = F._contrib_chunked_lm_head_ce_nobias(
            mtp_hidden, head_weight, _shift_left(F, labels))
        return F._contrib_mtp_loss(lm, mtp, loss_terms,
                                   mtp_weight=self._weight)


def publish_loss_terms(aux):
    """Publish the loss block's auxiliary state (``ShardedTrainStep.aux``,
    or any ``{name: array}`` holding ``*loss_terms``): gauges
    ``mx_lm_loss`` (the last step's mean next-token loss) and
    ``mx_mtp_loss`` (its mean multi-token-prediction loss, unweighted).
    Returns ``(lm, mtp)``, or None where there is no such state. One
    device-to-host read: call it after a window, not inside one."""
    import jax
    from ... import telemetry
    names = [n for n in aux if n.endswith("loss_terms")]
    if not names:
        return None
    state = aux[names[0]]
    lm, mtp = (float(v) for v in np.asarray(jax.device_get(
        state._jax() if hasattr(state, "_jax") else state), np.float64))
    telemetry.gauge("mx_lm_loss").set(lm)
    telemetry.gauge("mx_mtp_loss").set(mtp)
    return lm, mtp
