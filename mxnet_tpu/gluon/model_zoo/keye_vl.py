"""Keye-VL 2.0's language model: a causal decoder whose every layer is
``x <- x + Attn(RMSNorm(x))``, ``x <- x + MoE(RMSNorm(x))`` (Kwai-Keye,
``model_type`` ``KeyeVL2``; the keys below are its ``config.json``'s).
Attention is grouped-query with RMSNorm over each head of q and k,
multi-axis rotary positions (M-RoPE, ``rope_scaling.mrope_section``) and
a learned selector in front of it (``sa_config``: a lightning indexer
that keeps the ``topk`` keys of largest index score a query); the MLP
is a softmax top-k router over SwiGLU experts, no shared expert. No
bias anywhere, untied head.

The zoo's second decoder, built like the first (``nemotron_h.py``): one
mixer op of ``ops/decoder_ops.py`` a residual branch, traced by
``parallel.trace_block`` into the one program ``ShardedTrainStep``
compiles; recomputation lives in the mixer ops. The model returns
``(hidden states, index loss)``: the selector is trained by a loss of
its own, summed over the layers, which the loss adapter adds to the
language-model loss (docs/TRAINING.md "A second loss").

Expert parallelism's share is told as in ``nemotron_h.py``:
``experts_held`` from ``expert_offset`` on, of the router's
``num_experts``. ``expert_rows`` (rows routed to each held expert) and
``dsa_state`` (keys a query attended, index loss) are auxiliary states,
rewritten every call, never differentiated. The vision tower is not
built: the model takes M-RoPE position ids (3, batch, length) so that
image positions can be fed, and text needs none.
"""
from __future__ import annotations

import math

import numpy as np

from ... import initializer as init
from .. import nn
from ..block import HybridBlock
from .nemotron_h import publish_expert_rows

__all__ = ["KeyeVLTextModel", "KeyeVLLMLoss", "KeyeVLDecoderLayer",
           "publish_expert_rows", "publish_selector_state"]


class KeyeVLDecoderLayer(HybridBlock):
    """(x, positions or nothing) -> (x after both residual branches,
    this layer's index loss)."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        hidden = int(cfg["hidden_size"])
        heads, kv = int(cfg["num_attention_heads"]), \
            int(cfg["num_key_value_heads"])
        d = int(cfg["head_dim"])
        sa = cfg["sa_config"]
        ih, idim = int(sa["indexer_num_heads"]), int(sa["indexer_head_dim"])
        if int(sa["indexer_num_kv_heads"]) != 1:
            raise ValueError("the selector has one index key head, not %s"
                             % sa["indexer_num_kv_heads"])
        routed = int(cfg["num_experts"])
        held = int(cfg.get("experts_held", routed))
        offset = int(cfg.get("expert_offset", 0))
        if not 0 <= offset <= routed - held:
            raise ValueError("experts %d..%d are not among the router's %d"
                             % (offset, offset + held, routed))
        width = int(cfg["moe_intermediate_size"])
        eps = float(cfg["rms_norm_eps"])
        self._attn = dict(
            num_heads=heads, num_kv_heads=kv, head_dim=d, index_heads=ih,
            index_head_dim=idim, top_k=int(sa["topk"]),
            rope_theta=float(cfg["rope_theta"]),
            rope_sections=tuple(cfg["rope_scaling"]["mrope_section"]),
            eps=eps)
        self._moe = dict(
            top_k=int(cfg["num_experts_per_tok"]), expert_offset=offset,
            norm_topk_prob=bool(cfg["norm_topk_prob"]), score_func="softmax",
            activation="swiglu", eps=eps)
        # matrices N(0, 0.02); the two that write into the residual
        # stream shrunk by sqrt(2 x layers)
        w_in = init.Normal(0.02)
        w_out = init.Normal(
            0.02 / math.sqrt(2 * int(cfg["num_hidden_layers"])))

        def state(name, shape):
            p = self.params.get(name, shape=shape, grad_req="null",
                                init="zeros", differentiable=False)
            p._is_aux = True
            return p

        get = self.params.get
        with self.name_scope():
            self.attn_norm_weight = get("attn_norm_weight", shape=(hidden,),
                                        init="ones")
            self.q_weight = get("q_weight", shape=(heads * d, hidden),
                                init=w_in)
            self.k_weight = get("k_weight", shape=(kv * d, hidden), init=w_in)
            self.v_weight = get("v_weight", shape=(kv * d, hidden), init=w_in)
            self.o_weight = get("o_weight", shape=(hidden, heads * d),
                                init=w_out)
            self.q_norm_weight = get("q_norm_weight", shape=(d,), init="ones")
            self.k_norm_weight = get("k_norm_weight", shape=(d,), init="ones")
            self.index_q_weight = get("index_q_weight",
                                      shape=(ih * idim, hidden), init=w_in)
            self.index_k_weight = get("index_k_weight", shape=(idim, hidden),
                                      init=w_in)
            self.index_w_weight = get("index_w_weight", shape=(ih, hidden),
                                      init=w_in)
            self.index_k_norm_weight = get("index_k_norm_weight",
                                           shape=(idim,), init="ones")
            self.index_k_norm_bias = get("index_k_norm_bias", shape=(idim,),
                                         init="zeros")
            self.dsa_state = state("dsa_state", (2,))
            self.moe_norm_weight = get("moe_norm_weight", shape=(hidden,),
                                       init="ones")
            self.router_weight = get("router_weight", shape=(routed, hidden),
                                     init=w_in)
            self.expert_rows = state("expert_rows", (2, held))
            # an expert's gate rows, then its up projection's
            self.experts_gate_up_weight = get(
                "experts_gate_up_weight", shape=(held, 2 * width, hidden),
                init=w_in)
            self.experts_down_weight = get(
                "experts_down_weight", shape=(held, hidden, width),
                init=w_out)

    def hybrid_forward(self, F, x, positions=None, *, attn_norm_weight,
                       q_weight, k_weight, v_weight, o_weight, q_norm_weight,
                       k_norm_weight, index_q_weight, index_k_weight,
                       index_w_weight, index_k_norm_weight, index_k_norm_bias,
                       dsa_state, moe_norm_weight, router_weight, expert_rows,
                       experts_gate_up_weight, experts_down_weight):
        y, index_loss = F._contrib_sparse_gqa_mixer(
            x, attn_norm_weight, q_weight, k_weight, v_weight, o_weight,
            q_norm_weight, k_norm_weight, index_q_weight, index_k_weight,
            index_w_weight, index_k_norm_weight, index_k_norm_bias, dsa_state,
            positions, **self._attn)
        x = x + y
        x = x + F._contrib_moe_mixer(
            x, moe_norm_weight, router_weight, expert_rows,
            experts_gate_up_weight, experts_down_weight, **self._moe)
        return x, index_loss


class KeyeVLTextModel(HybridBlock):
    """ids (batch, length) [, M-RoPE position ids (3, batch, length)]
    -> (hidden states (batch, length, hidden) after the final norm, the
    layers' summed index loss, shape (1,)). ``cfg`` holds
    ``config.json``'s keys; ``num_experts`` is the router's width,
    ``experts_held`` and ``expert_offset`` (default: all, 0) this
    chip's share of each layer's experts; ``vocab_size`` is the rows
    held of the vocabulary. Without position ids every axis holds the
    token's index (text)."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        if int(cfg.get("decoder_sparse_step", 1)) != 1 \
                or cfg.get("mlp_only_layers"):
            raise ValueError("every layer holds experts here: "
                             "decoder_sparse_step %r, mlp_only_layers %r"
                             % (cfg.get("decoder_sparse_step"),
                                cfg.get("mlp_only_layers")))
        hidden = int(cfg["hidden_size"])
        self._eps = float(cfg["rms_norm_eps"])
        with self.name_scope():
            self.embed = nn.Embedding(int(cfg["vocab_size"]), hidden,
                                      weight_initializer=init.Normal(0.02),
                                      prefix="embed_")
            self.layers = nn.HybridSequential(prefix="")
            for i in range(int(cfg["num_hidden_layers"])):
                self.layers.add(KeyeVLDecoderLayer(cfg,
                                                   prefix="layers%d_" % i))
            self.norm_f_weight = self.params.get(
                "norm_f_weight", shape=(hidden,), init="ones")

    def hybrid_forward(self, F, ids, positions=None, *, norm_f_weight):
        x, total = self.embed(ids), None
        for layer in self.layers:
            x, loss = layer(x) if positions is None else layer(x, positions)
            total = loss if total is None else total + loss
        return F._contrib_rms_norm(x, norm_f_weight, eps=self._eps), total


class KeyeVLLMLoss(HybridBlock):
    """The untied, bias-free head and the cross-entropy through the
    streaming chunked-CE op, plus the model's second loss: (hidden
    states, index loss, labels) -> the mean next-token loss over every
    position + the index loss, shape (1,), float32."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.head_weight = self.params.get(
                "head_weight", init=init.Normal(0.02),
                shape=(int(cfg["vocab_size"]), int(cfg["hidden_size"])))

    def hybrid_forward(self, F, hidden, index_loss, labels, head_weight):
        return F._contrib_chunked_lm_head_ce_nobias(
            hidden, head_weight, labels).mean() + index_loss


def publish_selector_state(aux):
    """Publish the selectors' auxiliary states (``ShardedTrainStep.aux``,
    or any ``{name: array}`` holding ``*dsa_state``): gauges
    ``mx_attn_keys_per_query{block}`` (the mean number of keys a query
    attended in the last step) and ``mx_attn_index_loss{block}`` (the
    layer's index loss there). Returns ``{block: (keys, loss)}``. One
    device-to-host read: call it after a window, not inside one."""
    import jax
    from ... import telemetry
    names = sorted(n for n in aux if n.endswith("dsa_state"))
    states = jax.device_get([aux[n]._jax() if hasattr(aux[n], "_jax")
                             else aux[n] for n in names])
    out = {}
    for name, state in zip(names, states):
        block = name[:-len("_dsa_state")]
        keys, loss = (float(v) for v in np.asarray(state, np.float64))
        telemetry.gauge("mx_attn_keys_per_query", block=block).set(keys)
        telemetry.gauge("mx_attn_index_loss", block=block).set(loss)
        out[block] = (keys, loss)
    return out
