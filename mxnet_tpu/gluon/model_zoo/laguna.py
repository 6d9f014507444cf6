"""Laguna-XS.2: a causal decoder whose every layer is ``x <- x +
Attn_l(RMSNorm(x))``, ``x <- x + F_l(RMSNorm(x))`` (poolside,
``model_type`` ``laguna``; the keys below are its ``config.json``'s),
built from three per-layer lists at once. ``layer_types`` names a
layer's attention, ``sliding_attention`` (a query sees the last
``sliding_window`` keys, its own among them) or ``full_attention``
(every earlier key); ``num_attention_heads_per_layer`` gives its query
heads (more in a sliding layer than in a full one, over the same
``num_key_value_heads``), so ``q``, ``o`` and the gate differ in shape
from layer to layer; ``mlp_layer_types`` names ``F_l``, a ``dense``
SwiGLU MLP of width ``intermediate_size`` or ``sparse``: a softmax
top-k router (the chosen scores renormalised, times
``moe_routed_scaling_factor``) over SwiGLU experts beside one shared
expert. Attention is grouped-query with no norm on q or k, rotary by
the layer type's own table (``rope_parameters[kind]``: plain rotary
over the whole head for the sliding layers; for the full ones YaRN over
``partial_rotary_factor`` of a head's lanes, the rest without
position), and with ``gating`` a sigmoid gate a head, ``sigmoid(W_g
h)``, on the context before the output projection. No bias anywhere,
untied head.

The zoo's fifth decoder, and the first whose layers differ in their
parameters' shapes by a per-layer list. One class of layer; one mixer
op of ``ops/decoder_ops.py`` a residual branch
(``_contrib_rotary_gqa_mixer`` with the layer's heads, window, rotary
table and gate; ``_contrib_glu_mlp_mixer`` or ``_contrib_moe_mixer``),
traced by ``parallel.trace_block`` into the one program
``ShardedTrainStep`` compiles; recomputation lives in the mixer ops.

Expert parallelism's share is told as in ``nemotron_h.py``:
``experts_held`` from ``expert_offset`` on, of the router's
``num_experts``. ``expert_rows`` (rows routed to each held expert) is an
auxiliary state, rewritten every call, never differentiated.
"""
from __future__ import annotations

import math

from ... import initializer as init
from .. import nn
from ..block import HybridBlock
from .mellum import KINDS, MellumLMLoss, _rope_attrs
from .nemotron_h import publish_expert_rows

__all__ = ["LagunaModel", "LagunaLMLoss", "LagunaDecoderLayer",
           "publish_expert_rows", "KINDS", "MLP_KINDS"]

MLP_KINDS = ("dense", "sparse")

# a layer's parameters in the order its mixer ops take them
_ATTN = ("attn_norm_weight", "q_weight", "k_weight", "v_weight", "o_weight")
_DENSE = ("mlp_norm_weight", "gate_up_weight", "down_weight")
_SPARSE = ("mlp_norm_weight", "router_weight", "expert_rows",
           "experts_gate_up_weight", "experts_down_weight")


def _of_layer(cfg, key, index):
    """Entry ``index`` of one of the three per-layer lists."""
    values = cfg[key]
    if index >= len(values):
        raise ValueError("%s names %d layers, layer %d is asked for"
                         % (key, len(values), index))
    return values[index]


class LagunaDecoderLayer(HybridBlock):
    """x -> x after both residual branches; layer ``index``'s attention
    kind, query heads and MLP kind come from ``layer_types``,
    ``num_attention_heads_per_layer`` and ``mlp_layer_types``."""

    def __init__(self, cfg, index, **kwargs):
        super().__init__(**kwargs)
        self.kind = _of_layer(cfg, "layer_types", index)
        self.heads = int(_of_layer(cfg, "num_attention_heads_per_layer",
                                   index))
        self.mlp_kind = _of_layer(cfg, "mlp_layer_types", index)
        if self.kind not in KINDS:
            raise ValueError("layer type %r is not one of %s"
                             % (self.kind, KINDS))
        if self.mlp_kind not in MLP_KINDS:
            raise ValueError("MLP kind %r is not one of %s"
                             % (self.mlp_kind, MLP_KINDS))
        hidden = int(cfg["hidden_size"])
        heads, kv = self.heads, int(cfg["num_key_value_heads"])
        if heads < kv or heads % kv:
            raise ValueError("layer %d: %d key-value heads do not divide its "
                             "%d query heads" % (index, kv, heads))
        d = int(cfg["head_dim"])
        self._eps = eps = float(cfg["rms_norm_eps"])
        window = 0
        if self.kind == "sliding_attention":
            window = int(cfg["sliding_window"])
            if window < 1:
                raise ValueError("a sliding layer needs a window, not %r"
                                 % cfg["sliding_window"])
        self._attn = dict(
            num_heads=heads, num_kv_heads=kv, head_dim=d, window=window,
            eps=eps, **_rope_attrs(cfg["rope_parameters"][self.kind], d))
        self._gated = bool(cfg.get("gating", False))
        # matrices N(0, 0.02); those that write into the residual
        # stream shrunk by sqrt(2 x layers)
        w_in = init.Normal(0.02)
        w_out = init.Normal(
            0.02 / math.sqrt(2 * int(cfg["num_hidden_layers"])))
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
            if self._gated:
                # one row a query head; gates start near 1/2
                self.attn_gate_weight = get(
                    "attn_gate_weight", shape=(heads, hidden), init=w_in)
            self.mlp_norm_weight = get("mlp_norm_weight", shape=(hidden,),
                                       init="ones")
            if self.mlp_kind == "dense":
                width = int(cfg["intermediate_size"])
                # the gate's rows, then the up projection's
                self.gate_up_weight = get(
                    "gate_up_weight", shape=(2 * width, hidden), init=w_in)
                self.down_weight = get("down_weight", shape=(hidden, width),
                                       init=w_out)
            else:
                self._experts(cfg, hidden, w_in, w_out)

    def _experts(self, cfg, hidden, w_in, w_out):
        routed = int(cfg["num_experts"])
        held = int(cfg.get("experts_held", routed))
        offset = int(cfg.get("expert_offset", 0))
        if not 0 <= offset <= routed - held:
            raise ValueError("experts %d..%d are not among the router's %d"
                             % (offset, offset + held, routed))
        if cfg.get("moe_apply_router_weight_on_input", False):
            raise ValueError("the router's weight goes on an expert's "
                             "output here, not on its input")
        width = int(cfg["moe_intermediate_size"])
        shared = int(cfg["shared_expert_intermediate_size"])
        self._moe = dict(
            top_k=int(cfg["num_experts_per_tok"]), expert_offset=offset,
            routed_scaling_factor=float(cfg["moe_routed_scaling_factor"]),
            norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
            score_func="softmax", activation="swiglu", eps=self._eps)
        get = self.params.get
        self.router_weight = get("router_weight", shape=(routed, hidden),
                                 init=w_in)
        self.expert_rows = get("expert_rows", shape=(2, held),
                               grad_req="null", init="zeros",
                               differentiable=False)
        self.expert_rows._is_aux = True
        # an expert's gate rows, then its up projection's
        self.experts_gate_up_weight = get(
            "experts_gate_up_weight", shape=(held, 2 * width, hidden),
            init=w_in)
        self.experts_down_weight = get(
            "experts_down_weight", shape=(held, hidden, width), init=w_out)
        self.shared_gate_up_weight = get(
            "shared_gate_up_weight", shape=(2 * shared, hidden), init=w_in)
        self.shared_down_weight = get(
            "shared_down_weight", shape=(hidden, shared), init=w_out)

    def hybrid_forward(self, F, x, **w):
        # ``w``: this layer's parameters by name, those of its kinds only
        gate = {"gate_weight": w["attn_gate_weight"]} if self._gated else {}
        x = x + F._contrib_rotary_gqa_mixer(x, *(w[n] for n in _ATTN),
                                            **gate, **self._attn)
        if self.mlp_kind == "dense":
            return x + F._contrib_glu_mlp_mixer(
                x, *(w[n] for n in _DENSE), eps=self._eps)
        return x + F._contrib_moe_mixer(
            x, *(w[n] for n in _SPARSE), shared_w1=w["shared_gate_up_weight"],
            shared_w2=w["shared_down_weight"], **self._moe)


class LagunaModel(HybridBlock):
    """ids (batch, length) -> hidden states (batch, length, hidden)
    after the final norm. ``cfg`` holds ``config.json``'s keys; the
    first ``num_hidden_layers`` entries of ``layer_types``,
    ``num_attention_heads_per_layer`` and ``mlp_layer_types`` are built
    (a list shorter than that is refused); ``num_experts`` is the
    router's width, ``experts_held`` and ``expert_offset`` (default:
    all, 0) this chip's share of each expert layer; ``vocab_size`` is
    the rows held of the vocabulary."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        depth = int(cfg["num_hidden_layers"])
        hidden = int(cfg["hidden_size"])
        self._eps = float(cfg["rms_norm_eps"])
        with self.name_scope():
            self.embed = nn.Embedding(int(cfg["vocab_size"]), hidden,
                                      weight_initializer=init.Normal(0.02),
                                      prefix="embed_")
            self.layers = nn.HybridSequential(prefix="")
            for i in range(depth):
                self.layers.add(LagunaDecoderLayer(cfg, i,
                                                   prefix="layers%d_" % i))
            self.norm_f_weight = self.params.get(
                "norm_f_weight", shape=(hidden,), init="ones")

    def hybrid_forward(self, F, ids, *, norm_f_weight):
        x = self.embed(ids)
        for layer in self.layers:
            x = layer(x)
        return F._contrib_rms_norm(x, norm_f_weight, eps=self._eps)


class LagunaLMLoss(MellumLMLoss):
    """The untied, bias-free head and the cross-entropy through the
    streaming chunked-CE op: (hidden states, labels) -> the mean
    next-token loss over every position, shape (1,), float32."""
