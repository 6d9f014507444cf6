"""Mellum 2: a causal decoder whose every layer is ``x <- x +
Attn_l(RMSNorm(x))``, ``x <- x + MoE(RMSNorm(x))`` (JetBrains,
``model_type`` ``mellum``; the keys below are its ``config.json``'s),
with two kinds of attention layer in one stack, named layer by layer in
``layer_types``: ``sliding_attention`` (a query sees the last
``sliding_window`` keys, its own among them) and ``full_attention``
(every earlier key). Both are grouped-query with RMSNorm over each head
of q and k and rotary positions, by a table of the layer type's own
(``rope_parameters[kind]``: plain rotary for the sliding layers, YaRN
with its ``attention_factor`` on cos and sin for the full ones). The MLP
is a softmax top-k router over SwiGLU experts, no shared expert. No bias
anywhere, untied head.

The zoo's third decoder, and the first whose layers are built from a
per-layer list of kinds: one class of layer, two parameterisations of
one mixer op (``_contrib_rotary_gqa_mixer``: the window and the rotary
rule are its attributes). Built like the other two (``nemotron_h.py``,
``keye_vl.py``): one mixer op of ``ops/decoder_ops.py`` a residual
branch, traced by ``parallel.trace_block`` into the one program
``ShardedTrainStep`` compiles; recomputation lives in the mixer ops.

Expert parallelism's share is told as in ``nemotron_h.py``:
``experts_held`` from ``expert_offset`` on, of the router's
``num_experts``. ``expert_rows`` (rows routed to each held expert) is an
auxiliary state, rewritten every call, never differentiated. The
multi-token-prediction head the model card mentions has no key in
``config.json`` and is not built.
"""
from __future__ import annotations

import math

from ... import initializer as init
from .. import nn
from ..block import HybridBlock
from .nemotron_h import NemotronHLMLoss, publish_expert_rows

__all__ = ["MellumModel", "MellumLMLoss", "MellumDecoderLayer",
           "publish_expert_rows", "KINDS"]

KINDS = ("sliding_attention", "full_attention")


def _rope_attrs(rope, head_dim=None):
    """A ``rope_parameters`` entry as the mixer op's attributes; with
    ``partial_rotary_factor`` below 1, ``rotary_dim`` lanes of the
    ``head_dim`` are turned."""
    kind = rope.get("rope_type", "default")
    attrs = dict(rope_theta=float(rope["rope_theta"]))
    part = float(rope.get("partial_rotary_factor", 1))
    if part != 1:
        attrs["rotary_dim"] = int(head_dim * part)
    if kind == "yarn":
        attrs["rope_yarn"] = (
            float(rope["factor"]),
            float(rope["original_max_position_embeddings"]),
            float(rope.get("beta_fast", 32)), float(rope.get("beta_slow", 1)))
        # the public rule's default where the config gives none
        attrs["attention_factor"] = float(rope.get(
            "attention_factor", 0.1 * math.log(float(rope["factor"])) + 1.0))
    elif kind != "default":
        raise ValueError("rope_type %r is not one this model builds "
                         "(default, yarn)" % kind)
    return attrs


class MellumDecoderLayer(HybridBlock):
    """x -> x after both residual branches; ``kind`` (one of
    :data:`KINDS`) picks the attention's window and rotary table."""

    def __init__(self, cfg, kind, **kwargs):
        super().__init__(**kwargs)
        if kind not in KINDS:
            raise ValueError("layer type %r is not one of %s" % (kind, KINDS))
        self.kind = kind
        hidden = int(cfg["hidden_size"])
        heads, kv = int(cfg["num_attention_heads"]), \
            int(cfg["num_key_value_heads"])
        d = int(cfg["head_dim"])
        routed = int(cfg["num_experts"])
        held = int(cfg.get("experts_held", routed))
        offset = int(cfg.get("expert_offset", 0))
        if not 0 <= offset <= routed - held:
            raise ValueError("experts %d..%d are not among the router's %d"
                             % (offset, offset + held, routed))
        width = int(cfg["moe_intermediate_size"])
        eps = float(cfg["rms_norm_eps"])
        window = 0
        if kind == "sliding_attention":
            window = int(cfg["sliding_window"])
            if window < 1:
                raise ValueError("a sliding layer needs a window, not %r"
                                 % cfg["sliding_window"])
        self._attn = dict(
            num_heads=heads, num_kv_heads=kv, head_dim=d, window=window,
            eps=eps, **_rope_attrs(cfg["rope_parameters"][kind]))
        self._moe = dict(
            top_k=int(cfg["num_experts_per_tok"]), expert_offset=offset,
            norm_topk_prob=bool(cfg["norm_topk_prob"]), score_func="softmax",
            activation="swiglu", eps=eps)
        # matrices N(0, 0.02); the two that write into the residual
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
            self.q_norm_weight = get("q_norm_weight", shape=(d,), init="ones")
            self.k_norm_weight = get("k_norm_weight", shape=(d,), init="ones")
            self.moe_norm_weight = get("moe_norm_weight", shape=(hidden,),
                                       init="ones")
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
                "experts_down_weight", shape=(held, hidden, width),
                init=w_out)

    def hybrid_forward(self, F, x, *, attn_norm_weight, q_weight, k_weight,
                       v_weight, o_weight, q_norm_weight, k_norm_weight,
                       moe_norm_weight, router_weight, expert_rows,
                       experts_gate_up_weight, experts_down_weight):
        x = x + F._contrib_rotary_gqa_mixer(
            x, attn_norm_weight, q_weight, k_weight, v_weight, o_weight,
            q_norm_weight, k_norm_weight, **self._attn)
        return x + F._contrib_moe_mixer(
            x, moe_norm_weight, router_weight, expert_rows,
            experts_gate_up_weight, experts_down_weight, **self._moe)


class MellumModel(HybridBlock):
    """ids (batch, length) -> hidden states (batch, length, hidden)
    after the final norm. ``cfg`` holds ``config.json``'s keys;
    ``layer_types`` names each layer's attention (its first
    ``num_hidden_layers`` entries are built, or ``layer_types`` given
    here in their place); ``num_experts`` is the router's width,
    ``experts_held`` and ``expert_offset`` (default: all, 0) this
    chip's share of each layer's experts; ``vocab_size`` is the rows
    held of the vocabulary."""

    def __init__(self, cfg, layer_types=None, **kwargs):
        super().__init__(**kwargs)
        depth = int(cfg["num_hidden_layers"])
        kinds = list(cfg["layer_types"] if layer_types is None
                     else layer_types)
        if len(kinds) < depth:
            raise ValueError("layer_types names %d layers, "
                             "num_hidden_layers asks for %d"
                             % (len(kinds), depth))
        sparse = list(cfg.get("mlp_layer_types", ()))[:depth]
        if any(kind != "sparse" for kind in sparse):
            raise ValueError("every layer holds experts here: "
                             "mlp_layer_types %r" % sparse)
        self.layer_types = tuple(kinds[:depth])
        hidden = int(cfg["hidden_size"])
        self._eps = float(cfg["rms_norm_eps"])
        with self.name_scope():
            self.embed = nn.Embedding(int(cfg["vocab_size"]), hidden,
                                      weight_initializer=init.Normal(0.02),
                                      prefix="embed_")
            self.layers = nn.HybridSequential(prefix="")
            for i, kind in enumerate(self.layer_types):
                self.layers.add(MellumDecoderLayer(cfg, kind,
                                                   prefix="layers%d_" % i))
            self.norm_f_weight = self.params.get(
                "norm_f_weight", shape=(hidden,), init="ones")

    def hybrid_forward(self, F, ids, *, norm_f_weight):
        x = self.embed(ids)
        for layer in self.layers:
            x = layer(x)
        return F._contrib_rms_norm(x, norm_f_weight, eps=self._eps)


class MellumLMLoss(NemotronHLMLoss):
    """The untied, bias-free head and the cross-entropy through the
    streaming chunked-CE op (the first decoder's block): (hidden
    states, labels) -> the mean next-token loss over every position,
    shape (1,), float32."""

    def hybrid_forward(self, F, hidden, labels, head_weight):
        return super().hybrid_forward(F, hidden, labels, head_weight).mean()
