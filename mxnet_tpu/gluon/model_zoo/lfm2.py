"""LFM2 (mixture-of-experts): a causal decoder whose every layer is ``x
<- x + Op_l(RMSNorm(x))``, ``x <- x + F_l(RMSNorm(x))`` (LiquidAI,
``model_type`` ``lfm2_moe``; the keys below are its ``config.json``'s),
built from a per-layer list and a count at once. ``layer_types`` names
``Op_l``: ``conv``, a gated short convolution (two element-wise gates
around a depthwise causal filter of ``conv_L_cache`` taps, between a
hidden -> 3 x hidden and a hidden -> hidden product: no state beyond
the last taps, no activation function), or ``full_attention``, causal
grouped-query attention with an RMSNorm over each head of q and k
(one weight a lane, shared by the heads) before rotary positions over
the whole head. ``num_dense_layers`` names ``F_l``: a dense SwiGLU MLP
of width ``intermediate_size`` in the first that many layers, after
them a sigmoid top-k router (``use_expert_bias``: a selection bias that
moves the choice and never the weight; the chosen scores renormalised,
times ``routed_scaling_factor``) over SwiGLU experts with no shared
expert. No bias anywhere; the head is the embedding's matrix.

The zoo's sixth decoder; the first with two kinds of mixer *and* two
kinds of MLP in one class of layer, and the first whose loss block
reads a parameter of the net: :class:`Lfm2LMLoss` is handed the
embedding's ``Parameter`` itself (one object, one name), so a traced
step has one input for it, one master and one gradient, the sum of both
uses. Built like the others: one mixer op of ``ops/decoder_ops.py`` a
residual branch (``_contrib_short_conv_mixer`` or
``_contrib_rotary_gqa_mixer``; ``_contrib_glu_mlp_mixer`` or
``_contrib_moe_mixer``), traced by ``parallel.trace_block`` into the one
program ``ShardedTrainStep`` compiles; recomputation lives in the mixer
ops.

Expert parallelism's share is told as in ``nemotron_h.py``:
``experts_held`` from ``expert_offset`` on, of the router's
``num_experts``. ``expert_bias`` (seeded, never updated) and
``expert_rows`` (rows routed to each held expert) are auxiliary states,
never differentiated.
"""
from __future__ import annotations

import math

from ... import initializer as init
from .. import nn
from ..block import HybridBlock
from .nemotron_h import _Draw, publish_expert_rows

__all__ = ["Lfm2MoeModel", "Lfm2LMLoss", "Lfm2DecoderLayer",
           "publish_expert_rows", "KINDS", "MLP_KINDS"]

KINDS = ("conv", "full_attention")
MLP_KINDS = ("dense", "sparse")

# a layer's parameters in the order its mixer ops take them
_CONV = ("op_norm_weight", "in_weight", "conv_weight", "out_weight")
_ATTN = ("op_norm_weight", "q_weight", "k_weight", "v_weight", "o_weight",
         "q_norm_weight", "k_norm_weight")
_DENSE = ("ffn_norm_weight", "gate_up_weight", "down_weight")
_SPARSE = ("ffn_norm_weight", "router_weight", "expert_rows",
           "experts_gate_up_weight", "experts_down_weight", "expert_bias")


class Lfm2DecoderLayer(HybridBlock):
    """x -> x after both residual branches; layer ``index``'s mixer
    comes from ``layer_types[index]``, its MLP from ``index <
    num_dense_layers``."""

    def __init__(self, cfg, index, **kwargs):
        super().__init__(**kwargs)
        kinds = cfg["layer_types"]
        if index >= len(kinds):
            raise ValueError("layer_types names %d layers, layer %d is asked "
                             "for" % (len(kinds), index))
        self.kind = kinds[index]
        if self.kind not in KINDS:
            raise ValueError("layer type %r is not one of %s"
                             % (self.kind, KINDS))
        self.mlp_kind = MLP_KINDS[index >= int(cfg["num_dense_layers"])]
        hidden = int(cfg["hidden_size"])
        depth = int(cfg["num_hidden_layers"])
        self._eps = float(cfg["norm_eps"])
        # matrices N(0, 0.02); those that write into the residual
        # stream shrunk by sqrt(2 x layers)
        w_in = init.Normal(0.02)
        w_out = init.Normal(0.02 / math.sqrt(2 * depth))
        get = self.params.get
        with self.name_scope():
            self.op_norm_weight = get("op_norm_weight", shape=(hidden,),
                                      init="ones")
            if self.kind == "conv":
                self._conv(cfg, hidden, w_in, w_out)
            else:
                self._attention(cfg, hidden, w_in, w_out)
            self.ffn_norm_weight = get("ffn_norm_weight", shape=(hidden,),
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

    def _conv(self, cfg, hidden, w_in, w_out):
        taps = int(cfg["conv_L_cache"])
        if taps < 1:
            raise ValueError("a short convolution needs a tap, not "
                             "conv_L_cache %r" % cfg["conv_L_cache"])
        if cfg.get("conv_bias", False):
            raise ValueError("the short convolution is built without a bias "
                             "(the published conv_bias is false)")
        get = self.params.get
        # the B gate's rows, then the C gate's, then the value's
        self.in_weight = get("in_weight", shape=(3 * hidden, hidden),
                             init=w_in)
        # one filter a channel; at N(0, 0.02) sqrt(hidden / taps) the
        # filter's output is as large as a projection's of the same
        # input would be (sum of squares 0.02^2 x hidden a channel)
        self.conv_weight = get(
            "conv_weight", shape=(hidden, taps),
            init=init.Normal(0.02 * math.sqrt(hidden / taps)))
        self.out_weight = get("out_weight", shape=(hidden, hidden),
                              init=w_out)

    def _attention(self, cfg, hidden, w_in, w_out):
        heads, kv = (int(cfg["num_attention_heads"]),
                     int(cfg["num_key_value_heads"]))
        if heads < kv or heads % kv or hidden % heads:
            raise ValueError("%d key-value heads, %d query heads and a hidden "
                             "size of %d do not divide" % (kv, heads, hidden))
        d = hidden // heads
        rope = cfg["rope_parameters"]
        if rope.get("rope_type", "default") != "default":
            raise ValueError("rope_type %r is not built (the published "
                             "config's is default)" % rope["rope_type"])
        self._attn = dict(num_heads=heads, num_kv_heads=kv, head_dim=d,
                          rope_theta=float(rope["rope_theta"]), eps=self._eps)
        get = self.params.get
        self.q_weight = get("q_weight", shape=(heads * d, hidden), init=w_in)
        self.k_weight = get("k_weight", shape=(kv * d, hidden), init=w_in)
        self.v_weight = get("v_weight", shape=(kv * d, hidden), init=w_in)
        self.o_weight = get("o_weight", shape=(hidden, heads * d), init=w_out)
        # one weight a lane, shared by the heads
        self.q_norm_weight = get("q_norm_weight", shape=(d,), init="ones")
        self.k_norm_weight = get("k_norm_weight", shape=(d,), init="ones")

    def _experts(self, cfg, hidden, w_in, w_out):
        routed = int(cfg["num_experts"])
        held = int(cfg.get("experts_held", routed))
        offset = int(cfg.get("expert_offset", 0))
        if not 0 <= offset <= routed - held:
            raise ValueError("experts %d..%d are not among the router's %d"
                             % (offset, offset + held, routed))
        if not cfg.get("use_expert_bias", False):
            raise ValueError("the router is built with its selection bias "
                             "(the published use_expert_bias is true)")
        width = int(cfg["moe_intermediate_size"])
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
        self.expert_bias = state("expert_bias", (routed,),
                                 _Draw(lambda u: 0.02 * u - 0.01))
        self.expert_rows = state("expert_rows", (2, held), "zeros")
        # an expert's gate rows, then its up projection's
        self.experts_gate_up_weight = get(
            "experts_gate_up_weight", shape=(held, 2 * width, hidden),
            init=w_in)
        self.experts_down_weight = get(
            "experts_down_weight", shape=(held, hidden, width), init=w_out)

    def hybrid_forward(self, F, x, **w):
        # ``w``: this layer's parameters by name, those of its kinds only
        if self.kind == "conv":
            x = x + F._contrib_short_conv_mixer(
                x, *(w[n] for n in _CONV), eps=self._eps)
        else:
            x = x + F._contrib_rotary_gqa_mixer(
                x, *(w[n] for n in _ATTN), **self._attn)
        if self.mlp_kind == "dense":
            return x + F._contrib_glu_mlp_mixer(
                x, *(w[n] for n in _DENSE), eps=self._eps)
        return x + F._contrib_moe_mixer(x, *(w[n] for n in _SPARSE),
                                        **self._moe)


class Lfm2MoeModel(HybridBlock):
    """ids (batch, length) -> hidden states (batch, length, hidden)
    after the last norm. ``cfg`` holds ``config.json``'s keys: the first
    ``num_hidden_layers`` entries of ``layer_types`` are built (a
    shorter list is refused), the first ``num_dense_layers`` of them
    with the dense MLP; ``num_experts`` is the router's width,
    ``experts_held`` and ``expert_offset`` (default: all, 0) this chip's
    share of each expert layer; ``vocab_size`` is the rows held of the
    vocabulary. ``embed.weight`` is also the head's matrix
    (:class:`Lfm2LMLoss`)."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        depth = int(cfg["num_hidden_layers"])
        hidden = int(cfg["hidden_size"])
        self._eps = float(cfg["norm_eps"])
        with self.name_scope():
            self.embed = nn.Embedding(int(cfg["vocab_size"]), hidden,
                                      weight_initializer=init.Normal(0.02),
                                      prefix="embed_")
            self.layers = nn.HybridSequential(prefix="")
            for i in range(depth):
                self.layers.add(Lfm2DecoderLayer(cfg, i,
                                                 prefix="layers%d_" % i))
            self.norm_f_weight = self.params.get(
                "norm_f_weight", shape=(hidden,), init="ones")

    def hybrid_forward(self, F, ids, *, norm_f_weight):
        x = self.embed(ids)
        for layer in self.layers:
            x = layer(x)
        return F._contrib_rms_norm(x, norm_f_weight, eps=self._eps)


class Lfm2LMLoss(HybridBlock):
    """The tied, bias-free head and the cross-entropy through the
    streaming chunked-CE op: (hidden states, labels) -> the mean
    next-token loss over every position, shape (1,), float32. ``embed``
    is the model's embedding (``Lfm2MoeModel.embed``, or the model): its
    weight is read here as the head's, the same ``Parameter`` object
    under the same name, never a copy; the gradient a step takes for it
    is the sum over the lookup and the head."""

    def __init__(self, cfg, embed, **kwargs):
        super().__init__(**kwargs)
        embed = getattr(embed, "embed", embed)
        shape = (int(cfg["vocab_size"]), int(cfg["hidden_size"]))
        if tuple(embed.weight.shape) != shape:
            raise ValueError("the embedding handed in is %s, the head %s"
                             % (tuple(embed.weight.shape), shape))
        # (a Parameter set as an attribute joins this block's own: the
        # same object under the name the model gave it)
        self.embed_weight = embed.weight

    def hybrid_forward(self, F, hidden, labels, **w):
        # whatever prefix the model gave the embedding's name
        (embed_weight,) = w.values()
        return F._contrib_chunked_lm_head_ce_nobias(
            hidden, embed_weight, labels).mean()
