"""Granite 4.0-H (dense): a causal decoder whose every layer is ``x <-
x + r Mixer_l(RMSNorm(x))``, ``x <- x + r MLP(RMSNorm(x))`` (IBM,
``model_type`` ``granitemoehybrid`` with ``num_local_experts`` 0; the
keys below are its ``config.json``'s). ``layer_types`` names
``Mixer_l``: ``mamba``, a Mamba-2 mixer (``mamba_n_heads`` heads of
``mamba_d_head`` that read ``mamba_n_groups`` groups of B and C, state
``mamba_d_state``, ``mamba_d_conv`` taps with a bias), or
``attention``, causal grouped-query attention with no positional term
(``position_embedding_type`` ``nope``) whose scores are multiplied by
``attention_multiplier`` and not by ``1 / sqrt(d)``. The MLP of every
layer is a dense SwiGLU of width ``shared_intermediate_size``. Four
numbers of the model scale its streams: the embedding is multiplied by
``embedding_multiplier``, each branch by ``residual_multiplier`` (``r``
above) before it is added, the scores by ``attention_multiplier``, and
the logits are divided by ``logits_scaling``. No bias but the conv's;
the head is the embedding's matrix (``tie_word_embeddings``).

The zoo's seventh decoder, and the first that trains on *packed*
rows: it takes ``(ids, segment_ids)``, both (batch, length),
``segment_ids`` one integer a token, non-decreasing along a row, the
same for the tokens of one document. Where the id changes, the conv's
taps read zeros, the scan starts from the zero state and attention
sees no earlier key (``ops/decoder_ops.py``: the ``segment_ids`` input
of ``_contrib_mamba2_mixer`` and ``_contrib_gqa_mixer``), so a row of
documents gives, position for position, what each document gives
alone. The number of documents a row held in the last step is kept in
the auxiliary state ``seq_documents`` (written in the graph, never
differentiated; :func:`publish_seq_documents` reads it).

Built like the others: one mixer op a residual branch
(``_contrib_mamba2_mixer`` or ``_contrib_gqa_mixer``;
``_contrib_glu_mlp_mixer``), traced by ``parallel.trace_block`` into
the one program ``ShardedTrainStep`` compiles; recomputation lives in
the mixer ops. The scan's chunk is the schedule's, not the model's:
``scan_chunk`` (128 where ``cfg`` names none) whatever
``mamba_chunk_size`` says, because at the published 256 a group of 64
heads passes the scan kernels' VMEM budget and the mixers would run
the composition (docs/KERNELS.md).
"""
from __future__ import annotations

import math

import numpy as np

from ... import initializer as init
from .. import nn
from ..block import HybridBlock
from .nemotron_h import _Draw, _dt_bias

__all__ = ["GraniteHybridModel", "GraniteHybridLMLoss",
           "GraniteHybridDecoderLayer", "publish_seq_documents", "KINDS"]

KINDS = ("mamba", "attention")

# Mamba-2's own range of the seeded time step (config.json has no key
# for it)
_TIME_STEP = {"time_step_min": 0.001, "time_step_max": 0.1,
              "time_step_floor": 1e-4}

# a layer's parameters in the order its mixer ops take them
_MAMBA = ("op_norm_weight", "in_proj_weight", "conv_weight", "conv_bias",
          "dt_bias", "a_log", "d", "gate_norm_weight", "out_proj_weight")
_ATTN = ("op_norm_weight", "q_weight", "k_weight", "v_weight", "o_weight")
_MLP = ("ffn_norm_weight", "gate_up_weight", "down_weight")


class GraniteHybridDecoderLayer(HybridBlock):
    """(x, segment_ids) -> x after both residual branches; layer
    ``index``'s mixer comes from ``layer_types[index]``."""

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
        if int(cfg.get("num_local_experts", 0)):
            raise ValueError("the routed experts of granitemoehybrid are not "
                             "built (the published num_local_experts is 0)")
        hidden = int(cfg["hidden_size"])
        self._eps = float(cfg["rms_norm_eps"])
        self._residual = float(cfg["residual_multiplier"])
        # matrices N(0, 0.02); those that write into the residual
        # stream shrunk by sqrt(2 x layers)
        w_in = init.Normal(0.02)
        w_out = init.Normal(0.02 / math.sqrt(2 * int(cfg["num_hidden_layers"])))
        get = self.params.get
        with self.name_scope():
            self.op_norm_weight = get("op_norm_weight", shape=(hidden,),
                                      init="ones")
            if self.kind == "mamba":
                self._mamba(cfg, hidden, w_in, w_out)
            else:
                self._attention(cfg, hidden, w_in, w_out)
            self.ffn_norm_weight = get("ffn_norm_weight", shape=(hidden,),
                                       init="ones")
            width = int(cfg["shared_intermediate_size"])
            # the gate's rows, then the up projection's
            self.gate_up_weight = get(
                "gate_up_weight", shape=(2 * width, hidden), init=w_in)
            self.down_weight = get("down_weight", shape=(hidden, width),
                                   init=w_out)

    def _mamba(self, cfg, hidden, w_in, w_out):
        heads, p = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
        groups, n = int(cfg["mamba_n_groups"]), int(cfg["mamba_d_state"])
        k = int(cfg["mamba_d_conv"])
        inner, conv = heads * p, heads * p + 2 * groups * n
        if inner != int(cfg["mamba_expand"]) * hidden:
            raise ValueError("%d heads of %d are not mamba_expand %r x %d"
                             % (heads, p, cfg["mamba_expand"], hidden))
        if not cfg.get("mamba_conv_bias", True) \
                or cfg.get("mamba_proj_bias", False):
            raise ValueError("the Mamba-2 mixer is built with the conv's "
                             "bias and no other (as published)")
        self._mixer = dict(num_heads=heads, head_dim=p, n_groups=groups,
                           state_size=n, eps=self._eps,
                           chunk_size=int(cfg.get("scan_chunk", 128)))
        get = self.params.get
        # z, then xBC (x | B | C), then dt
        self.in_proj_weight = get(
            "in_proj_weight", shape=(inner + conv + heads, hidden), init=w_in)
        self.conv_weight = get("conv_weight", shape=(conv, k),
                               init=init.Uniform(1.0 / math.sqrt(k)))
        self.conv_bias = get(
            "conv_bias", shape=(conv,),
            init=_Draw(lambda u: (2 * u - 1) / math.sqrt(k)))
        self.dt_bias = get("dt_bias", shape=(heads,),
                           init=_dt_bias(dict(_TIME_STEP, **{
                               key: cfg[key] for key in _TIME_STEP
                               if key in cfg})))
        self.a_log = get("a_log", shape=(heads,),
                         init=_Draw(lambda u: np.log(1 + 15 * u)))
        self.d = get("d", shape=(heads,), init="ones")
        self.gate_norm_weight = get("gate_norm_weight", shape=(inner,),
                                    init="ones")
        self.out_proj_weight = get("out_proj_weight", shape=(hidden, inner),
                                   init=w_out)

    def _attention(self, cfg, hidden, w_in, w_out):
        heads, kv = (int(cfg["num_attention_heads"]),
                     int(cfg["num_key_value_heads"]))
        if heads < kv or heads % kv or hidden % heads:
            raise ValueError("%d key-value heads, %d query heads and a hidden "
                             "size of %d do not divide" % (kv, heads, hidden))
        if cfg.get("position_embedding_type", "nope") != "nope" \
                or cfg.get("attention_bias", False):
            raise ValueError("the attention layer is built without positions "
                             "and without bias (as published)")
        d = hidden // heads
        self._mixer = dict(num_heads=heads, num_kv_heads=kv, head_dim=d,
                           scale=float(cfg["attention_multiplier"]),
                           eps=self._eps)
        get = self.params.get
        self.q_weight = get("q_weight", shape=(heads * d, hidden), init=w_in)
        self.k_weight = get("k_weight", shape=(kv * d, hidden), init=w_in)
        self.v_weight = get("v_weight", shape=(kv * d, hidden), init=w_in)
        self.o_weight = get("o_weight", shape=(hidden, heads * d), init=w_out)

    def hybrid_forward(self, F, x, segment_ids, **w):
        # ``w``: this layer's parameters by name, those of its kind only
        if self.kind == "mamba":
            y = F._contrib_mamba2_mixer(x, *(w[n] for n in _MAMBA),
                                        segment_ids=segment_ids,
                                        **self._mixer)
        else:
            y = F._contrib_gqa_mixer(x, *(w[n] for n in _ATTN),
                                     segment_ids=segment_ids, **self._mixer)
        x = x + y * self._residual
        return x + F._contrib_glu_mlp_mixer(
            x, *(w[n] for n in _MLP), eps=self._eps) * self._residual


class GraniteHybridModel(HybridBlock):
    """(ids, segment_ids), both (batch, length) -> hidden states (batch,
    length, hidden) after the last norm. ``cfg`` holds ``config.json``'s
    keys: the first ``num_hidden_layers`` entries of ``layer_types``
    are built (a shorter list is refused); ``vocab_size`` is the rows
    held of the vocabulary; ``scan_chunk`` (optional) the scan's chunk.
    ``embed.weight`` is also the head's matrix
    (:class:`GraniteHybridLMLoss`)."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        hidden = int(cfg["hidden_size"])
        self._eps = float(cfg["rms_norm_eps"])
        self._embedding = float(cfg["embedding_multiplier"])
        if not cfg.get("tie_word_embeddings", True):
            raise ValueError("the head is built tied to the embedding (as "
                             "published)")
        with self.name_scope():
            self.embed = nn.Embedding(int(cfg["vocab_size"]), hidden,
                                      weight_initializer=init.Normal(0.02),
                                      prefix="embed_")
            self.layers = nn.HybridSequential(prefix="")
            for i in range(int(cfg["num_hidden_layers"])):
                self.layers.add(GraniteHybridDecoderLayer(
                    cfg, i, prefix="layers%d_" % i))
            self.norm_f_weight = self.params.get(
                "norm_f_weight", shape=(hidden,), init="ones")
            self.seq_documents = self.params.get(
                "seq_documents", shape=(1,), grad_req="null", init="zeros",
                differentiable=False)
            self.seq_documents._is_aux = True

    def hybrid_forward(self, F, ids, segment_ids, *, norm_f_weight,
                       seq_documents):
        segment_ids = F._contrib_count_documents(segment_ids, seq_documents)
        x = self.embed(ids) * self._embedding
        for layer in self.layers:
            x = layer(x, segment_ids)
        return F._contrib_rms_norm(x, norm_f_weight, eps=self._eps)


class GraniteHybridLMLoss(HybridBlock):
    """The tied, bias-free head, its logits divided by
    ``logits_scaling``, and the cross-entropy through the streaming
    chunked-CE op: (hidden states, labels) -> the mean next-token loss
    over every position, shape (1,), float32. The division is taken on
    the hidden states (``(h / s) E^T``; exact where ``s`` is a power of
    two, as the published 8 is), so the head op needs no term for it.
    ``embed`` is the model's embedding (``GraniteHybridModel.embed``,
    or the model): its weight is read here as the head's, the same
    ``Parameter`` object under the same name, never a copy; the
    gradient a step takes for it is the sum over the lookup and the
    head."""

    def __init__(self, cfg, embed, **kwargs):
        super().__init__(**kwargs)
        embed = getattr(embed, "embed", embed)
        shape = (int(cfg["vocab_size"]), int(cfg["hidden_size"]))
        if tuple(embed.weight.shape) != shape:
            raise ValueError("the embedding handed in is %s, the head %s"
                             % (tuple(embed.weight.shape), shape))
        self._scaling = float(cfg["logits_scaling"])
        # (a Parameter set as an attribute joins this block's own: the
        # same object under the name the model gave it)
        self.embed_weight = embed.weight

    def hybrid_forward(self, F, hidden, labels, **w):
        # whatever prefix the model gave the embedding's name
        (embed_weight,) = w.values()
        return F._contrib_chunked_lm_head_ce_nobias(
            hidden * (1.0 / self._scaling), embed_weight, labels).mean()


def publish_seq_documents(aux):
    """Publish the gauge ``mx_seq_documents{block}``: the documents a
    sequence held in the last step, mean over the batch, from the
    model's ``seq_documents`` auxiliary state
    (``ShardedTrainStep.aux``, or any ``{name: array}`` holding
    ``*seq_documents``). Returns ``{block: count}`` (``block``: the
    model's prefix, ``model`` where it has none). One device-to-host
    read: call it after a window, not inside one."""
    import jax
    from ... import telemetry
    names = sorted(n for n in aux if n.endswith("seq_documents"))
    values = jax.device_get([aux[n]._jax() if hasattr(aux[n], "_jax")
                             else aux[n] for n in names])
    out = {}
    for name, value in zip(names, values):
        block = name[:-len("seq_documents")].rstrip("_") or "model"
        out[block] = float(np.asarray(value, np.float64).reshape(-1)[0])
        telemetry.gauge("mx_seq_documents", block=block).set(out[block])
    return out
