"""Nemotron-H: a causal decoder whose layers are, by a pattern string,
Mamba-2 mixers (``M``), routed-expert MLPs (``E``) and grouped-query
attention (``*``), each ``x <- x + mixer(RMSNorm(x))`` (NVIDIA
Nemotron-H, ``model_type`` ``nemotron_h``; the keys below are its
``config.json``'s). No positional term, no bias except the conv's,
untied head, squared-ReLU experts behind a sigmoid top-k router.

The first decoder of the zoo. It is built from the ops of
``ops/decoder_ops.py`` (one mixer op a layer), traced by
``parallel.trace_block`` into the one program ``ShardedTrainStep``
compiles, as BERT is. Recomputation lives in the mixer ops
(docs/TRAINING.md "Decoder layers and recomputation").

Expert parallelism's share: an expert layer is told which experts of
the router's range it holds (``experts_held`` from ``expert_offset``
on). It routes over all ``n_routed_experts``, computes the shared
expert and its own experts' terms, and leaves out what the absent
experts would add. ``e_score_correction_bias`` and the per-expert row
count ``expert_rows`` are auxiliary states (never differentiated; the
bias keeps its seeded value, the count is rewritten every call).
"""
from __future__ import annotations

import math

import numpy as np

from ... import initializer as init
from ... import ndarray as nd
from .. import nn
from ..block import HybridBlock

__all__ = ["NemotronHModel", "NemotronHLMLoss", "Mamba2Layer",
           "ExpertLayer", "AttentionLayer", "publish_expert_rows"]


class _Draw(init.Initializer):
    """Fills an array with ``fn(u)``, u uniform in [0, 1) of its shape
    from the framework's seeded stream, whatever its name ends in."""

    def __init__(self, fn):
        super().__init__()
        self._fn = fn

    def _init_impl(self, _, arr):
        u = nd.random_uniform(low=0.0, high=1.0, shape=arr.shape,
                              ctx=arr.ctx).asnumpy().astype(np.float64)
        arr[:] = nd.array(self._fn(u).astype(np.float32), ctx=arr.ctx)


def _dt_bias(cfg):
    """Inverse softplus of a time step drawn log-uniform between
    ``time_step_min`` and ``time_step_max`` (Mamba-2's init)."""
    lo, hi = math.log(cfg["time_step_min"]), math.log(cfg["time_step_max"])

    def fn(u):
        dt = np.maximum(np.exp(u * (hi - lo) + lo), cfg["time_step_floor"])
        return dt + np.log(-np.expm1(-dt))
    return _Draw(fn)


class _Layer(HybridBlock):
    """What the three kinds share: the pre-norm weight and the
    residual add around one mixer op."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self._eps = float(cfg["layer_norm_epsilon"])
        self._hidden = int(cfg["hidden_size"])
        # matrices N(0, 0.02); those that write into the residual
        # stream shrunk by sqrt(layers) (rescale_prenorm_residual)
        self._in = init.Normal(0.02)
        self._out = init.Normal(0.02 / math.sqrt(len(_pattern(cfg))))
        with self.name_scope():
            self.norm_weight = self.params.get(
                "norm_weight", shape=(self._hidden,), init="ones")


class Mamba2Layer(_Layer):
    def __init__(self, cfg, **kwargs):
        super().__init__(cfg, **kwargs)
        heads, p = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
        groups, n = int(cfg["n_groups"]), int(cfg["ssm_state_size"])
        inner, conv = heads * p, heads * p + 2 * groups * n
        k = int(cfg["conv_kernel"])
        self._attrs = dict(num_heads=heads, head_dim=p, n_groups=groups,
                           state_size=n, chunk_size=int(cfg["chunk_size"]),
                           eps=self._eps)
        get = self.params.get
        with self.name_scope():
            self.in_proj_weight = get(
                "in_proj_weight", shape=(inner + conv + heads, self._hidden),
                init=self._in)
            self.conv_weight = get("conv_weight", shape=(conv, k),
                                   init=init.Uniform(1.0 / math.sqrt(k)))
            self.conv_bias = get(
                "conv_bias", shape=(conv,),
                init=_Draw(lambda u: (2 * u - 1) / math.sqrt(k)))
            self.dt_bias = get("dt_bias", shape=(heads,), init=_dt_bias(cfg))
            self.a_log = get("a_log", shape=(heads,),
                             init=_Draw(lambda u: np.log(1 + 15 * u)))
            self.d = get("d", shape=(heads,), init="ones")
            self.gate_norm_weight = get("gate_norm_weight", shape=(inner,),
                                        init="ones")
            self.out_proj_weight = get(
                "out_proj_weight", shape=(self._hidden, inner),
                init=self._out)

    def hybrid_forward(self, F, x, norm_weight, in_proj_weight, conv_weight,
                       conv_bias, dt_bias, a_log, d, gate_norm_weight,
                       out_proj_weight):
        return x + F._contrib_mamba2_mixer(
            x, norm_weight, in_proj_weight, conv_weight, conv_bias, dt_bias,
            a_log, d, gate_norm_weight, out_proj_weight, **self._attrs)


class ExpertLayer(_Layer):
    def __init__(self, cfg, **kwargs):
        super().__init__(cfg, **kwargs)
        routed = int(cfg["n_routed_experts"])
        held = int(cfg.get("experts_held", routed))
        offset = int(cfg.get("expert_offset", 0))
        if not 0 <= offset <= routed - held:
            raise ValueError("experts %d..%d are not among the router's %d"
                             % (offset, offset + held, routed))
        width = int(cfg["moe_intermediate_size"])
        shared = int(cfg["moe_shared_expert_intermediate_size"]) \
            * int(cfg["n_shared_experts"])
        self._attrs = dict(
            top_k=int(cfg["num_experts_per_tok"]), expert_offset=offset,
            routed_scaling_factor=float(cfg["routed_scaling_factor"]),
            norm_topk_prob=bool(cfg["norm_topk_prob"]), eps=self._eps)
        get = self.params.get
        with self.name_scope():
            self.router_weight = get(
                "router_weight", shape=(routed, self._hidden), init=self._in)
            self.e_score_correction_bias = get(
                "e_score_correction_bias", shape=(routed,), grad_req="null",
                init=_Draw(lambda u: 0.02 * u - 0.01), differentiable=False)
            self.expert_rows = get(
                "expert_rows", shape=(2, held), grad_req="null",
                init="zeros", differentiable=False)
            self.e_score_correction_bias._is_aux = True
            self.expert_rows._is_aux = True
            self.shared_up_weight = get(
                "shared_up_weight", shape=(shared, self._hidden),
                init=self._in)
            self.shared_down_weight = get(
                "shared_down_weight", shape=(self._hidden, shared),
                init=self._out)
            self.experts_up_weight = get(
                "experts_up_weight", shape=(held, width, self._hidden),
                init=self._in)
            self.experts_down_weight = get(
                "experts_down_weight", shape=(held, self._hidden, width),
                init=self._out)

    def hybrid_forward(self, F, x, norm_weight, router_weight,
                       e_score_correction_bias, expert_rows,
                       shared_up_weight, shared_down_weight,
                       experts_up_weight, experts_down_weight):
        return x + F._contrib_moe_mixer(
            x, norm_weight, router_weight, expert_rows, experts_up_weight,
            experts_down_weight, e_score_correction_bias, shared_up_weight,
            shared_down_weight, **self._attrs)


class AttentionLayer(_Layer):
    def __init__(self, cfg, **kwargs):
        super().__init__(cfg, **kwargs)
        heads, kv = int(cfg["num_attention_heads"]), \
            int(cfg["num_key_value_heads"])
        d = int(cfg["head_dim"])
        self._attrs = dict(num_heads=heads, num_kv_heads=kv, head_dim=d,
                           eps=self._eps)
        get = self.params.get
        with self.name_scope():
            self.q_weight = get("q_weight", shape=(heads * d, self._hidden),
                                init=self._in)
            self.k_weight = get("k_weight", shape=(kv * d, self._hidden),
                                init=self._in)
            self.v_weight = get("v_weight", shape=(kv * d, self._hidden),
                                init=self._in)
            self.o_weight = get("o_weight", shape=(self._hidden, heads * d),
                                init=self._out)

    def hybrid_forward(self, F, x, norm_weight, q_weight, k_weight, v_weight,
                       o_weight):
        return x + F._contrib_gqa_mixer(
            x, norm_weight, q_weight, k_weight, v_weight, o_weight,
            **self._attrs)


_KINDS = {"M": Mamba2Layer, "E": ExpertLayer, "*": AttentionLayer}


def _pattern(cfg):
    pattern = cfg["hybrid_override_pattern"][:int(cfg["num_hidden_layers"])]
    if len(pattern) != int(cfg["num_hidden_layers"]) \
            or set(pattern) - set(_KINDS):
        raise ValueError("hybrid_override_pattern %r does not give %s layers "
                         "of kinds %s" % (cfg["hybrid_override_pattern"],
                                          cfg["num_hidden_layers"],
                                          sorted(_KINDS)))
    return pattern


class NemotronHModel(HybridBlock):
    """ids (batch, length) -> hidden states (batch, length, hidden)
    after the final norm. ``cfg`` holds ``config.json``'s keys; the
    first ``num_hidden_layers`` characters of
    ``hybrid_override_pattern`` give the layers; ``experts_held`` and
    ``expert_offset`` (default: all, 0) give this chip's share of each
    expert layer; ``vocab_size`` is the rows held of the vocabulary."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        hidden = int(cfg["hidden_size"])
        self._eps = float(cfg["layer_norm_epsilon"])
        with self.name_scope():
            self.embed = nn.Embedding(int(cfg["vocab_size"]), hidden,
                                      weight_initializer=init.Normal(0.02),
                                      prefix="embed_")
            self.layers = nn.HybridSequential(prefix="")
            for i, kind in enumerate(_pattern(cfg)):
                self.layers.add(_KINDS[kind](cfg, prefix="layers%d_" % i))
            self.norm_f_weight = self.params.get(
                "norm_f_weight", shape=(hidden,), init="ones")

    def hybrid_forward(self, F, ids, norm_f_weight):
        x = self.embed(ids)
        for layer in self.layers:
            x = layer(x)
        return F._contrib_rms_norm(x, norm_f_weight, eps=self._eps)


class NemotronHLMLoss(HybridBlock):
    """The untied, bias-free head and the cross-entropy as one block,
    through the streaming chunked-CE op (the (positions, vocabulary)
    logits never exist whole): (hidden states, labels) -> per-position
    loss, float32. Next-token labels are the feed's to shift."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.head_weight = self.params.get(
                "head_weight", init=init.Normal(0.02),
                shape=(int(cfg["vocab_size"]), int(cfg["hidden_size"])))

    def hybrid_forward(self, F, hidden, labels, head_weight):
        return F._contrib_chunked_lm_head_ce_nobias(hidden, head_weight,
                                                    labels)


def publish_expert_rows(aux):
    """Publish the expert layers' row counts from their auxiliary
    states (``ShardedTrainStep.aux``, or any ``{name: array}`` holding
    ``*expert_rows``): gauges ``mx_moe_expert_rows{block, expert}`` (rows
    routed to each held expert in the last step), the counter
    ``mx_moe_dropped_rows_total`` (rows routed to a held expert that
    its product did not compute: the layer is dropless, so it stays 0)
    and, where the op recorded its buffer's shape as it was traced
    (``mx_moe_buffer_shape``: telemetry on, this process),
    ``mx_moe_buffer_blocks{block, state}``: the blocks of the sorted
    buffer that hold a routed row (``computed``: each expert's rows in
    whole blocks, which is what the grouped kernels multiply; more than
    ``held`` where the routing overfilled the buffer and the dense
    product ran instead) and the blocks the buffer has (``held``: what
    the composition multiplies).
    Returns ``{block: routed counts}``. One device-to-host read: call
    it after a window, not inside one."""
    import jax
    from ... import telemetry
    names = sorted(n for n in aux if n.endswith("expert_rows"))
    rows = jax.device_get([aux[n]._jax() if hasattr(aux[n], "_jax")
                           else aux[n] for n in names])
    out = {}
    for name, (routed, done) in zip(names, rows):
        block = name[:-len("_expert_rows")]
        out[block] = np.asarray(routed, np.float64)
        for e, r in enumerate(routed):
            telemetry.gauge("mx_moe_expert_rows", block=block,
                            expert=str(e)).set(float(r))
        telemetry.counter("mx_moe_dropped_rows_total").inc(
            float(np.sum(routed) - np.sum(done)))
        block_rows, blocks = (
            telemetry.gauge("mx_moe_buffer_shape", held=str(len(routed)),
                            dim=dim).get() for dim in ("block_rows", "blocks"))
        if block_rows > 0:      # (0: none recorded; -1: more than one)
            computed = float(np.sum(np.ceil(out[block] / block_rows)))
            for state, n in (("computed", computed), ("held", blocks)):
                telemetry.gauge("mx_moe_buffer_blocks", block=block,
                                state=state).set(n)
    return out
