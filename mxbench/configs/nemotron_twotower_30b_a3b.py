"""nemotron_twotower_30b_a3b: builds the causal tower's Gluon blocks
from the sizes in nemotron_twotower_30b_a3b.json, counts the model's
FLOPs, and counts what the three new kernels' scopes execute (for
their roofline shares). The plain reference is
``reference/nemotron_twotower_30b_a3b.py``."""
from __future__ import annotations

import math

import numpy as np

# the program's jax.named_scopes that mxbench/scopes.py reads device
# time by, innermost first
SCOPES = ("mx.mamba2.ssd", "mx.mamba2", "mx.moe.experts", "mx.moe",
          "mx.attn.causal")


class _HeadLoss:
    """(hidden states, labels) -> [mean next-token loss]: the adapter
    ShardedTrainStep wants around the parametric head."""

    def __init__(self, head):
        self.head = head

    def collect_params(self):
        return self.head.collect_params()

    def __call__(self, hidden, labels):
        return [self.head(hidden, labels).mean()]


def model_cfg(sizes):
    """The file's keys as the model reads them: the file's
    ``n_routed_experts`` counts the experts held here (it is under
    ``reduced``); the router's width is the published count."""
    cfg = {k: v for k, v in sizes.items()
           if isinstance(v, (int, float, str, bool))}
    cfg["experts_held"] = sizes["n_routed_experts"]
    cfg["n_routed_experts"] = sizes["deployment"]["router_experts"]
    cfg["expert_offset"] = sizes["deployment"]["expert_offset"]
    return cfg


def sharded_parts(sizes, dropout, seq):
    """(net, loss, number of data inputs) for ShardedTrainStep. Data
    inputs: ids, labels, each (batch, seq). The Gluon parameters are
    initialised on the host: ``ShardedTrainStep`` makes its own fp32
    masters on the chip, and a second copy there (2.7 GB, and as much
    again for Gluon's gradient buffers) is what the step's temporaries
    need."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.nemotron_h import (NemotronHLMLoss,
                                                      NemotronHModel)
    if dropout:
        raise ValueError("the Nemotron-H stack has no dropout")
    cfg = model_cfg(sizes)
    net = NemotronHModel(cfg, prefix="")
    head = NemotronHLMLoss(cfg, prefix="")
    for block in (net, head):
        block.collect_params().setattr("grad_req", "null")
        block.initialize(ctx=mx.cpu())
    return net, _HeadLoss(head), 2


def expert_rows(aux):
    """{expert layer: rows routed to each held expert in the last
    step} from a step's auxiliary states, published as the program's
    gauges on the way."""
    from mxnet_tpu.gluon.model_zoo.nemotron_h import publish_expert_rows
    return publish_expert_rows(aux)


def expert_even_share(sizes, tokens):
    """Rows an expert of a layer is routed on average: every token
    chooses top-k of the router's experts, whatever the routing."""
    return tokens * sizes["num_experts_per_tok"] \
        / sizes["deployment"]["router_experts"]


def named_weights(net, loss):
    """{name: float32 numpy array} of the net's and the head's
    parameters, as the reference reads them."""
    out = {}
    for block in (net, loss.head):
        for name, p in block.collect_params().items():
            out[name] = p.data().asnumpy().astype(np.float32)
    return out


# ---------------------------------------------------------------------------
# counts: multiply-adds a token, one forward
# ---------------------------------------------------------------------------
def _kinds(sizes):
    pattern = sizes["hybrid_override_pattern"][:sizes["num_hidden_layers"]]
    return {k: pattern.count(k) for k in "ME*"}


def _ssd_macs(sizes):
    """The scan's products a token in its chunked form: C.B^T inside a
    chunk (groups x chunk x state), the masked mix times x (heads x
    chunk x head_dim), each chunk's state and the read of the entering
    state (heads x head_dim x state each)."""
    h, p = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    g, n, q = sizes["n_groups"], sizes["ssm_state_size"], sizes["chunk_size"]
    return g * q * n + h * q * p + 2 * h * p * n


def _mamba_macs(sizes):
    u = sizes["hidden_size"]
    inner = sizes["mamba_num_heads"] * sizes["mamba_head_dim"]
    conv = inner + 2 * sizes["n_groups"] * sizes["ssm_state_size"]
    return (u * (inner + conv + sizes["mamba_num_heads"]) + inner * u
            + conv * sizes["conv_kernel"] + _ssd_macs(sizes))


def _expert_macs(sizes):
    return 2 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def _moe_macs(sizes):
    """Router, shared expert, and the routed rows at their expectation
    under even routing: top-k x held / routed experts a token."""
    u = sizes["hidden_size"]
    routed = sizes["deployment"]["router_experts"]
    share = sizes["num_experts_per_tok"] * sizes["n_routed_experts"] / routed
    return (u * routed + 2 * u * sizes["moe_shared_expert_intermediate_size"]
            * sizes["n_shared_experts"] + share * _expert_macs(sizes))


def _attn_macs(sizes, seq):
    """Projections, and the two products over the causal half: a token
    sees seq / 2 keys on average."""
    u, d = sizes["hidden_size"], sizes["head_dim"]
    h, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    return 2 * u * h * d + 2 * u * kv * d + 2 * (seq / 2) * h * d


def train_flops_per_sample(sizes, seq):
    """Model FLOPs of one training sequence: forward + backward ~ 3x
    the forward, 2 FLOPs a multiply-add; recomputation not counted,
    routed rows at their expectation, attention over the causal half,
    the head over the vocabulary slice."""
    n = _kinds(sizes)
    per_tok = (n["M"] * _mamba_macs(sizes) + n["E"] * _moe_macs(sizes)
               + n["*"] * _attn_macs(sizes, seq)
               + sizes["hidden_size"] * sizes["vocab_size"])
    return per_tok * 2 * 3 * seq


def expert_capacity(sizes, tokens):
    """Rows of an expert layer's one buffer, all held experts together:
    whole blocks (ops/decoder_ops.py::_moe_experts, its constants)."""
    from mxnet_tpu.ops.decoder_ops import BLOCK_ROWS, CAPACITY_FACTOR
    held, k = sizes["n_routed_experts"], sizes["num_experts_per_tok"]
    even = tokens * k / sizes["deployment"]["router_experts"]
    block = min(BLOCK_ROWS, -(-math.ceil(CAPACITY_FACTOR * even) // 8) * 8)
    most = -(-tokens * min(k, held) // block) + held
    return block * min(most, math.ceil(CAPACITY_FACTOR * even * held / block)
                       + held)


def scope_costs(sizes, seq, batch):
    """{scope: (FLOPs, bytes)} that one training step executes inside
    each new kernel's ``jax.named_scope``, all its layers together,
    counting what runs: the forward, what of it the backward
    recomputes (a product whose value no gradient needs is not
    recomputed: the last of each chain), and a backward of two
    products for each of the forward's. Checked against
    ``cost_analysis()`` of each op compiled alone for the chip
    (PERF.md section 4). Bytes are the least a pass must move: its
    inputs read and outputs written once in bf16 (weights too), twice
    that in the backward.

    - ``mx.mamba2.ssd``: C.B^T, the mix times x, the chunks' states and
      the read of the entering state, plus the carry between chunks (a
      float32 product of chunks^2 x state a sequence, counted once and
      not by its bf16 passes); recomputed: C.B^T, the states and the
      carry. x, B, C, dt in and y out.
    - ``mx.moe.experts``: the two batched products over the buffer's
      blocks, whole (a block is computed whatever the routing);
      recomputed: the first product. The experts' weights and the
      buffer's rows.
    - ``mx.attn.causal``: Q K^T and P V over each query block's prefix
      of keys, the diagonal block whole; recomputed: Q K^T. q, k, v in
      and the context out."""
    from mxnet_tpu.ops.decoder_ops import QUERY_BLOCK
    n = _kinds(sizes)
    tokens = seq * batch
    h, p = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    g, s, q = sizes["n_groups"], sizes["ssm_state_size"], sizes["chunk_size"]
    chunks = -(-seq // q)
    carry = batch * chunks * chunks * h * p * s
    again = tokens * (g * q * s + h * p * s) + carry
    ssd_flops = n["M"] * 2 * (3 * (tokens * _ssd_macs(sizes) + carry) + again)
    ssd_io = tokens * (2 * h * p + 2 * g * s + h) * 2
    ssd_bytes = n["M"] * ssd_io * (1 + 1 + 2)

    held = sizes["n_routed_experts"]
    rows = expert_capacity(sizes, tokens)
    moe_flops = n["E"] * 7 * rows * _expert_macs(sizes)
    weights = held * _expert_macs(sizes) * 2
    buf = rows * sizes["hidden_size"] * 2 * 2
    moe_bytes = n["E"] * ((1 + 1 + 2) * (weights + buf) + weights)

    hq, kv, d = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    pairs = sum((min(lo + QUERY_BLOCK, seq) - lo) * min(lo + QUERY_BLOCK, seq)
                for lo in range(0, seq, QUERY_BLOCK))
    attn_flops = n["*"] * 2 * 7 * batch * pairs * hq * d
    attn_io = tokens * (2 * hq * d + 2 * kv * d) * 2
    attn_bytes = n["*"] * attn_io * (1 + 1 + 2)
    return {"mx.mamba2.ssd": (ssd_flops, ssd_bytes),
            "mx.moe.experts": (moe_flops, moe_bytes),
            "mx.attn.causal": (attn_flops, attn_bytes)}
