"""granite_4_0_h_micro: builds granite-4.0-h-micro's Gluon blocks from
the sizes in granite_4_0_h_micro.json (each layer's mixer from the
first ``num_hidden_layers`` entries of ``layer_types``), counts the
model's FLOPs, and counts for the roofline shares what the scan's scope
executes (the Nemotron file's rule, so that the two cells' shares of
the one pair of kernels compare) and the least that the attention's
scope needs: the pairs of a query and a key inside one document. The
plain reference is ``reference/granite_4_0_h_micro.py``."""
from __future__ import annotations

import numpy as np

# the program's jax.named_scopes that mxbench/scopes.py reads device
# time by, innermost first (the scan stands inside mx.mamba2)
SCOPES = ("mx.mamba2.ssd", "mx.mamba2", "mx.attn.causal", "mx.mlp")

MAMBA, ATTENTION = "mamba", "attention"


class _HeadLoss:
    """(hidden states, labels) -> [mean next-token loss]: the adapter
    ShardedTrainStep wants around the parametric head."""

    def __init__(self, head):
        self.head = head

    def collect_params(self):
        return self.head.collect_params()

    def __call__(self, hidden, labels):
        return [self.head(hidden, labels)]


def layer_kinds(sizes):
    """The mixer kinds of the layers built: the head of the published
    ``layer_types``."""
    return list(sizes["layer_types"][:sizes["num_hidden_layers"]])


def model_cfg(sizes):
    """The file's keys as the model reads them."""
    cfg = {k: v for k, v in sizes.items()
           if isinstance(v, (int, float, str, bool))}
    cfg["layer_types"] = layer_kinds(sizes)
    return cfg


def sharded_parts(sizes, dropout, seq):
    """(net, loss, number of data inputs) for ShardedTrainStep. Data
    inputs: ids, segment ids, labels, each (batch, seq). The Gluon
    parameters are initialised on the host: ``ShardedTrainStep`` makes
    its own fp32 masters on the chip. The loss block reads the net's
    embedding: one parameter, named once."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.granite_hybrid import (
        GraniteHybridLMLoss, GraniteHybridModel)
    if dropout:
        raise ValueError("Granite 4.0-H has no dropout")
    cfg = model_cfg(sizes)
    net = GraniteHybridModel(cfg, prefix="")
    head = GraniteHybridLMLoss(cfg, net, prefix="")
    net.collect_params().setattr("grad_req", "null")
    net.initialize(ctx=mx.cpu())
    return net, _HeadLoss(head), 3


def expert_rows(aux):
    """No expert layer: {}. What the generator reads of the step's
    auxiliary states after the window is the count of documents a
    sequence held, published as the program's gauge on the way."""
    from mxnet_tpu.gluon.model_zoo.granite_hybrid import publish_seq_documents
    publish_seq_documents(aux)
    return {}


def expert_even_share(sizes, tokens):
    return 0.0


def named_weights(net, loss):
    """{name: float32 numpy array} of the net's parameters, as the
    reference reads them; the head's is the embedding's, once."""
    out = {}
    for block in (net, loss.head):
        for name, p in block.collect_params().items():
            out[name] = p.data().asnumpy().astype(np.float32)
    return out


# ---------------------------------------------------------------------------
# counts: multiply-adds a token, one forward
# ---------------------------------------------------------------------------
def head_dim(sizes):
    return sizes["hidden_size"] // sizes["num_attention_heads"]


def causal_pairs(seq):
    return seq * (seq + 1) // 2


def document_pairs(lengths):
    """The pairs of a query and a key it sees in a row of documents of
    these lengths."""
    return sum(causal_pairs(int(n)) for n in lengths)


def _ssd_macs(sizes):
    """The scan's products a token in its chunked form, at the chunk
    the program takes: C.B^T inside a chunk once a group (groups x
    chunk x state), the masked mix times x (heads x chunk x head_dim),
    each chunk's state and the read of the entering state (heads x
    head_dim x state each)."""
    h, p = sizes["mamba_n_heads"], sizes["mamba_d_head"]
    g, n, q = sizes["mamba_n_groups"], sizes["mamba_d_state"], \
        sizes["scan_chunk"]
    return g * q * n + h * q * p + 2 * h * p * n


def _mamba_macs(sizes):
    u = sizes["hidden_size"]
    inner = sizes["mamba_n_heads"] * sizes["mamba_d_head"]
    conv = inner + 2 * sizes["mamba_n_groups"] * sizes["mamba_d_state"]
    return (u * (inner + conv + sizes["mamba_n_heads"]) + inner * u
            + conv * sizes["mamba_d_conv"] + _ssd_macs(sizes))


def _attn_proj_macs(sizes):
    """q and o over the query heads, k and v over the key-value heads."""
    u, d = sizes["hidden_size"], head_dim(sizes)
    return 2 * u * u + 2 * u * sizes["num_key_value_heads"] * d


def macs_per_token(sizes, seq, pairs=None):
    """{part: multiply-adds a token of one forward}, over the layers
    built. ``pairs``: the query-key pairs inside documents a sequence
    holds (the feed's mean); one document of ``seq`` tokens where none
    is given."""
    u = sizes["hidden_size"]
    kinds = layer_kinds(sizes)
    pairs = causal_pairs(seq) if pairs is None else pairs
    return {
        "mamba": kinds.count(MAMBA) * _mamba_macs(sizes),
        "attn_proj": kinds.count(ATTENTION) * _attn_proj_macs(sizes),
        "attn_pairs": kinds.count(ATTENTION) * 2 * pairs / seq * u,
        "mlp": len(kinds) * 3 * u * sizes["shared_intermediate_size"],
        "head": u * sizes["vocab_size"],
    }


def train_flops_per_sample(sizes, seq, pairs=None):
    """Model FLOPs of one training sequence: forward + backward ~ 3x
    the forward, 2 FLOPs a multiply-add; recomputation not counted,
    attention over the pairs inside documents, the tied head once (over
    the vocabulary slice; the embedding's lookup is no product)."""
    return sum(macs_per_token(sizes, seq, pairs).values()) * 2 * 3 * seq


def scope_costs(sizes, seq, batch, pairs=None):
    """{scope: (FLOPs, bytes)} of one training step inside each scope
    that has a roofline reader, all its layers together.

    - ``mx.mamba2.ssd``: what runs, by the Nemotron file's rule: C.B^T
      (once a group: here one group of 64 heads), the mix times x, the
      chunks' states and the read of the entering state, plus the carry
      between chunks (a float32 product of chunks^2 x state a sequence,
      counted once); recomputed: C.B^T, the states and the carry; a
      backward of two products for each of the forward's. x, B, C, dt
      in and y out in bf16, once a pass, twice in the backward.
    - ``mx.attn.causal``: **the least the mathematics needs**: the
      seven products (Q K^T, P V, two each in the backward, Q K^T once
      more to rebuild the probabilities) over the pairs of a query and
      a key *inside one document* (``pairs`` a sequence, the feed's
      mean), at the published 64 lanes a head. The kernel visits every
      causal tile and masks (ops/pallas_causal_gqa.py), so what it
      spends on other documents' keys and on the empty half of a
      128-lane step reads as lost share; the share cannot pass 100%.
      Bytes: q, k, v in and the context out once in bf16 a pass, twice
      in the backward."""
    kinds = layer_kinds(sizes)
    mambas, attns = kinds.count(MAMBA), kinds.count(ATTENTION)
    tokens = seq * batch
    h, p = sizes["mamba_n_heads"], sizes["mamba_d_head"]
    g, s, q = sizes["mamba_n_groups"], sizes["mamba_d_state"], \
        sizes["scan_chunk"]
    chunks = -(-seq // q)
    carry = batch * chunks * chunks * h * p * s
    again = tokens * (g * q * s + h * p * s) + carry
    ssd_flops = mambas * 2 * (3 * (tokens * _ssd_macs(sizes) + carry) + again)
    ssd_io = tokens * (2 * h * p + 2 * g * s + h) * 2
    ssd_bytes = mambas * ssd_io * (1 + 1 + 2)

    heads, kv, d = (sizes["num_attention_heads"],
                    sizes["num_key_value_heads"], head_dim(sizes))
    pairs = causal_pairs(seq) if pairs is None else pairs
    attn_flops = attns * 2 * 7 * batch * pairs * heads * d
    attn_bytes = attns * tokens * (2 * heads + 2 * kv) * d * 2 * (1 + 2)
    return {"mx.mamba2.ssd": (ssd_flops, ssd_bytes),
            "mx.attn.causal": (attn_flops, attn_bytes)}
