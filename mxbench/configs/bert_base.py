"""bert_base: builds the Gluon blocks from the sizes in bert_base.json
and counts the model's FLOPs. The plain reference is
``reference/bert_base.py``."""
from __future__ import annotations

import numpy as np


class _HeadLoss:
    """(model outputs, labels) -> [mean MLM loss]: the adapter
    ShardedTrainStep wants around the parametric head (as
    tools/bert_bench.py's Wrapper)."""

    def __init__(self, head):
        self.head = head

    def collect_params(self):
        return self.head.collect_params()

    def __call__(self, outputs, labels):
        seq = outputs[0] if isinstance(outputs, (list, tuple)) else outputs
        return [self.head(seq, labels).mean()]


def sharded_parts(sizes, dropout, seq):
    """(net, loss, number of data inputs) for ShardedTrainStep, both
    initialised with their shapes resolved. Data inputs: ids, token
    types, labels, each (batch, seq)."""
    from mxnet_tpu import nd
    from mxnet_tpu.gluon.model_zoo.bert import BERTMLMLoss, BERTModel
    net = BERTModel(num_layers=sizes["num_hidden_layers"],
                    units=sizes["hidden_size"],
                    hidden_size=sizes["intermediate_size"],
                    num_heads=sizes["num_attention_heads"],
                    max_length=sizes["max_position_embeddings"],
                    vocab_size=sizes["vocab_size"],
                    token_type_vocab_size=sizes["type_vocab_size"],
                    dropout=dropout, use_pooler=False,
                    use_classifier=False, use_decoder=False)
    net.initialize()
    net(nd.zeros((2, seq), dtype="int32"), nd.zeros((2, seq), dtype="int32"))
    head = BERTMLMLoss(vocab_size=sizes["vocab_size"],
                       units=sizes["hidden_size"], prefix="decoder_")
    head.initialize()
    return net, _HeadLoss(head), 3


def named_weights(net, loss):
    """{name without the block's own prefix: float32 numpy array} of
    the net's and the head's parameters, as the reference reads them."""
    out = {}
    for block in (net, loss.head):
        cut = len(block.prefix) if block is net else 0
        for name, p in block.collect_params().items():
            out[name[cut:]] = p.data().asnumpy().astype(np.float32)
    return out


def train_flops_per_sample(sizes, seq):
    """Model FLOPs of one training sample (forward + backward ~ 3x the
    forward; recomputation not counted): per token, per layer the QKV
    and output projections (4 U^2), the FFN (2 U F) and attention's two
    L x L products (2 L U); once the head's transform (U^2) and
    vocabulary projection (U V); 2 FLOPs a multiply-add. Copied from
    tools/bert_bench.py."""
    u, f = sizes["hidden_size"], sizes["intermediate_size"]
    per_tok = (sizes["num_hidden_layers"] * (4 * u * u + 2 * u * f
                                             + 2 * seq * u)
               + u * sizes["vocab_size"] + u * u) * 2 * 3
    return per_tok * seq
