"""Generator of kind ``train_lm_packed``: ``train_lm_stream``'s job (a
causal language model under ``parallel.ShardedTrainStep`` whose state
fills the chip, checked as the one instance that is then timed) on
*packed* rows: documents of varying length concatenated with no
padding, three arrays a batch, ``ids``, ``segment_ids`` and ``labels``.
Everything but the feed is ``train_lm_stream``'s own, loaded from its
file (as that file loads ``train_stream``'s): this file's copy of that
module is handed :class:`PackedRowsFeed` in ``TokenRowsFeed``'s place,
so its ``run``, ``checked_loop``, ``reference_first``, ``control`` and
``agree`` draw, check and time packed batches (a model's
``sharded_parts`` says that it takes three inputs; the reference's
``train_losses`` is handed the three arrays).

What this file adds:

- the feed. Document lengths are log-normal (``documents``: ``median``,
  ``sigma``, clipped to ``min`` .. ``max``), drawn from ``--seed``; the
  documents are laid end to end in drawn order and the stream is cut
  into rows of ``seq + 1`` tokens, so a document that a row's end cuts
  starts the next row as a new document. A token's ``segment_id`` is
  its document's index in its row (0, 1, ..: non-decreasing); ids are
  uniform over the vocabulary slice; ``ids = row[:-1]``, ``labels =
  row[1:]`` (no loss mask: a document's last token predicts the next
  one's first), ``segment_ids`` those of ``row[:-1]``;
- a second control beside ``control``'s bf16 masters:
  :func:`control_no_reset`, the reference told to take every row as one
  document, through the same comparison;
- after the run, the model's counts for the roofline shares from the
  pairs of a query and a key inside documents that the pool's rows hold
  on average (``Run.document_pairs``; ``configs/<name>.py`` takes them
  as ``pairs``).

Reads from its traffic file what ``train_lm_stream`` reads, and
``documents``.
"""
from __future__ import annotations

import numpy as np

from mxbench import manifest

_lm = manifest.load_module("traffic", "train_lm_stream.py")
UNITS = _lm.UNITS
checked_loop, reference_first = _lm.checked_loop, _lm.reference_first
control, agree = _lm.control, _lm.agree


def document_lengths(rng, spec, tokens):
    """Lengths drawn until they cover ``tokens``: log-normal with the
    mix's median and sigma, rounded, clipped to its min .. max."""
    mean = float(np.exp(np.log(spec["median"]) + spec["sigma"] ** 2 / 2))
    out, have = [], 0
    while have < tokens:
        n = max(int(2 * (tokens - have) / mean), 16)
        drawn = np.clip(np.rint(rng.lognormal(np.log(spec["median"]),
                                              spec["sigma"], n)),
                        spec["min"], spec["max"]).astype(np.int64)
        out.append(drawn)
        have += int(drawn.sum())
    return np.concatenate(out)


def pack(lengths, rows, width):
    """(rows, width) int32 segment ids of the stream of documents of
    ``lengths`` cut into rows: a token's id is its document's index in
    its row, a document cut by a row's end starting the next row as
    document 0."""
    ends = np.cumsum(lengths)
    tokens = np.arange(rows * width)
    doc = np.searchsorted(ends, tokens, side="right").reshape(rows, width)
    return (doc - doc[:, :1]).astype(np.int32)


class PackedRowsFeed:
    """Integer ids, their documents' ids and next-token labels drawn
    batch by batch from a host pool of packed rows of ``seq + 1`` tokens
    (a seeded order over the pool, wrapping), moved with ``nd.array`` at
    each step."""

    def __init__(self, ctx, batch, seq, check=False):
        rng = np.random.default_rng(ctx.seed)
        pool = batch if check else \
            max(int(ctx.traffic["feed"]["pool_sequences"]), batch)
        spec = dict(ctx.traffic["documents"])
        spec["max"] = min(int(spec["max"]), seq)
        self.rows = rng.integers(0, ctx.sizes["vocab_size"], (pool, seq + 1),
                                 dtype=np.int32)
        self.segments = pack(document_lengths(rng, spec, pool * (seq + 1)),
                             pool, seq + 1)
        self.order = rng.permutation(pool)
        self.batch, self.at = batch, 0

    def host_batch(self):
        if self.at + self.batch > len(self.order):
            self.at = 0
        pick = self.order[self.at:self.at + self.batch]
        self.at += self.batch
        rows = self.rows[pick]
        return (np.ascontiguousarray(rows[:, :-1]),
                np.ascontiguousarray(self.segments[pick][:, :-1]),
                np.ascontiguousarray(rows[:, 1:]))

    def next(self):
        from mxnet_tpu import nd
        return tuple(nd.array(a, dtype="int32") for a in self.host_batch())

    def documents_a_row(self):
        """Mean number of documents in a row's ``seq`` input tokens."""
        return float(np.mean(self.segments[:, -2] + 1))

    def pairs_a_row(self):
        """Mean number of (query, key) pairs inside documents in a
        row's ``seq`` input tokens: sum over its documents of n (n + 1)
        / 2."""
        seg = self.segments[:, :-1]
        total = 0
        for row in seg:
            n = np.bincount(row).astype(np.int64)
            total += int(np.sum(n * (n + 1) // 2))
        return total / len(seg)

    def close(self):
        pass


_lm.TokenRowsFeed = PackedRowsFeed


def control_no_reset(ctx, batch, seq):
    """The check held against its second control: the reference with no
    document reset (every row taken as one document: taps, state and
    attention cross every boundary) in the system's place. (ok, the two
    readings); whether ``ok`` comes out False on uniform random tokens
    is what the configuration's ``check.why`` records."""
    steps = int(ctx.sizes["check"]["steps"])
    weights, host, (want,) = reference_first(ctx, batch, seq)
    crossed = ctx.refmod.train_losses(weights, host, ctx.sizes,
                                      ctx.traffic["optimizer"], steps,
                                      reset=False)
    ok, first, drop = agree(crossed, want, ctx.sizes["check"])
    ctx.say("control: no document reset %s, reference %s; first loss off by "
            "%.3g, change off by %.3g -> %s"
            % (crossed, want, first, drop, "ok" if ok else "WRONG"))
    return ok, first, drop


def run(ctx):
    out = _lm.run(ctx)
    tr = ctx.traffic
    batch, seq = int(tr["batch_per_chip"]) * len(ctx.devices), int(tr["seq"])
    feed = PackedRowsFeed(ctx, batch, seq)
    out.document_pairs = feed.pairs_a_row()
    ctx.say("the pool's rows hold %.2f documents and %.0f in-document pairs "
            "a sequence (one document: %d)"
            % (feed.documents_a_row(), out.document_pairs,
               seq * (seq + 1) // 2))
    out.flops_per_sample = ctx.cfgmod.train_flops_per_sample(
        ctx.sizes, seq, out.document_pairs)
    out.scope_costs = ctx.cfgmod.scope_costs(ctx.sizes, seq, batch,
                                             out.document_pairs)
    return out
