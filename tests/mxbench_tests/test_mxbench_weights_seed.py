"""``weights_seed``, the optional key of a ``train_lm_stream`` mix: where
a mix names it, the model's initialisation comes from it and ``--seed``
draws the data alone; where a mix lacks it, weights and data are what
``--seed`` gave before the key existed. In both cases the check's
reference and the instance that is timed start from the same weights.
Toy sizes, on the CPU. (That every cell still rehearses is
``test_mxbench_rehearse.py``'s, a case a cell.)"""
import numpy as np
import pytest

from mxbench import manifest, run as mxrun

LM_CELLS = [c for c in manifest.workload_names()
            if manifest.traffic(manifest.workload(c)["traffic"])[0]["kind"]
            == "train_lm_stream"]
# the cells in which pinning the weights narrows the runs' spread over
# seeds (PERF.md section 6, PR 58); in the Keye-VL cell, whose step's time
# follows the routing as well, it does not, and the mix has no key
PINNED = {"mellum2_12b_a2_5b_longctx_s16384",
          "glm_4_7_flash_30b_a3b_midtrain_s8192"}
SEEDS = (5, 3_000_000_019)


def _ctx(cell, seed):
    ctx, gen, _ = mxrun.context(cell, seed=seed, seconds=0.0, trace=False,
                                rehearse=True)
    return ctx, gen


def _weights(ctx, seed_it):
    seed_it()
    net, loss, _ = ctx.cfgmod.sharded_parts(ctx.sizes, 0.0,
                                            ctx.traffic["seq"])
    return ctx.cfgmod.named_weights(net, loss)


def _same(a, b):
    return set(a) == set(b) and all(np.array_equal(a[k], b[k]) for k in a)


def _draws(cell):
    """What ``--seed`` draws: with the key the pool and its order and
    nothing of the model; without it what the parent drew, weights and
    pool, for that seed."""
    import mxnet_tpu as mx
    pinned = "weights_seed" in manifest.traffic(
        manifest.workload(cell)["traffic"])[0]
    assert pinned == (cell in PINNED)
    got = {}
    for seed in SEEDS:
        ctx, gen = _ctx(cell, seed)
        batch, seq = ctx.traffic["batch_per_chip"], ctx.traffic["seq"]
        feed = gen.TokenRowsFeed(ctx, batch, seq)
        got[seed] = (_weights(ctx, lambda: gen.seed_weights(ctx)), feed)
        # the pool is the parent's for that seed, key or no key
        rng = np.random.default_rng(seed)
        pool = max(ctx.traffic["feed"]["pool_sequences"], batch)
        rows = rng.integers(0, ctx.sizes["vocab_size"], (pool, seq + 1),
                            dtype=np.int32)
        assert np.array_equal(feed.rows, rows)
        assert np.array_equal(feed.order, rng.permutation(pool))
        named = ctx.traffic["weights_seed"] if pinned else seed % 2 ** 31
        assert _same(got[seed][0],
                     _weights(ctx, lambda: mx.random.seed(named)))
    (wa, fa), (wb, fb) = (got[s] for s in SEEDS)
    assert not np.array_equal(fa.rows, fb.rows)
    assert _same(wa, wb) == pinned
    assert any(np.ptp(v) > 0 for v in wa.values())      # not all constants


def _check(cell):
    """The check's reference and the instance that goes on into the
    window start from the same weights, under the mix as it is and with
    the key taken out or put in."""
    for flipped in (False, True):
        ctx, gen = _ctx(cell, SEEDS[-1])
        ctx.traffic = dict(ctx.traffic)
        if flipped and ctx.traffic.pop("weights_seed", None) is None:
            ctx.traffic["weights_seed"] = 2
        said = []
        ctx.say = said.append
        _, ok = gen.checked_loop(ctx, ctx.traffic["batch_per_chip"],
                                 ctx.traffic["seq"])
        assert ok
        assert "check: instance built from the same weights: True" in said


CASES = [("draws", c) for c in LM_CELLS] + [
    ("check", "mellum2_12b_a2_5b_longctx_s16384"),
    ("check", "nemotron_twotower_30b_a3b_pretrain_s8192")]


@pytest.mark.parametrize("what, cell", CASES,
                         ids=["%s-%s" % c for c in CASES])
def test_weights_seed(what, cell):
    assert PINNED <= set(LM_CELLS)
    {"draws": _draws, "check": _check}[what](cell)
