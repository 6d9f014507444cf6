"""The train_stream generator's feeds and window: the same seed gives
the same batches, ids stay inside the vocabulary, the window keeps at
most ``inflight`` steps unfinished and counts every step."""
import types

import numpy as np
import pytest

from mxbench import manifest


def _ctx(traffic_name, config, seed):
    traffic, gen = manifest.traffic(traffic_name)
    sizes, cfgmod, refmod = manifest.config(config)
    traffic = dict(traffic, **traffic["toy"])
    sizes = dict(sizes, **sizes["toy"])
    return gen, types.SimpleNamespace(traffic=traffic, sizes=sizes,
                                      seed=seed, rehearse=True,
                                      say=lambda msg: None)


@pytest.mark.parametrize("seed", [0, 7, 3_000_000_019])
def test_token_pool_is_a_function_of_the_seed(seed):
    gen, ctx = _ctx("pretrain_mlm_s128", "bert_base", seed)
    a = gen.TokenPoolFeed(ctx, 4, 16)
    b = gen.TokenPoolFeed(ctx, 4, 16)
    other = gen.TokenPoolFeed(types.SimpleNamespace(
        traffic=ctx.traffic, sizes=ctx.sizes, seed=seed + 1, say=None), 4, 16)
    seen = []
    for _ in range(6):      # more than one pass over the pool of 16
        xa, xb = a.host_batch(), b.host_batch()
        for u, v in zip(xa, xb):
            np.testing.assert_array_equal(u, v)
        ids, types_, labels = xa
        assert ids.shape == types_.shape == labels.shape == (4, 16)
        assert ids.dtype == np.int32
        assert 0 <= ids.min() and ids.max() < ctx.sizes["vocab_size"]
        assert 0 <= labels.min() and labels.max() < ctx.sizes["vocab_size"]
        seen.append(ids.copy())
    assert not np.array_equal(seen[0], seen[1])         # a fresh batch
    np.testing.assert_array_equal(seen[0], seen[4])     # wraps at 16 / 4
    assert not np.array_equal(seen[0], other.host_batch()[0])


def test_raw_record_feed_round_trip():
    """What the iterator hands over is what was written: every image
    of a batch (mirrored or not) is one of the seeded records, with its
    own label, normalised to [0, 1]."""
    gen, ctx = _ctx("imagenet_raw_recordio_b128", "resnet50_v1", 11)
    feed = gen.RawRecordFeed(ctx, 8, None)
    try:
        assert feed.images.shape == (32, 64, 64, 3)
        x, y = feed.next()
        x, y = x.asnumpy(), y.asnumpy()
        assert x.shape == (8, 3, 64, 64) and y.shape == (8,)
        assert 0.0 <= x.min() and x.max() <= 1.0
        want = feed.images.astype(np.float32).transpose(0, 3, 1, 2) / 255.0
        for img, label in zip(x, y):
            hits = [i for i in range(32)
                    if np.allclose(img, want[i], atol=1e-6)
                    or np.allclose(img, want[i][:, :, ::-1], atol=1e-6)]
            assert len(hits) == 1 and feed.labels[hits[0]] == label
        for _ in range(6):          # past the end of the file: wraps
            feed.next()
    finally:
        feed.close()
    again = gen.RawRecordFeed(ctx, 8, None, check=True)
    np.testing.assert_array_equal(again.host_batch()[0].shape, (8, 3, 64, 64))


class _FakeLoop:
    def __init__(self):
        self.launched, self.waited, self.unfinished_max = 0, [], 0

    def step(self, *batch):
        self.launched += 1
        self.unfinished_max = max(self.unfinished_max,
                                  self.launched - len(self.waited))
        return self.launched

    def wait(self, loss):
        self.waited.append(loss)

    def wait_all(self, loss):
        self.final = loss


class _FakeFeed:
    def next(self):
        return (0,)


@pytest.mark.parametrize("inflight", [1, 2, 4])
def test_window_bounds_the_steps_in_flight(inflight):
    _, gen = manifest.traffic("pretrain_mlm_s128")
    loop = _FakeLoop()
    steps, losses, wall, host = gen.measure(loop, _FakeFeed(), 0.05,
                                            inflight)
    assert steps == loop.launched == len(losses) > inflight
    assert loop.waited == list(range(1, steps - inflight + 1))
    assert loop.unfinished_max == inflight + 1
    assert loop.final == steps and wall >= 0.05
    assert sum(host.values()) == pytest.approx(wall, rel=1e-6)
