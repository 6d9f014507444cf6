"""Elastic topology (ISSUE 16; parallel/reshard.py, elastic.py,
docs/ELASTIC.md): portable redistribution primitives (fragment plans,
staged blocks, general NamedSharding->NamedSharding moves), topology-
free checkpoints (manifest v2 sharding section + optimizer-state
sidecar), Trainer.reshard_to live shrink/grow across the
8->4->2->8 matrix for replicated / ZeRO / ZeRO+dcn / quantized-EF
state, the Estimator's preemption poll (slice_preempt -> live reshard,
reshard_fail -> checkpoint-restore degradation) and the shardcheck-
clean transition-program contract. Tier-1 (8-device CPU mesh)."""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import (compilewatch, elastic, faultinject, gluon,
                       model as model_mod, staticcheck, telemetry)
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon import zero as zero_mod
from mxnet_tpu.gluon.contrib.estimator import Estimator
from mxnet_tpu.parallel import reshard as rs
from mxnet_tpu.staticcheck import spmd_rules

pytestmark = pytest.mark.elastic


def _ctxs(n):
    import jax
    if jax.device_count() < n:
        pytest.skip("needs %d devices" % n)
    return [mx.tpu(i) for i in range(n)]


def _devs(n):
    import jax
    if jax.device_count() < n:
        pytest.skip("needs %d devices" % n)
    return jax.devices()[:n]


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    for var in ("MXNET_ZERO", "MXNET_ZERO_DCN", "MXNET_ZERO_MIN_SIZE",
                "MXNET_KVSTORE_QUANTIZE", "MXNET_ELASTIC",
                "MXNET_ELASTIC_POLL", "MXNET_ELASTIC_BLOCK",
                "MXNET_ELASTIC_MIN_DEVICES", "MXNET_ELASTIC_SIGTERM"):
        monkeypatch.delenv(var, raising=False)
    faultinject.reset()
    elastic.clear()
    telemetry.refresh()
    yield
    faultinject.reset()
    elastic.clear()
    telemetry.refresh()
    telemetry.reset()


# ===========================================================================
# host-side plan primitives
# ===========================================================================
def _host_shards(data, lay):
    """Canonical flat array -> per-device shard buffers (numpy)."""
    shards = [np.zeros(lay.offset + lay.frag, data.dtype)
              for _ in range(lay.n)]
    for p in range(lay.n):
        lo, hi = lay.data_extent(lay.owner[p])
        if hi > lo:
            shards[p][lay.offset:lay.offset + (hi - lo)] = data[lo:hi]
    return shards


def _apply_moves(moves, src_shards, n_dst, shard_len, dtype):
    dst = [np.zeros(shard_len, dtype) for _ in range(n_dst)]
    for m in moves:
        dst[m.dst_pos][m.dst_lo:m.dst_lo + m.elems] = \
            src_shards[m.src_pos][m.src_lo:m.src_hi]
    return dst


class TestPlanPrimitives:
    def test_owner_permutation(self):
        assert rs.owner_permutation(8) == tuple(range(8))
        perm = rs.owner_permutation(8, 2)
        assert sorted(perm) == list(range(8))
        # 2004.13336 dcn x ici map: position p -> (p % ici) * dcn + p // ici
        assert perm == tuple((p % 4) * 2 + p // 4 for p in range(8))
        with pytest.raises(rs.ReshardError):
            rs.owner_permutation(8, 3)

    def test_data_extent_tiny(self):
        # size SMALLER than the replica count: frag=1, fragments past
        # the data are pure padding (the satellite-2 regression shape)
        lay = rs.FragLayout.build(3, 8)
        assert lay.frag == 1
        assert [lay.data_extent(r) for r in range(8)] == \
            [(0, 1), (1, 2), (2, 3)] + [(r, r) for r in range(3, 8)]
        one = rs.FragLayout.build(1, 8)
        assert one.data_extent(0) == (0, 1)
        assert all(one.data_extent(r)[1] <= one.data_extent(r)[0]
                   for r in range(1, 8))

    @pytest.mark.parametrize("size", [1, 3, 7, 8, 130])
    @pytest.mark.parametrize("src_n,src_dcn,dst_n,dst_dcn", [
        (8, 0, 4, 0), (8, 2, 4, 0), (4, 0, 2, 0), (2, 0, 8, 4),
        (8, 2, 8, 4), (8, 0, 8, 0),
    ])
    def test_plan_moves_exact(self, size, src_n, src_dcn, dst_n,
                              dst_dcn):
        data = np.arange(1, size + 1, dtype=np.float32)
        src = rs.FragLayout.build(size, src_n, src_dcn)
        dst = rs.FragLayout.build(size, dst_n, dst_dcn)
        moves = rs.plan_moves(src, dst)
        got = _apply_moves(moves, _host_shards(data, src), dst_n,
                           dst.frag, data.dtype)
        want = _host_shards(data, dst)
        for p in range(dst_n):
            np.testing.assert_array_equal(got[p], want[p])
        # padding never moves: total moved elements == real data size
        assert sum(m.elems for m in moves) == size

    def test_plan_moves_size_mismatch(self):
        with pytest.raises(rs.ReshardError):
            rs.plan_moves(rs.FragLayout.build(8, 4),
                          rs.FragLayout.build(9, 4))

    def test_stage_blocks_bound(self):
        src = rs.FragLayout.build(1000, 2)
        dst = rs.FragLayout.build(1000, 8)
        moves = rs.plan_moves(src, dst)
        blocks = rs.stage_blocks(moves, 64)
        # every staged block keeps <= block_elems in flight, including
        # fragments far larger than the block (they get split)
        assert all(sum(m.elems for m in b) <= 64 for b in blocks)
        flat = [m for b in blocks for m in b]
        got = _apply_moves(flat, _host_shards(
            np.arange(1000, dtype=np.float32), src), 8, dst.frag,
            np.float32)
        want = _host_shards(np.arange(1000, dtype=np.float32), dst)
        for p in range(8):
            np.testing.assert_array_equal(got[p], want[p])

    def test_peak_live_bound(self):
        assert rs.peak_live_bytes(100, 16) == 116
        assert rs.block_bytes() == 4 << 20    # default


# ===========================================================================
# device execution: fragment path (the ZeRO state space)
# ===========================================================================
class TestFragmentDevice:
    def _pack(self, sizes, n, n_dcn=0):
        lays, off = [], 0
        for s in sizes:
            lay = rs.FragLayout.build(s, n, n_dcn, offset=off)
            lays.append(lay)
            off += lay.frag
        return lays, off

    @pytest.mark.parametrize("n_dcn", [0, 2])
    def test_chain_8_4_2_8(self, n_dcn):
        """8 -> 4 -> 2 -> 8(dcn) round trip of a packed group buffer
        with tiny + non-dividing params; bitwise at every hop."""
        devs = _devs(8)
        sizes = [1, 3, 7, 130]
        arrs = [np.random.rand(s).astype(np.float32) for s in sizes]
        lays, C = self._pack(sizes, 8, n_dcn)
        bufs = rs.place_from_host(list(zip(arrs, lays)), 8, C, devs,
                                  np.float32)
        for back in rs.gather_to_host(bufs, lays):
            pass
        chain = [(4, 0, devs[:4]), (2, 0, devs[:2]), (8, 4, devs)]
        cur_bufs, cur_lays, cur_n = bufs, lays, 8
        for (n2, dcn2, devs2) in chain:
            lays2, C2 = self._pack(sizes, n2, dcn2)
            moves = []
            for a, b in zip(cur_lays, lays2):
                moves.extend(rs.plan_moves(a, b))
            cur_bufs = rs.reshard_fragments(cur_bufs, moves, n2, C2,
                                            devs2)
            cur_lays, cur_n = lays2, n2
            got = rs.gather_to_host(cur_bufs, cur_lays)
            for a, g in zip(arrs, got):
                np.testing.assert_array_equal(a, g)

    def test_staged_blocks_exact(self):
        """A tiny block size forces many staged blocks; result stays
        bitwise exact and the planned-peak gauge records the
        2112.01075 bound (dst shard + one block)."""
        devs = _devs(4)
        data = np.random.rand(1000).astype(np.float32)
        src = rs.FragLayout.build(1000, 4)
        dst = rs.FragLayout.build(1000, 2)
        bufs = rs.place_from_host([(data, src)], 4, src.frag, devs,
                                  np.float32)
        out = rs.reshard_fragments(bufs, rs.plan_moves(src, dst), 2,
                                   dst.frag, devs[:2], blk_bytes=64,
                                   label="blocktest")
        np.testing.assert_array_equal(
            rs.gather_to_host(out, [dst])[0], data)
        g = telemetry.gauge("mx_reshard_planned_peak_bytes",
                            kind="blocktest")
        assert g.get() == rs.peak_live_bytes(dst.frag * 4, 64)

    def test_reshard_fail_site(self):
        devs = _devs(2)
        data = np.arange(8, dtype=np.float32)
        lay = rs.FragLayout.build(8, 2)
        bufs = rs.place_from_host([(data, lay)], 2, lay.frag, devs,
                                  np.float32)
        faultinject.set_fault("reshard_fail", 1.0, max_fires=1)
        with pytest.raises(rs.ReshardError):
            rs.reshard_fragments(bufs, rs.plan_moves(lay, lay), 2,
                                 lay.frag, devs)
        assert faultinject.fires("reshard_fail") == 1

    def test_place_size_mismatch(self):
        devs = _devs(2)
        with pytest.raises(rs.ReshardError):
            rs.place_from_host(
                [(np.zeros(5, np.float32), rs.FragLayout.build(6, 2))],
                2, 3, devs, np.float32)

    def test_overlapping_moves_rejected(self):
        devs = _devs(2)
        bufs = rs.place_from_host(
            [(np.arange(8, dtype=np.float32), rs.FragLayout.build(8, 2))],
            2, 4, devs, np.float32)
        bad = [rs.Move(0, 0, 4, 0, 0), rs.Move(1, 0, 4, 0, 2)]
        with pytest.raises(rs.ReshardError):
            rs.reshard_fragments(bufs, bad, 2, 4, devs)

    def test_transition_integrity_exact_past_float24(self):
        """Odd shard_len > 2^24: a float32 element-count psum cannot
        represent the total exactly, so the old check raised
        ReshardError on every transition at this scale; the int32
        shard-count psum must stay exact."""
        import jax
        import jax.numpy as jnp
        devs = _devs(2)
        shard_len = (1 << 24) + 1
        bufs = [jax.device_put(jnp.zeros(shard_len, jnp.float32), d)
                for d in devs]
        out = rs._run_flat_transition(bufs, 2, shard_len, np.float32,
                                      tuple(devs), "bigshard")
        assert len(out) == 2
        assert all(int(b.shape[0]) == shard_len for b in out)


# ===========================================================================
# device execution: general NamedSharding redistribution
# ===========================================================================
def _mesh(devs, names=("dp",), shape=None):
    from mxnet_tpu.kvstore import device_mesh
    return device_mesh(tuple(devs), names, shape=shape) \
        if shape else device_mesh(tuple(devs), names)


def _put(arr, mesh, spec):
    import jax
    from jax.sharding import NamedSharding
    return jax.device_put(arr, NamedSharding(mesh, spec))


class TestRedistribute:
    def test_matrix_8_4_2_8(self):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        devs = _devs(8)
        x_np = np.random.rand(16, 6).astype(np.float32)
        x = _put(x_np, _mesh(devs), P("dp"))
        for n in (4, 2, 8):
            dst = NamedSharding(_mesh(devs[:n]), P("dp"))
            x = rs.redistribute(x, dst)
            assert x.sharding.is_equivalent_to(dst, x.ndim)
            np.testing.assert_array_equal(np.asarray(jax.device_get(x)),
                                          x_np)

    def test_replicated_and_2d(self):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        devs = _devs(8)
        x_np = np.random.rand(8, 8).astype(np.float32)
        mesh2d = _mesh(devs, ("a", "b"), shape=(4, 2))
        # sharded 2-axis -> replicated on a SMALLER device set -> back
        x = _put(x_np, mesh2d, P("a", "b"))
        rep = rs.redistribute(
            x, NamedSharding(_mesh(devs[:2]), P(None)))
        np.testing.assert_array_equal(np.asarray(jax.device_get(rep)),
                                      x_np)
        back = rs.redistribute(rep, NamedSharding(mesh2d, P("a", "b")))
        np.testing.assert_array_equal(np.asarray(jax.device_get(back)),
                                      x_np)

    def test_blocked_staging(self):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        devs = _devs(8)
        x_np = np.random.rand(64, 5).astype(np.float32)
        x = _put(x_np, _mesh(devs), P("dp"))
        out = rs.redistribute(
            x, NamedSharding(_mesh(devs[:2]), P("dp")), blk_bytes=128)
        np.testing.assert_array_equal(np.asarray(jax.device_get(out)),
                                      x_np)

    def test_tree(self):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        devs = _devs(4)
        tree = {"w": np.random.rand(8, 3).astype(np.float32),
                "b": np.random.rand(4).astype(np.float32)}
        src = NamedSharding(_mesh(devs), P())
        placed = {k: jax.device_put(v, src) for k, v in tree.items()}
        dst = NamedSharding(_mesh(devs[:2]), P())
        out = rs.redistribute_tree(placed, dst)
        for k in tree:
            np.testing.assert_array_equal(
                np.asarray(jax.device_get(out[k])), tree[k])

    def test_unequal_intersection_widths_blocked(self):
        """A destination shard intersecting source pieces of UNEQUAL
        widths (12 cols cut 4-ways at the source, 3-ways at the
        destination: a dst shard sees a width-3 and a width-1
        intersection) under a small block: the staged split must chunk
        every intersection on ONE common row grid — a per-box step
        used to skew piece boundaries and fail assembly on valid
        input."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        devs = _devs(4)
        x_np = np.random.rand(8, 12).astype(np.float32)
        x = _put(x_np, _mesh(devs), P(None, "dp"))
        out = rs.redistribute(
            x, NamedSharding(_mesh(devs[:3]), P(None, "dp")),
            blk_bytes=32)       # 8 elems/block: per-box steps diverge
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(out)), x_np)

    def test_redistribute_fail_site(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        devs = _devs(2)
        x = _put(np.zeros((4, 2), np.float32), _mesh(devs), P("dp"))
        faultinject.set_fault("reshard_fail", 1.0, max_fires=1)
        with pytest.raises(rs.ReshardError):
            rs.redistribute(x, NamedSharding(_mesh(devs[:1]), P()))


# ===========================================================================
# trainer-level reshard + checkpoint sidecar
# ===========================================================================
def _setup(seed, ctxs, opt_kw=None):
    mx.random.seed(seed)
    np.random.seed(seed)
    net = nn.Dense(3)
    net.initialize(mx.initializer.Xavier(), ctx=list(ctxs))
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       opt_kw or {"learning_rate": 0.05,
                                  "momentum": 0.9})
    est = Estimator(net, gluon.loss.L2Loss(),
                    train_metrics=[mx.metric.MSE()], trainer=tr,
                    context=list(ctxs))
    return net, tr, est


def _loader(n=32):
    rng = np.random.RandomState(0)
    X = rng.randn(n, 4).astype(np.float32)
    Y = (X @ rng.randn(4, 3)).astype(np.float32)
    return gluon.data.DataLoader(gluon.data.ArrayDataset(X, Y),
                                 batch_size=8)


def _params(net):
    return {k: p.data().asnumpy()
            for k, p in net._structural_params().items()}


_VARIANTS = {
    "replicated": {},
    "zero": {"MXNET_ZERO": "1"},
    "zero_dcn": {"MXNET_ZERO": "1", "MXNET_ZERO_DCN": "2"},
    "quant_ef": {"MXNET_ZERO": "1", "MXNET_KVSTORE_QUANTIZE": "int8"},
}


class TestTrainerReshard:
    @pytest.mark.parametrize("variant", sorted(_VARIANTS))
    def test_chain_8_4_2_8_bitparity(self, variant, monkeypatch):
        """Trainer.reshard_to across the full topology matrix: params
        AND the canonical optimizer-state blob (incl. ZeRO fragments,
        dcn permutations, quantization EF residuals) are bitwise
        unchanged at every hop, and training still steps at the end."""
        for k, v in _VARIANTS[variant].items():
            monkeypatch.setenv(k, v)
        ctxs = _ctxs(8)
        net, tr, est = _setup(13, ctxs)
        est.fit(_loader(), epochs=1)
        if variant != "replicated":
            assert isinstance(tr._zero, zero_mod.ZeroEngine), tr._zero
        p0, blob0 = _params(net), tr.states_blob()
        for n in (4, 2, 8):
            tr.reshard_to(ctxs[:n])
            assert len(tr._contexts) == n
            if variant != "replicated":
                assert isinstance(tr._zero, zero_mod.ZeroEngine)
                assert tr._zero._n == n
            got = _params(net)
            for k in p0:
                assert (got[k] == p0[k]).all(), \
                    "%s params changed at n=%d" % (k, n)
            assert tr.states_blob() == blob0, \
                "state blob changed at n=%d" % n
        est.context = list(tr._contexts)
        est.fit(_loader(), epochs=1)
        for k, v in _params(net).items():
            assert np.isfinite(v).all(), k

    def test_continuation_parity(self):
        """Loss-curve continuation: finishing a run after a live
        8->4 reshard is bitwise identical to a control run handed the
        same snapshot on the survivor topology directly."""
        ctxs = _ctxs(8)
        net1, tr1, est1 = _setup(17, ctxs)
        est1.fit(_loader(), epochs=1)
        p0, blob0 = _params(net1), tr1.states_blob()
        tr1.reshard_to(ctxs[:4])
        est1.context = ctxs[:4]
        est1.fit(_loader(), epochs=2)
        net2, _tr2, est2 = _setup(99, ctxs[:4])   # different init seed
        est2._restore_arg_params(p0)
        est2.trainer.load_states_blob(blob0)
        est2.fit(_loader(), epochs=2)
        got1, got2 = _params(net1), _params(net2)
        for k in got1:
            assert (got1[k] == got2[k]).all(), k

    def test_zero_reshard_from_plan_validation(self, monkeypatch):
        """Engine-to-engine moves refuse mismatched state spaces."""
        monkeypatch.setenv("MXNET_ZERO", "1")
        ctxs = _ctxs(8)
        net, tr, est = _setup(23, ctxs)
        est.fit(_loader(), epochs=1)
        old = tr._zero
        assert isinstance(old, zero_mod.ZeroEngine)
        tr.reshard_to(ctxs[:4])
        new = tr._zero
        old_n = old._nstates
        try:
            old._nstates = old_n + 1
            with pytest.raises(MXNetError):
                new.reshard_from(old)
        finally:
            old._nstates = old_n


class TestCheckpointTopologyFree:
    @pytest.mark.parametrize("variant", ["replicated", "zero"])
    def test_resume_other_topology(self, variant, tmp_path,
                                   monkeypatch):
        """An 8-device checkpoint resumes on 4 (and a 4-device one on
        8): params bitwise equal, optimizer state (canonical blob)
        equal, manifest v2 sharding section readable."""
        for k, v in _VARIANTS[variant].items():
            monkeypatch.setenv(k, v)
        prefix = str(tmp_path / "ck")
        net, tr, est = _setup(31, _ctxs(8))
        est.fit(_loader(), epochs=2, ckpt_prefix=prefix)
        ref_p, ref_blob = _params(net), tr.states_blob()

        sh = model_mod.checkpoint_sharding(prefix, 2)
        assert sh is not None and sh["n_devices"] == 8
        assert sh["layout"] == ("zero" if variant == "zero"
                                else "replicated")
        if variant == "zero":
            assert set(sh["params"]) == \
                {p.name for p in tr._params}

        for n2 in (4, 8):
            net2, tr2, est2 = _setup(77, _ctxs(n2))  # different init
            epoch = est2.resume_from(prefix)
            assert epoch == 2
            got = _params(net2)
            for k in ref_p:
                assert (got[k] == ref_p[k]).all(), (k, n2)
            assert tr2.states_blob() == ref_blob, n2
            est2.fit(_loader(), epochs=3, ckpt_prefix=str(
                tmp_path / ("cont%d" % n2)), resume=prefix)

    def test_v1_params_only_checkpoint_compat(self, tmp_path):
        """A checkpoint written WITHOUT the v2 extras (old writer /
        no trainer) still loads; the states reader reports None and
        restore degrades to params-only."""
        prefix = str(tmp_path / "old")
        arg = {"w": mx.nd.array(np.arange(6, dtype=np.float32))}
        model_mod.save_checkpoint(prefix, 1, None, arg, {})
        model_mod.wait_checkpoints()
        assert model_mod.load_checkpoint_states(prefix, 1) is None
        assert model_mod.checkpoint_sharding(prefix, 1) is None
        loaded = model_mod.load_latest_checkpoint(prefix)
        assert loaded is not None and loaded[2] == 1

    def test_corrupt_states_sidecar_degrades(self, tmp_path):
        """A truncated/corrupt .states sidecar fails its sha256 check
        and restore degrades to params-only instead of unpickling
        garbage."""
        prefix = str(tmp_path / "bad")
        net, tr, est = _setup(41, _ctxs(2))
        est.fit(_loader(), epochs=1, ckpt_prefix=prefix)
        model_mod.wait_checkpoints()
        entry = model_mod.checkpoint_entry(prefix, 1)
        assert entry is not None and "states" in entry
        spath = os.path.join(os.path.dirname(prefix), entry["states"])
        with open(spath, "wb") as f:
            f.write(b"garbage")
        assert model_mod.load_checkpoint_states(prefix, 1) is None
        net2, tr2, est2 = _setup(42, _ctxs(2))
        assert est2.resume_from(prefix) == 1     # params-only restore

    def test_manifest_section_contents(self, monkeypatch):
        monkeypatch.setenv("MXNET_ZERO", "1")
        monkeypatch.setenv("MXNET_ZERO_DCN", "2")
        net, tr, est = _setup(51, _ctxs(8))
        est.fit(_loader(), epochs=1)
        sec = rs.sharding_manifest(tr)
        assert sec["layout"] == "zero"
        assert sec["n_dcn"] == 2
        assert sorted(sec["owner"]) == list(range(8))
        for meta in sec["params"].values():
            assert meta["frag"] == -(-meta["size"] // 8)


# ===========================================================================
# live shrink/grow through the Estimator poll loop
# ===========================================================================
class TestEstimatorElastic:
    def _elastic_env(self, monkeypatch):
        monkeypatch.setenv("MXNET_ELASTIC", "1")
        monkeypatch.setenv("MXNET_ELASTIC_POLL", "1")

    def test_live_shrink_slice_preempt(self, tmp_path, monkeypatch):
        self._elastic_env(monkeypatch)
        prefix = str(tmp_path / "el")
        live = telemetry.counter("mx_elastic_transitions_total",
                                 kind="live")
        restored = telemetry.counter("mx_elastic_transitions_total",
                                     kind="restored")
        live0, rest0 = live.get(), restored.get()
        net, tr, est = _setup(61, _ctxs(8))
        est.fit(_loader(), epochs=1, ckpt_prefix=prefix)
        faultinject.set_fault("slice_preempt", 1.0, max_fires=1)
        est.fit(_loader(), epochs=3, ckpt_prefix=prefix, resume=True)
        assert faultinject.fires("slice_preempt") == 1
        assert len(tr._contexts) == 4           # front half survives
        assert live.get() - live0 == 1
        assert restored.get() - rest0 == 0      # zero restarts
        for k, v in _params(net).items():
            assert np.isfinite(v).all(), k

    def test_grow_back(self, tmp_path, monkeypatch):
        self._elastic_env(monkeypatch)
        prefix = str(tmp_path / "gr")
        net, tr, est = _setup(67, _ctxs(8))
        est.fit(_loader(), epochs=1, ckpt_prefix=prefix)
        elastic.request_preemption(2)
        est.fit(_loader(), epochs=2, ckpt_prefix=prefix, resume=True)
        assert len(tr._contexts) == 2
        elastic.request_preemption(8)           # capacity came back
        est.fit(_loader(), epochs=3, ckpt_prefix=prefix, resume=True)
        assert len(tr._contexts) == 8

    def test_degradation_reshard_fail(self, tmp_path, monkeypatch):
        self._elastic_env(monkeypatch)
        prefix = str(tmp_path / "dg")
        restored = telemetry.counter("mx_elastic_transitions_total",
                                     kind="restored")
        rest0 = restored.get()
        net, tr, est = _setup(71, _ctxs(8))
        est.fit(_loader(), epochs=2, ckpt_prefix=prefix)
        faultinject.set_fault("reshard_fail", 1.0, max_fires=1)
        elastic.request_preemption(4)
        est.fit(_loader(), epochs=3, ckpt_prefix=prefix, resume=True)
        assert len(tr._contexts) == 4
        assert restored.get() - rest0 == 1
        for k, v in _params(net).items():
            assert np.isfinite(v).all(), k

    def test_min_devices_gate(self, tmp_path, monkeypatch):
        """A survivor set below MXNET_ELASTIC_MIN_DEVICES skips the
        live attempt and goes straight to checkpoint-restore."""
        self._elastic_env(monkeypatch)
        monkeypatch.setenv("MXNET_ELASTIC_MIN_DEVICES", "4")
        prefix = str(tmp_path / "mg")
        failed = telemetry.counter("mx_elastic_transitions_total",
                                   kind="live_failed")
        f0 = failed.get()
        net, tr, est = _setup(73, _ctxs(8))
        est.fit(_loader(), epochs=1, ckpt_prefix=prefix)
        elastic.request_preemption(2)
        est.fit(_loader(), epochs=2, ckpt_prefix=prefix, resume=True)
        assert len(tr._contexts) == 2
        assert failed.get() - f0 == 0   # live path never attempted

    def test_transition_no_restore_raises(self, monkeypatch):
        net, tr, est = _setup(79, _ctxs(4))
        est.fit(_loader(), epochs=1)
        faultinject.set_fault("reshard_fail", 1.0, max_fires=1)
        with pytest.raises(MXNetError):
            elastic.run_transition(tr, tr._contexts[:2], restore=None)

    def test_poll_survivor_specs(self):
        ctxs = _ctxs(8)
        elastic.request_preemption("0,2,4")
        assert elastic.poll_survivors(ctxs) == [ctxs[0], ctxs[2],
                                                ctxs[4]]
        assert elastic.poll_survivors(ctxs) is None   # consumed
        elastic.request_preemption(3)
        assert elastic.poll_survivors(ctxs) == ctxs[:3]
        elastic.request_preemption("half")
        assert elastic.poll_survivors(ctxs) == ctxs[:4]
        elastic.request_preemption("banana")          # malformed
        assert elastic.poll_survivors(ctxs) is None   # logged + dropped
        elastic.request_preemption("0,99")            # out of range
        assert elastic.poll_survivors(ctxs) is None

    def test_kv_notice_consumed(self, monkeypatch):
        """A KV-sourced notice must fire exactly once: the key is
        deleted after consumption (a stale spec re-triggering on every
        poll would silently re-shrink the run after a later grow)."""
        from mxnet_tpu import dist

        class FakeKV:
            def __init__(self):
                self.store = {}

            def key_value_try_get(self, k):
                if k not in self.store:
                    raise KeyError(k)
                return self.store[k]

            def key_value_set(self, k, v, allow_overwrite=False):
                self.store[k] = v

            def key_value_delete(self, k):
                self.store.pop(k, None)

        ctxs = _ctxs(8)
        fake = FakeKV()
        monkeypatch.setattr(dist, "_coord_client", lambda: fake)
        assert elastic.announce(4)
        assert elastic.poll_survivors(ctxs) == ctxs[:4]
        assert elastic.KV_KEY not in fake.store       # consumed
        assert elastic.poll_survivors(ctxs) is None   # no re-trigger
        elastic.request_preemption(8)                 # grow back
        assert elastic.poll_survivors(ctxs) == ctxs
        assert elastic.poll_survivors(ctxs) is None   # still quiet
        assert elastic.announce(2)                    # fresh notice
        assert elastic.poll_survivors(ctxs) == ctxs[:2]

    def test_kv_notice_tombstone_without_delete(self, monkeypatch):
        """Clients without key_value_delete tombstone the key instead;
        the tombstone is ignored and a fresh announce re-fires."""
        from mxnet_tpu import dist

        class FakeKVNoDelete:
            def __init__(self):
                self.store = {}

            def key_value_try_get(self, k):
                if k not in self.store:
                    raise KeyError(k)
                return self.store[k]

            def key_value_set(self, k, v, allow_overwrite=False):
                self.store[k] = v

        ctxs = _ctxs(8)
        fake = FakeKVNoDelete()
        monkeypatch.setattr(dist, "_coord_client", lambda: fake)
        assert elastic.announce(4)
        assert elastic.poll_survivors(ctxs) == ctxs[:4]
        assert fake.store[elastic.KV_KEY] == ""       # tombstoned
        assert elastic.poll_survivors(ctxs) is None
        assert elastic.announce(6)
        assert elastic.poll_survivors(ctxs) == ctxs[:6]

    @pytest.fixture
    def sigterm_as_found(self):
        """The handler is installed for the process's life: left in, the
        worker and every child it forks swallow SIGTERM from here on
        (``DataLoader`` retires its workers with ``terminate()``)."""
        import signal
        was = signal.getsignal(signal.SIGTERM)
        installed = elastic._SIGTERM_INSTALLED[0]
        yield
        signal.signal(signal.SIGTERM, was)
        elastic._SIGTERM_INSTALLED[0] = installed
        elastic._SIGTERM_FLAG[0] = False
        assert signal.getsignal(signal.SIGTERM) is was

    def test_sigterm_handler_lock_free(self, monkeypatch, sigterm_as_found):
        """SIGTERM may arrive while the main thread HOLDS the elastic
        lock (poll_survivors runs every elastic poll); the handler
        must not acquire it — the old locked handler deadlocked the
        process exactly at preemption time."""
        import os
        import signal
        ctxs = _ctxs(8)
        monkeypatch.setenv("MXNET_ELASTIC_SIGTERM", "1")
        elastic.install_sigterm_handler()
        sig = telemetry.counter("mx_elastic_preemptions_total",
                                source="sigterm")
        s0 = sig.get()
        with elastic._LOCK:                 # simulate a poll in flight
            os.kill(os.getpid(), signal.SIGTERM)
        assert elastic.pending()
        assert elastic.poll_survivors(ctxs) == ctxs[:4]   # "half"
        assert sig.get() - s0 == 1          # counted at the poll
        assert elastic.poll_survivors(ctxs) is None
        # an explicit pending spec wins over the SIGTERM default
        os.kill(os.getpid(), signal.SIGTERM)
        elastic.request_preemption(2)
        assert elastic.poll_survivors(ctxs) == ctxs[:2]


# ===========================================================================
# transition programs are watched + shardcheck-clean (satellite 6)
# ===========================================================================
class TestShardcheckClean:
    @pytest.fixture(autouse=True)
    def _gates(self, monkeypatch):
        monkeypatch.setenv("MXNET_TELEMETRY", "1")
        monkeypatch.setenv("MXNET_STATICCHECK_SPMD", "1")
        telemetry.refresh()
        staticcheck.refresh()
        telemetry.reset()
        staticcheck.reset()
        compilewatch.reset()
        yield
        compilewatch.reset()
        staticcheck.refresh()

    def test_transition_programs_checked_clean(self):
        devs = _devs(8)
        n0 = spmd_rules.programs_checked()
        data = np.random.rand(130).astype(np.float32)
        src = rs.FragLayout.build(130, 8, 2)
        dst = rs.FragLayout.build(130, 4)
        bufs = rs.place_from_host([(data, src)], 8, src.frag, devs,
                                  np.float32)
        out = rs.reshard_fragments(bufs, rs.plan_moves(src, dst), 4,
                                   dst.frag, devs[:4])
        np.testing.assert_array_equal(
            rs.gather_to_host(out, [dst])[0], data)
        from jax.sharding import NamedSharding, PartitionSpec as P
        x = _put(np.random.rand(16, 3).astype(np.float32),
                 _mesh(devs), P("dp"))
        rs.redistribute(x, NamedSharding(_mesh(devs[:2]), P("dp")))
        assert rs.transition_programs() > 0
        assert spmd_rules.programs_checked() > n0
        assert staticcheck.spmd_findings() == [], \
            staticcheck.spmd_findings()
        sites = [p.get("site") for p in compilewatch.programs()]
        assert "reshard" in sites
