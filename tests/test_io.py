"""RecordIO container + image pipeline tests (ref: tests/python/unittest/
test_recordio.py + test_io.py patterns: byte-roundtrip, idx seek,
magic-splitting payloads, iterator epoch/pad semantics)."""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, recordio
from mxnet_tpu.io import ImageRecordIter


def test_recordio_roundtrip(tmp_path):
    path = str(tmp_path / "a.rec")
    w = recordio.MXRecordIO(path, "w")
    payloads = [b"hello", b"x" * 1000, b"", b"abcd" * 33]
    for p in payloads:
        w.write(p)
    w.close()
    r = recordio.MXRecordIO(path, "r")
    for p in payloads:
        assert r.read() == p
    assert r.read() is None
    r.close()


def test_recordio_magic_in_payload(tmp_path):
    # payload containing the magic word must round-trip via multi-part
    # framing (dmlc recordio semantics)
    import struct
    magic = struct.pack("<I", 0xced7230a)
    path = str(tmp_path / "m.rec")
    cases = [magic, b"abcd" + magic + b"efgh", magic * 3,
             b"xy" + magic,  # unaligned magic stays inline
             magic + b"tail"]
    w = recordio.MXRecordIO(path, "w")
    for c in cases:
        w.write(c)
    w.close()
    r = recordio.MXRecordIO(path, "r")
    for c in cases:
        assert r.read() == c
    r.close()


def test_indexed_recordio(tmp_path):
    rec = str(tmp_path / "i.rec")
    idx = str(tmp_path / "i.idx")
    w = recordio.MXIndexedRecordIO(idx, rec, "w")
    for i in range(20):
        w.write_idx(i, b"rec%03d" % i)
    w.close()
    r = recordio.MXIndexedRecordIO(idx, rec, "r")
    assert r.keys == list(range(20))
    assert r.read_idx(13) == b"rec013"
    assert r.read_idx(2) == b"rec002"
    r.close()


def test_pack_unpack_labels():
    hdr = recordio.IRHeader(0, 3.5, 7, 0)
    s = recordio.pack(hdr, b"payload")
    h2, p2 = recordio.unpack(s)
    assert h2.label == 3.5 and h2.id == 7 and p2 == b"payload"
    # vector label
    hdr = recordio.IRHeader(0, np.array([1.0, 2.0, 3.0], np.float32), 9, 0)
    s = recordio.pack(hdr, b"zz")
    h2, p2 = recordio.unpack(s)
    assert h2.flag == 3 and np.allclose(h2.label, [1, 2, 3]) and p2 == b"zz"


def _write_raw_pack(tmp_path, n=32, h=8, w=12, name="r"):
    rec = str(tmp_path / (name + ".rec"))
    idx = str(tmp_path / (name + ".idx"))
    wr = recordio.MXIndexedRecordIO(idx, rec, "w")
    rng = np.random.RandomState(0)
    imgs = []
    for i in range(n):
        img = rng.randint(0, 255, (h, w, 3), np.uint8)
        imgs.append(img)
        wr.write_idx(i, recordio.pack(recordio.IRHeader(0, float(i), i, 0),
                                      img.tobytes()))
    wr.close()
    return rec, idx, imgs


def test_image_record_iter_raw(tmp_path):
    rec, idx, imgs = _write_raw_pack(tmp_path)
    it = ImageRecordIter(path_imgrec=rec, path_imgidx=idx,
                         data_shape=(3, 8, 12), batch_size=10)
    labels = []
    nb = 0
    for batch in it:
        nb += 1
        take = 10 - (batch.pad or 0)
        labels.extend(batch.label[0].asnumpy().astype(int)[:take].tolist())
        assert batch.data[0].shape == (10, 3, 8, 12)
    assert nb == 4 and sorted(labels) == list(range(32))
    # pixel fidelity through the native path
    it.reset()
    b0 = next(it)
    got = b0.data[0].asnumpy()[3].transpose(1, 2, 0)
    np.testing.assert_allclose(got, imgs[3].astype(np.float32))
    # second epoch after reset iterates again
    it.reset()
    assert next(it).data[0].shape[0] == 10


def test_image_record_iter_shuffle_epoch(tmp_path):
    rec, idx, _ = _write_raw_pack(tmp_path, n=24)
    it = ImageRecordIter(path_imgrec=rec, path_imgidx=idx,
                         data_shape=(3, 8, 12), batch_size=8, shuffle=True,
                         seed=3)
    e1 = [tuple(b.label[0].asnumpy().astype(int)) for b in it]
    it.reset()
    e2 = [tuple(b.label[0].asnumpy().astype(int)) for b in it]
    flat1 = sorted(x for t in e1 for x in t)
    flat2 = sorted(x for t in e2 for x in t)
    assert flat1 == list(range(24)) and flat2 == list(range(24))
    assert e1 != e2  # different shuffle order across epochs


def test_image_record_iter_normalize(tmp_path):
    rec, idx, imgs = _write_raw_pack(tmp_path, n=4, name="n")
    it = ImageRecordIter(path_imgrec=rec, path_imgidx=idx,
                         data_shape=(3, 8, 12), batch_size=4,
                         mean_r=1.0, mean_g=2.0, mean_b=3.0,
                         std_r=2.0, std_g=2.0, std_b=2.0)
    b = next(it)
    got = b.data[0].asnumpy()[0].transpose(1, 2, 0)
    want = (imgs[0].astype(np.float32) - np.array([1, 2, 3], np.float32)) / 2.0
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_image_record_iter_jpeg(tmp_path):
    cv2 = pytest.importorskip("cv2")
    rec = str(tmp_path / "j.rec")
    yy, xx = np.mgrid[0:16, 0:24]
    img = np.stack([(xx * 9) % 256, (yy * 9) % 256, ((xx + yy) * 4) % 256],
                   -1).astype(np.uint8)
    w = recordio.MXRecordIO(rec, "w")
    w.write(recordio.pack_img(recordio.IRHeader(0, 5.0, 0, 0),
                              img[:, :, ::-1], quality=95))
    w.close()
    it = ImageRecordIter(path_imgrec=rec, data_shape=(3, 16, 24),
                         batch_size=1)
    b = next(it)
    got = b.data[0].asnumpy()[0].transpose(1, 2, 0)
    assert float(b.label[0].asnumpy()[0]) == 5.0
    assert np.abs(got - img.astype(np.float32)).mean() < 6.0


def test_image_iter_python_surface(tmp_path):
    rec, idx, imgs = _write_raw_pack(tmp_path, n=12, name="p")
    from mxnet_tpu.image import ImageIter, CreateAugmenter
    it = ImageIter(batch_size=4, data_shape=(3, 8, 12), path_imgrec=rec,
                   path_imgidx=idx,
                   aug_list=CreateAugmenter((3, 8, 12)))
    b = next(it)
    assert b.data[0].shape == (4, 3, 8, 12)
    got = b.data[0].asnumpy()[2].transpose(1, 2, 0)
    np.testing.assert_allclose(got, imgs[2].astype(np.float32))


def test_pack_img_unpack_img(tmp_path):
    pytest.importorskip("cv2")
    from mxnet_tpu.recordio import pack_img, unpack_img, IRHeader
    img = (np.mgrid[0:10, 0:10][0] * 20 % 256).astype(np.uint8)
    img = np.stack([img] * 3, -1)
    s = pack_img(IRHeader(0, 1.0, 0, 0), img, quality=95)
    hdr, out = unpack_img(s)
    assert hdr.label == 1.0
    assert out.shape == (10, 10, 3)
    assert np.abs(out.astype(np.float32) - img.astype(np.float32)).mean() < 4


@pytest.mark.slow
def test_native_pipeline_throughput(tmp_path):
    """The native host pipeline must sustain well over baseline
    (raw 224x224 records, shuffle+mirror). Bar set conservatively for
    CI noise; measured ~12k img/s on the 1-core build host."""
    import ctypes as ct
    import time
    from mxnet_tpu import native as nat
    rec = str(tmp_path / "big.rec")
    idx = str(tmp_path / "big.idx")
    w = recordio.MXIndexedRecordIO(idx, rec, "w")
    raw = np.random.randint(0, 255, (224, 224, 3), np.uint8)
    for i in range(256):
        w.write_idx(i, recordio.pack(recordio.IRHeader(0, float(i), i, 0),
                                     raw.tobytes()))
    w.close()
    lib = nat.load_io_lib()
    assert lib is not None
    h = lib.MXIOCreateImageRecordIter(rec.encode(), idx.encode(), 128, 224,
                                      224, 1, 1, 0, 1, 0, 1, 7)
    assert h
    try:
        data_p = ct.POINTER(ct.c_uint8)()
        label_p = ct.POINTER(ct.c_float)()
        n = ct.c_int(0)

        def nxt():
            rc = lib.MXIONext(h, ct.byref(data_p), ct.byref(label_p),
                              ct.byref(n))
            if rc == 1:
                lib.MXIOReset(h)
                rc = lib.MXIONext(h, ct.byref(data_p), ct.byref(label_p),
                                  ct.byref(n))
            assert rc == 0
            return n.value

        nxt()
        t0 = time.perf_counter()
        total = 0
        for _ in range(10):
            total += nxt()
        rate = total / (time.perf_counter() - t0)
        assert rate > 3000, "native pipeline too slow: %.0f img/s" % rate
    finally:
        lib.MXIOFree(h)


def test_corrupt_rec_raises(tmp_path):
    # a truncated/corrupt .rec must surface an error, not a silent
    # short epoch
    rec, idx, _ = _write_raw_pack(tmp_path, n=10, name="c")
    size = os.path.getsize(rec)
    with open(rec, "r+b") as f:
        f.truncate(size - 100)  # chop mid-record
    it = ImageRecordIter(path_imgrec=rec, data_shape=(3, 8, 12),
                         batch_size=4)
    with pytest.raises(mx.MXNetError):
        for _ in range(5):
            next(it)


def test_im2rec_tool_end_to_end(tmp_path):
    """tools/im2rec.py: list generation + packing (JPEG and raw) read
    back through the native pipeline (ref: tools/im2rec.py)."""
    cv2 = pytest.importorskip("cv2")
    import subprocess, sys
    root = tmp_path / "imgs"
    for cls in ("cat", "dog"):
        (root / cls).mkdir(parents=True)
    for i in range(3):
        for ci, cls in enumerate(("cat", "dog")):
            img = np.full((16, 16, 3), 40 * (i + 1) + 100 * ci, np.uint8)
            cv2.imwrite(str(root / cls / ("%d.png" % i)), img)
    prefix = str(tmp_path / "pack")
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "im2rec.py")
    out = subprocess.run([sys.executable, tool, prefix, str(root)],
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert os.path.exists(prefix + ".rec")
    it = ImageRecordIter(path_imgrec=prefix + ".rec",
                         path_imgidx=prefix + ".idx",
                         data_shape=(3, 16, 16), batch_size=6)
    b = next(it)
    labels = sorted(b.label[0].asnumpy().astype(int).tolist())
    assert labels == [0, 0, 0, 1, 1, 1]

    # raw pass-through mode
    prefix2 = str(tmp_path / "raw")
    out2 = subprocess.run([sys.executable, tool, prefix2, str(root),
                           "--pass-through-raw"],
                          capture_output=True, text=True, timeout=120)
    assert out2.returncode == 0, out2.stderr
    it2 = ImageRecordIter(path_imgrec=prefix2 + ".rec",
                          path_imgidx=prefix2 + ".idx",
                          data_shape=(3, 16, 16), batch_size=6)
    b2 = next(it2)
    # constant-valued images survive raw round-trip EXACTLY: check the
    # value itself, not just constancy (labels sorted per .lst order)
    labels2 = b2.label[0].asnumpy().astype(int)
    vals = b2.data[0].asnumpy().reshape(6, -1)
    # each value must match its class/label: cat = 40*(i+1), dog = +100
    for row in range(6):
        assert vals[row].std() < 1e-6
        v = float(vals[row][0])
        if labels2[row] == 0:
            assert v in (40.0, 80.0, 120.0), v
        else:
            assert v in (140.0, 180.0, 220.0), v


def test_image_record_iter_no_round_batch_tail_pad(tmp_path):
    """round_batch=False short tail: data stays at the advertised
    provide_data shape and pad signals the fill (ADVICE r2 regression)."""
    rec, idx, _ = _write_raw_pack(tmp_path, n=13, name="tail")
    it = ImageRecordIter(path_imgrec=rec, path_imgidx=idx,
                         data_shape=(3, 8, 12), batch_size=5,
                         round_batch=False)
    batches = list(it)
    assert len(batches) == 3
    for b in batches:
        assert b.data[0].shape == tuple(it.provide_data[0][1])
    assert batches[-1].pad == 2
    labels = []
    for b in batches:
        take = 5 - (b.pad or 0)
        labels.extend(b.label[0].asnumpy().astype(int)[:take].tolist())
    assert sorted(labels) == list(range(13))


# ---------------------------------------------------------------------------
# ImageRecordIter keeps one batch ahead (ISSUE 26): what a user can see is
# what the native handle, driven directly, gives.
# ---------------------------------------------------------------------------
def _native_epochs(rec, idx, batch, h, w, shuffle, mirror, seed, epochs):
    """[[(uint8 NHWC, labels)] per batch] per epoch from the native
    handle itself: MXIONext until the marker, then MXIOReset."""
    import ctypes as ct
    from mxnet_tpu import native as nat
    lib = nat.load_io_lib()
    hd = lib.MXIOCreateImageRecordIter(
        rec.encode(), idx.encode() if shuffle else None, batch, h, w, 1,
        int(shuffle), 0, int(mirror), 0, 1, seed)
    assert hd
    data_p = ct.POINTER(ct.c_uint8)()
    label_p = ct.POINTER(ct.c_float)()
    n = ct.c_int(0)
    out = []
    try:
        for _ in range(epochs):
            out.append([])
            while True:
                rc = lib.MXIONext(hd, ct.byref(data_p), ct.byref(label_p),
                                  ct.byref(n))
                if rc == 1:
                    break
                assert rc == 0
                out[-1].append((
                    np.ctypeslib.as_array(
                        data_p, shape=(n.value, h, w, 3)).copy(),
                    np.ctypeslib.as_array(
                        label_p, shape=(n.value,)).copy()))
            lib.MXIOReset(hd)
    finally:
        lib.MXIOFree(hd)
    return out


def _as_handed_over(raw, lab, batch):
    """A native batch as next() hands it over: round_batch tail padding,
    NCHW float32, and pad."""
    count = len(raw)
    if count < batch:
        reps = -(-batch // count)
        raw = np.tile(raw, (reps, 1, 1, 1))[:batch]
        lab = np.tile(lab, reps)[:batch]
    return (raw.astype(np.float32).transpose(0, 3, 1, 2), lab,
            batch - count)


def _assert_epoch(got, want, batch):
    """An epoch from _epoch_of is, bit for bit, the native handle's."""
    assert len(got) == len(want)        # StopIteration at the same call
    for (data, label, pad), (raw, lab) in zip(got, want):
        wd, wl, wp = _as_handed_over(raw, lab, batch)
        np.testing.assert_array_equal(data, wd)
        np.testing.assert_array_equal(label, wl)
        assert pad == wp


def _epoch_of(it):
    """One epoch of next() up to StopIteration: [(data, label, pad)]."""
    got = []
    while True:
        try:
            b = it.next()
        except StopIteration:
            return got
        assert b.data[0].shape == tuple(it.provide_data[0][1])
        assert b.label[0].shape == tuple(it.provide_label[0][1])
        got.append((b.data[0].asnumpy(), b.label[0].asnumpy(), b.pad or 0))


@pytest.mark.parametrize("engine", ["native", "none"])
def test_image_record_iter_lookahead_matches_native_epochs(
        tmp_path, monkeypatch, engine):
    """Three epochs of next() / StopIteration / reset() over a record
    count that is no multiple of the batch: data, labels and pad are
    bit for bit what the native handle gives, with the look-ahead and
    its carry across reset(), and with no engine at all."""
    if engine == "none":
        import mxnet_tpu.engine as eng_mod
        monkeypatch.setattr(eng_mod, "native_or_none", lambda: None)
    rec, idx, _ = _write_raw_pack(tmp_path, n=23, name="la")
    want = _native_epochs(rec, idx, 5, 8, 12, False, False, 0, 3)
    it = ImageRecordIter(path_imgrec=rec, path_imgidx=idx,
                         data_shape=(3, 8, 12), batch_size=5)
    for epoch in want:
        assert len(epoch) == 5
        got = _epoch_of(it)
        _assert_epoch(got, epoch, 5)
        assert got[-1][2] == 2
        it.reset()


def test_image_record_iter_lookahead_shuffled_epochs(tmp_path):
    """Shuffle and rand_mirror on, a fixed seed: the first epoch is bit
    for bit the native handle's; every later epoch holds each record
    once, mirrored or not, under its own label, and ends at the same
    call."""
    rec, idx, imgs = _write_raw_pack(tmp_path, n=23, name="ls")
    (first,) = _native_epochs(rec, idx, 5, 8, 12, True, True, 11, 1)
    it = ImageRecordIter(path_imgrec=rec, path_imgidx=idx,
                         data_shape=(3, 8, 12), batch_size=5, shuffle=True,
                         rand_mirror=True, seed=11)
    got = _epoch_of(it)
    assert len(first) == 5
    _assert_epoch(got, first, 5)
    orders = [[int(x) for d, l, p in got for x in l[:5 - p]]]
    for _ in range(3):
        it.reset()
        got = _epoch_of(it)
        assert len(got) == 5 and [p for _, _, p in got] == [0, 0, 0, 0, 2]
        seen = []
        for data, label, pad in got:
            for img, lab in zip(data[:5 - pad], label[:5 - pad]):
                want = imgs[int(lab)].astype(np.float32)
                img = img.transpose(1, 2, 0)
                assert np.array_equal(img, want) \
                    or np.array_equal(img, want[:, ::-1])
                seen.append(int(lab))
            # the padding repeats the tail's own records
            np.testing.assert_array_equal(
                data[5 - pad:], np.tile(data[:5 - pad], (5, 1, 1, 1))[:pad])
        assert sorted(seen) == list(range(23))
        orders.append(seen)
    assert any(o != orders[0] for o in orders[1:])      # reshuffled


def test_image_record_iter_reset_in_mid_epoch(tmp_path):
    """reset() inside an epoch discards the batch in flight and starts
    over from the epoch's first batch; so does a reset() after the last
    batch, before StopIteration was seen, and the epoch after it is
    whole."""
    rec, idx, imgs = _write_raw_pack(tmp_path, n=12, name="lm")
    it = ImageRecordIter(path_imgrec=rec, path_imgidx=idx,
                         data_shape=(3, 8, 12), batch_size=4)

    def labels(b):
        return b.label[0].asnumpy().astype(int).tolist()

    assert labels(it.next()) == [0, 1, 2, 3]
    assert labels(it.next()) == [4, 5, 6, 7]
    it.reset()
    b = it.next()
    assert labels(b) == [0, 1, 2, 3]
    np.testing.assert_array_equal(
        b.data[0].asnumpy()[2], imgs[2].astype(np.float32).transpose(2, 0, 1))
    it.reset()
    it.reset()                      # twice in a row, and before any next()
    assert [labels(b) for b in it] == [[0, 1, 2, 3], [4, 5, 6, 7],
                                       [8, 9, 10, 11]]
    it.reset()
    for _ in range(3):
        last = it.next()
    assert labels(last) == [8, 9, 10, 11]
    it.reset()                      # the look-ahead has turned the epoch
    assert [labels(b) for b in it] == [[0, 1, 2, 3], [4, 5, 6, 7],
                                       [8, 9, 10, 11]]
    # without a reset() the iteration goes on into the next epoch
    assert labels(it.next()) == [0, 1, 2, 3]


@pytest.fixture
def _io_counters(monkeypatch):
    from mxnet_tpu import telemetry
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    monkeypatch.delenv("MXNET_TELEMETRY_HEARTBEAT", raising=False)
    telemetry.refresh()
    telemetry.reset()

    def read():
        snap = telemetry.snapshot()["counters"]
        return {k: int(snap.get('mx_io_batches_total{handoff="%s"}' % k, 0))
                for k in ("ready", "waited", "cold")}
    yield read
    telemetry.refresh()
    telemetry.reset()


def test_image_record_iter_handoff_counter(tmp_path, _io_counters):
    """mx_io_batches_total: one cold batch, then ready / waited only,
    an epoch turn included; a reset() in mid-epoch costs one more cold
    batch."""
    rec, idx, _ = _write_raw_pack(tmp_path, n=12, name="lc")
    it = ImageRecordIter(path_imgrec=rec, path_imgidx=idx,
                         data_shape=(3, 8, 12), batch_size=4)
    assert _io_counters() == {"ready": 0, "waited": 0, "cold": 0}
    it.next()
    assert _io_counters() == {"ready": 0, "waited": 0, "cold": 1}
    for rest in (2, 3):             # the epoch's rest, then a whole one
        assert len(list(it)) == rest
        it.reset()
    got = _io_counters()
    assert got["cold"] == 1 and got["ready"] + got["waited"] == 5
    from mxnet_tpu.engine import native_wait_all
    it.next()
    native_wait_all()               # the look-ahead's op is over
    got = _io_counters()
    it.next()
    assert _io_counters() == dict(got, ready=got["ready"] + 1)
    it.reset()                      # in mid-epoch
    it.next()
    now = _io_counters()
    assert now["cold"] == 2 and sum(now.values()) == 9


def test_image_record_iter_handoff_counter_without_engine(
        tmp_path, monkeypatch, _io_counters):
    """With no native engine every batch is produced inside a next():
    all of them count cold, the one adopted at an epoch turn too."""
    import mxnet_tpu.engine as eng_mod
    monkeypatch.setattr(eng_mod, "native_or_none", lambda: None)
    rec, idx, _ = _write_raw_pack(tmp_path, n=12, name="le")
    it = ImageRecordIter(path_imgrec=rec, path_imgidx=idx,
                         data_shape=(3, 8, 12), batch_size=4)
    for _ in range(2):
        assert len(list(it)) == 3
        it.reset()
    assert _io_counters() == {"ready": 0, "waited": 0, "cold": 6}


def test_image_record_iter_discarded_batch_error_raises_at_reset(tmp_path):
    """An upload that fails for a batch reset() discards is not dropped:
    it raises at that reset(), and the iterator goes on after another."""
    rec, idx, _ = _write_raw_pack(tmp_path, n=12, name="lx")
    it = ImageRecordIter(path_imgrec=rec, path_imgidx=idx,
                         data_shape=(3, 8, 12), batch_size=4)
    produce, calls = it._produce, []

    def second_fails(out):
        calls.append(out)
        if len(calls) != 2:         # the look-ahead the first next() starts
            return produce(out)
        out.done = True
        raise RuntimeError("no room on the device")

    it._produce = second_fails
    assert it.next().label[0].asnumpy().tolist() == [0, 1, 2, 3]
    with pytest.raises(RuntimeError, match="no room on the device"):
        it.reset()
    it.reset()
    assert [b.label[0].asnumpy().astype(int).tolist() for b in it] == [
        [0, 1, 2, 3], [4, 5, 6, 7], [8, 9, 10, 11]]


def test_image_record_iter_deleted_with_an_op_in_flight(tmp_path):
    """An iterator dropped while its look-ahead is in flight waits for
    the op before it frees the native handle: no hang, no warning, no
    error left on the engine."""
    import gc
    import warnings
    from mxnet_tpu.engine import native_engine
    rec, idx, _ = _write_raw_pack(tmp_path, n=64, h=64, w=64, name="ld")
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        for _ in range(4):
            it = ImageRecordIter(path_imgrec=rec, path_imgidx=idx,
                                 data_shape=(3, 64, 64), batch_size=16,
                                 shuffle=True, rand_mirror=True)
            b = it.next()
            assert it._ahead is not None
            del it
            gc.collect()
            assert b.data[0].shape == (16, 3, 64, 64)
    assert not [m for m in native_engine().pending_ops()
                if m[0] == "io_batch_upload"]
    nd.waitall()
