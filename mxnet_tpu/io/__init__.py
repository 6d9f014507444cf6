"""Data iterators (ref: python/mxnet/io/io.py :: DataIter, NDArrayIter,
ResizeIter, PrefetchingIter; DataBatch/DataDesc) plus ImageRecordIter
backed by the native C++ pipeline (mxnet_tpu/native/io.cc — the
src/io/iter_image_recordio_2.cc equivalent: threaded RecordIO parse +
JPEG decode + crop/mirror augment + double buffering).
"""
from __future__ import annotations

import threading
from collections import namedtuple
from typing import List, Optional

import numpy as np

from ..base import MXNetError
from .. import ndarray as nd
from ..ndarray import NDArray

__all__ = ["DataDesc", "DataBatch", "DataIter", "NDArrayIter", "ResizeIter",
           "PrefetchingIter", "ImageRecordIter"]


class DataDesc(namedtuple("DataDesc", ["name", "shape", "dtype", "layout"])):
    def __new__(cls, name, shape, dtype=np.float32, layout="NCHW"):
        return super().__new__(cls, name, tuple(shape), dtype, layout)

    @staticmethod
    def get_batch_axis(layout):
        if layout is None:
            return 0
        return layout.find("N")


class DataBatch:
    def __init__(self, data, label=None, pad=None, index=None,
                 bucket_key=None, provide_data=None, provide_label=None):
        if data is not None and not isinstance(data, (list, tuple)):
            data = [data]
        if label is not None and not isinstance(label, (list, tuple)):
            label = [label]
        self.data = data
        self.label = label
        self.pad = pad
        self.index = index
        self.bucket_key = bucket_key
        self.provide_data = provide_data
        self.provide_label = provide_label

    def __str__(self):
        data_shapes = [d.shape for d in self.data] if self.data else None
        label_shapes = [l.shape for l in self.label] if self.label else None
        return "{}: data shapes: {} label shapes: {}".format(
            self.__class__.__name__, data_shapes, label_shapes)


class DataIter:
    def __init__(self, batch_size=0):
        self.batch_size = batch_size

    def __iter__(self):
        return self

    def reset(self):
        pass

    def next(self):
        if self.iter_next():
            return DataBatch(data=self.getdata(), label=self.getlabel(),
                             pad=self.getpad(), index=self.getindex())
        raise StopIteration

    def __next__(self):
        from .. import telemetry
        with telemetry.span("io::%s.next" % type(self).__name__, "io",
                            hist="mx_dataiter_batch_seconds",
                            iter=type(self).__name__) as sp:
            try:
                return self.next()
            except StopIteration:
                sp.cancel()     # the exhausted probe is not a batch
                raise

    def iter_next(self):
        raise NotImplementedError

    def getdata(self):
        raise NotImplementedError

    def getlabel(self):
        raise NotImplementedError

    def getindex(self):
        return None

    def getpad(self):
        raise NotImplementedError


def _init_data(data, allow_empty, default_name):
    assert data is not None or allow_empty
    if data is None:
        data = []
    if isinstance(data, (np.ndarray, NDArray)):
        data = [data]
    if isinstance(data, list):
        if not allow_empty:
            assert len(data) > 0
        if len(data) == 1:
            data = {default_name: data[0]}
        else:
            data = {"_%d_%s" % (i, default_name): d for i, d in enumerate(data)}
    if not isinstance(data, dict):
        raise TypeError("Input must be NDArray, numpy.ndarray, list or dict")
    out = []
    for k, v in data.items():
        if not isinstance(v, NDArray):
            v = nd.array(np.asarray(v))
        out.append((k, v))
    return out


class NDArrayIter(DataIter):
    """Iterate over in-memory arrays (ref: io.py :: NDArrayIter), with
    pad/discard/roll_over last-batch handling."""

    def __init__(self, data, label=None, batch_size=1, shuffle=False,
                 last_batch_handle="pad", data_name="data",
                 label_name="softmax_label"):
        super().__init__(batch_size)
        self.data = _init_data(data, allow_empty=False, default_name=data_name)
        self.label = _init_data(label, allow_empty=True, default_name=label_name)
        self.num_data = self.data[0][1].shape[0]
        self.cursor = -batch_size
        self.shuffle = shuffle
        self.last_batch_handle = last_batch_handle
        self._cache_idx = None
        self._shuffled_indices = np.arange(self.num_data)
        if shuffle:
            self._do_shuffle()

    def _do_shuffle(self):
        np.random.shuffle(self._shuffled_indices)

    @property
    def provide_data(self) -> List[DataDesc]:
        return [DataDesc(k, (self.batch_size,) + v.shape[1:], v.dtype)
                for k, v in self.data]

    @property
    def provide_label(self) -> List[DataDesc]:
        return [DataDesc(k, (self.batch_size,) + v.shape[1:], v.dtype)
                for k, v in self.label]

    def reset(self):
        if self.shuffle:
            self._do_shuffle()
        if self.last_batch_handle == "roll_over" and \
                0 < self.cursor < self.num_data:
            self.cursor = -self.batch_size + (self.cursor % self.num_data) % self.batch_size
        else:
            self.cursor = -self.batch_size

    def iter_next(self):
        self.cursor += self.batch_size
        return self.cursor < self.num_data

    def next(self):
        if not self.iter_next():
            raise StopIteration
        return DataBatch(data=self.getdata(), label=self.getlabel(),
                         pad=self.getpad(), index=None)

    def _take(self, arrays):
        start = self.cursor
        end = min(start + self.batch_size, self.num_data)
        idx = self._shuffled_indices[start:end]
        pad = self.batch_size - (end - start)
        if pad and self.last_batch_handle == "pad":
            idx = np.concatenate([idx, self._shuffled_indices[:pad]])
        out = []
        for _, v in arrays:
            a = v.asnumpy()[idx]
            out.append(nd.array(a, dtype=v.dtype))
        return out

    def getdata(self):
        if self.last_batch_handle == "discard" and \
                self.cursor + self.batch_size > self.num_data:
            raise StopIteration
        return self._take(self.data)

    def getlabel(self):
        if not self.label:
            return None
        return self._take(self.label)

    def getpad(self):
        if self.last_batch_handle == "pad" and \
                self.cursor + self.batch_size > self.num_data:
            return self.cursor + self.batch_size - self.num_data
        return 0


class ResizeIter(DataIter):
    """Resize an iterator to fixed batches per epoch (ref: io.py)."""

    def __init__(self, data_iter, size, reset_internal=True):
        super().__init__(data_iter.batch_size)
        self.data_iter = data_iter
        self.size = size
        self.reset_internal = reset_internal
        self.cur = 0
        self.current_batch = None

    @property
    def provide_data(self):
        return self.data_iter.provide_data

    @property
    def provide_label(self):
        return self.data_iter.provide_label

    def reset(self):
        self.cur = 0
        if self.reset_internal:
            self.data_iter.reset()

    def iter_next(self):
        if self.cur == self.size:
            return False
        try:
            self.current_batch = self.data_iter.next()
        except StopIteration:
            self.data_iter.reset()
            self.current_batch = self.data_iter.next()
        self.cur += 1
        return True

    def next(self):
        if self.iter_next():
            return self.current_batch
        raise StopIteration

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getpad(self):
        return self.current_batch.pad


class PrefetchingIter(DataIter):
    """Double-buffer wrapper over iterators in background threads
    (ref: io.py :: PrefetchingIter ≈ src/io/iter_prefetcher.h). Overlaps
    host batch prep with device compute — on TPU this hides host→HBM
    transfer latency."""

    def __init__(self, iters, rename_data=None, rename_label=None):
        super().__init__()
        if not isinstance(iters, list):
            iters = [iters]
        self.n_iter = len(iters)
        self.iters = iters
        self.rename_data = rename_data
        self.rename_label = rename_label
        self.batch_size = self.provide_data[0][1][0]
        self.current_batch = [None for _ in range(self.n_iter)]
        self.next_batch = [None for _ in range(self.n_iter)]
        self.data_ready = [threading.Event() for _ in range(self.n_iter)]
        self.data_taken = [threading.Event() for _ in range(self.n_iter)]
        for e in self.data_taken:
            e.set()
        self.started = True

        def prefetch_func(self, i):
            while True:
                self.data_taken[i].wait()
                if not self.started:
                    break
                try:
                    self.next_batch[i] = self.iters[i].next()
                except StopIteration:
                    self.next_batch[i] = None
                self.data_taken[i].clear()
                self.data_ready[i].set()

        self.prefetch_threads = [
            threading.Thread(target=prefetch_func, args=[self, i], daemon=True)
            for i in range(self.n_iter)]
        for t in self.prefetch_threads:
            t.start()

    def __del__(self):
        try:
            self.started = False
            for e in self.data_taken:
                e.set()
            for t in self.prefetch_threads:
                t.join(timeout=0.1)
        except Exception:
            pass

    @property
    def provide_data(self):
        if self.rename_data is None:
            return sum([i.provide_data for i in self.iters], [])
        return sum([[DataDesc(r[x.name], x.shape, x.dtype)
                     if isinstance(x, DataDesc) else DataDesc(*x)
                     for x in i.provide_data]
                    for r, i in zip(self.rename_data, self.iters)], [])

    @property
    def provide_label(self):
        if self.rename_label is None:
            return sum([i.provide_label for i in self.iters], [])
        return sum([[DataDesc(r[x.name], x.shape, x.dtype)
                     if isinstance(x, DataDesc) else DataDesc(*x)
                     for x in i.provide_label]
                    for r, i in zip(self.rename_label, self.iters)], [])

    def reset(self):
        for e in self.data_ready:
            e.wait()
        for i in self.iters:
            i.reset()
        for e in self.data_ready:
            e.clear()
        for e in self.data_taken:
            e.set()

    def iter_next(self):
        for e in self.data_ready:
            e.wait()
        if self.next_batch[0] is None:
            return False
        self.current_batch = self.next_batch[0]
        for e in self.data_ready:
            e.clear()
        for e in self.data_taken:
            e.set()
        return True

    def next(self):
        if self.iter_next():
            return self.current_batch
        raise StopIteration

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getindex(self):
        return self.current_batch.index

    def getpad(self):
        return self.current_batch.pad


class ImageRecordIter(DataIter):
    """Image RecordIO iterator on the native C++ pipeline.

    Ref: src/io/iter_image_recordio_2.cc :: ImageRecordIOParser2 behind
    MXDataIterCreateIter('ImageRecordIter'). The C++ worker reads
    .rec/.idx (dmlc framing), decodes JPEG (or raw pass-through
    records), augments (resize-short, random/center crop, mirror) and
    double-buffers batches.

    TPU-native batch contract: the host emits NHWC uint8 (4x fewer
    host->HBM bytes than fp32); `data_layout="NCHW"` (default, reference
    parity) transposes + casts + normalizes ON DEVICE where XLA fuses it
    into the consumer. mean/std normalization happens on device for the
    same reason.
    """

    def __init__(self, path_imgrec, data_shape, batch_size,
                 path_imgidx=None, label_width=1, shuffle=False,
                 rand_crop=False, rand_mirror=False, resize=0,
                 mean_r=0.0, mean_g=0.0, mean_b=0.0,
                 std_r=1.0, std_g=1.0, std_b=1.0,
                 data_layout="NCHW", dtype="float32", seed=0,
                 round_batch=True, ctx=None, device=True,
                 preprocess_threads=1, **kwargs):
        super().__init__(batch_size)
        from .. import native as native_mod
        from ..context import current_context
        if len(data_shape) != 3 or data_shape[0] != 3:
            raise ValueError("data_shape must be (3, H, W)")
        self._lib = native_mod.load_io_lib()
        self._c, self._h, self._w = (int(data_shape[0]), int(data_shape[1]),
                                     int(data_shape[2]))
        self._label_width = int(label_width)
        self._layout = data_layout
        self._dtype = np.dtype(dtype)
        self._mean = np.array([mean_r, mean_g, mean_b], np.float32)
        self._std = np.array([std_r, std_g, std_b], np.float32)
        self._ctx = ctx or current_context()
        self._round_batch = bool(round_batch)
        idx = path_imgidx.encode() if (path_imgidx and shuffle) else None
        if shuffle and not path_imgidx:
            raise MXNetError("shuffle=True needs path_imgidx")
        import ctypes as ct
        self._handle = self._lib.MXIOCreateImageRecordIter(
            path_imgrec.encode(), idx, int(batch_size), self._h, self._w,
            self._label_width, int(bool(shuffle)), int(bool(rand_crop)),
            int(bool(rand_mirror)), int(resize), int(preprocess_threads),
            int(seed))
        if not self._handle:
            raise MXNetError("ImageRecordIter init failed: %s"
                             % native_mod.last_error())
        self._ct = ct
        self._jit_post = None

    @property
    def provide_data(self):
        shape = (self.batch_size, self._c, self._h, self._w) \
            if self._layout == "NCHW" \
            else (self.batch_size, self._h, self._w, self._c)
        return [DataDesc("data", shape, self._dtype, self._layout)]

    @property
    def provide_label(self):
        shape = (self.batch_size,) if self._label_width == 1 \
            else (self.batch_size, self._label_width)
        return [DataDesc("softmax_label", shape, np.float32, "N")]

    def reset(self):
        self._lib.MXIOReset(self._handle)

    def _postprocess(self, raw_u8):
        """Device-side cast/normalize/transpose — one tiny jitted
        program whose output XLA lays out for the consumer."""
        if self._jit_post is None:
            import jax
            import jax.numpy as jnp
            mean, std = self._mean, self._std
            layout, dt = self._layout, self._dtype

            @jax.jit
            def post(x):  # x: N,H,W,C u8
                y = x.astype(jnp.float32)
                if (mean != 0).any():
                    y = y - mean.reshape(1, 1, 1, 3)
                if (std != 1).any():
                    y = y / std.reshape(1, 1, 1, 3)
                if layout == "NCHW":
                    y = y.transpose(0, 3, 1, 2)
                return y.astype(dt)

            self._jit_post = post
        return self._jit_post(raw_u8)

    def next(self):
        import jax
        ct = self._ct
        data_p = ct.POINTER(ct.c_uint8)()
        label_p = ct.POINTER(ct.c_float)()
        n = ct.c_int(0)
        rc = self._lib.MXIONext(self._handle, ct.byref(data_p),
                                ct.byref(label_p), ct.byref(n))
        if rc == 1:
            raise StopIteration
        if rc != 0:
            from .. import native as native_mod
            raise MXNetError("ImageRecordIter: %s" % native_mod.last_error())
        count = n.value
        pad = 0
        buf = np.ctypeslib.as_array(data_p,
                                    shape=(count, self._h, self._w, self._c))
        lab = np.ctypeslib.as_array(label_p,
                                    shape=(count, self._label_width))
        if count < self.batch_size and self._round_batch:
            # pad the tail batch by repeating (reference round_batch)
            reps = -(-self.batch_size // count)
            buf = np.tile(buf, (reps, 1, 1, 1))[:self.batch_size]
            lab = np.tile(lab, (reps, 1))[:self.batch_size]
            pad = self.batch_size - count
        elif count < self.batch_size:
            # round_batch=False short tail: still pad to the advertised
            # provide_data shape (consumers bind to the full batch_size)
            # and signal the padding via DataBatch.pad, like the
            # reference's last-batch-handling contract
            full = np.zeros((self.batch_size,) + buf.shape[1:], buf.dtype)
            full[:count] = buf
            fl = np.zeros((self.batch_size,) + lab.shape[1:], lab.dtype)
            fl[:count] = lab
            buf, lab = full, fl
            pad = self.batch_size - count
        else:
            # the views alias the native double buffer, which the
            # producer recycles after our NEXT MXIONext call — copy out
            # (on THIS thread, before the next MXIONext) so the async
            # upload can't read overwritten pixels
            buf = buf.copy()
            lab = lab.copy()
        # native-IO -> device hand-off as a native-engine op (ref:
        # SURVEY §1 L2 "every mutation flows through the engine"): the
        # host->HBM upload + normalize run on an engine worker with the
        # batch arrays gated on the op's write var, so next() returns
        # immediately and the upload overlaps the consumer's compute;
        # an upload error re-raises at wait_to_read.
        dev = self._ctx.jax_device
        label_arr = np.ascontiguousarray(
            lab[:, 0] if self._label_width == 1 else lab)

        def make(data, label, buf=buf, label_arr=label_arr):
            def upload():
                raw = jax.device_put(buf, dev)
                data._set_jax(self._postprocess(raw))
                label._set_jax(jax.device_put(label_arr, dev))
            return upload

        from ..engine import gate_arrays, native_or_none, push_gated
        eng = native_or_none()
        if eng is None:
            data = NDArray(None, self._ctx)
            label = NDArray(None, self._ctx)
            make(data, label)()
        else:
            data = NDArray(None, self._ctx)
            label = NDArray(None, self._ctx)
            avals = [jax.ShapeDtypeStruct(tuple(self.provide_data[0][1]),
                                          np.dtype(self._dtype)),
                     jax.ShapeDtypeStruct(label_arr.shape, label_arr.dtype)]
            var, _gate = gate_arrays([data, label], avals)
            push_gated(make(data, label), var, label="io_batch_upload")
        return DataBatch([data], [label], pad=pad,
                         provide_data=self.provide_data,
                         provide_label=self.provide_label)

    def __del__(self):
        try:
            if getattr(self, "_handle", None):
                self._lib.MXIOFree(self._handle)
                self._handle = None
        except Exception:
            pass
