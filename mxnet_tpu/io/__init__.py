"""Data iterators (ref: python/mxnet/io/io.py :: DataIter, NDArrayIter,
ResizeIter, PrefetchingIter; DataBatch/DataDesc) plus ImageRecordIter
backed by the native C++ pipeline (mxnet_tpu/native/io.cc — the
src/io/iter_image_recordio_2.cc equivalent: threaded RecordIO parse +
JPEG decode + crop/mirror augment + double buffering).
"""
from __future__ import annotations

import functools
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


class _Produced:
    """What one production of `ImageRecordIter` leaves behind: written
    by the producing op, read once the op's engine var has been waited
    for (`var` is None when there is nothing to wait for)."""

    __slots__ = ("var", "done", "at", "data", "label", "pad")

    def __init__(self):
        self.var = None
        self.done = False
        # "mid": a batch inside an epoch. "turn": the op met the epoch
        # marker, so StopIteration is owed before this batch, which is
        # the first of the next epoch. "first": that batch once
        # StopIteration has been raised, or waived by reset().
        self.at = "mid"
        self.data = self.label = None
        self.pad = 0


def _record_producer(lib, handle, batch_size, hwc, label_width, round_batch,
                     dev, post):
    """Everything one batch of `ImageRecordIter` takes, as a function of
    its `_Produced` slot. It refers to the native handle and not to the
    iterator: an op in flight must not keep the iterator alive, whose
    `__del__` waits for the op before it frees the handle."""
    import ctypes as ct
    import jax

    def produce(out):
        try:
            data_p = ct.POINTER(ct.c_uint8)()
            label_p = ct.POINTER(ct.c_float)()
            n = ct.c_int(0)
            refs = (ct.byref(data_p), ct.byref(label_p), ct.byref(n))
            rc = lib.MXIONext(handle, *refs)
            if rc == 1:
                # the epoch marker. The look-ahead carries across the
                # turn: reset here, once, and go on to the next epoch's
                # first batch, which the user's reset() adopts
                out.at = "turn"
                lib.MXIOReset(handle)
                rc = lib.MXIONext(handle, *refs)
                if rc == 1:
                    return          # an epoch with no batch in it
            if rc != 0:
                from .. import native as native_mod
                raise MXNetError("ImageRecordIter: %s"
                                 % native_mod.last_error())
            count = n.value
            buf = np.ctypeslib.as_array(data_p, shape=(count,) + hwc)
            lab = np.ctypeslib.as_array(label_p, shape=(count, label_width))
            if count < batch_size and round_batch:
                # pad the tail batch by repeating (reference round_batch)
                reps = -(-batch_size // count)
                buf = np.tile(buf, (reps, 1, 1, 1))[:batch_size]
                lab = np.tile(lab, (reps, 1))[:batch_size]
                out.pad = batch_size - count
            elif count < batch_size:
                # round_batch=False short tail: still pad to the
                # advertised provide_data shape (consumers bind to the
                # full batch_size) and signal the padding via
                # DataBatch.pad, like the reference's
                # last-batch-handling contract
                full = np.zeros((batch_size,) + buf.shape[1:], buf.dtype)
                full[:count] = buf
                fl = np.zeros((batch_size,) + lab.shape[1:], lab.dtype)
                fl[:count] = lab
                buf, lab = full, fl
                out.pad = batch_size - count
            else:
                # the views alias the native double buffer, which the
                # producer recycles at the NEXT MXIONext call (the next
                # production's): copy out before the upload, which may
                # read the host buffer after device_put returns
                buf = buf.copy()
                lab = lab.copy()
            out.data = post(jax.device_put(buf, dev))
            out.label = jax.device_put(np.ascontiguousarray(
                lab[:, 0] if label_width == 1 else lab), dev)
        finally:
            out.done = True

    return produce


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

    One batch ahead, on the device (ref: src/io/iter_prefetcher.h, the
    PrefetcherIter upstream's ImageRecordIter is built on): `next()`
    returns the batch whose production the previous call started, and
    starts the next one before it returns. A production is one op on
    the native engine (label `io_batch_upload`): fetch from the native
    double buffer, copy out, pad the tail, upload, normalise. So the
    upload of batch k+1 is enqueued on the device ahead of the step
    that consumes batch k. The batch is handed over: its op has
    completed, `data[0]` / `label[0]` carry no engine gate and go
    straight into a recorded, hybridized step, and an error of the op
    raises at the `next()` that would have returned its batch. The
    look-ahead carries across the end of an epoch: the op that meets
    the marker resets the native pipeline and produces the next epoch's
    first batch, `next()` raises StopIteration as before, and `reset()`
    adopts that batch instead of resetting again. A `reset()` inside an
    epoch waits for the op in flight, discards its batch (an error of
    that op raises at the `reset()`) and resets; the next batch is then
    produced inside `next()`, as the first one is. Without the native
    engine every batch is.
    `mx_io_batches_total{handoff=ready|waited|cold}` counts how each
    batch came (docs/OBSERVABILITY.md).
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
        self._ctx = ctx or current_context()
        idx = path_imgidx.encode() if (path_imgidx and shuffle) else None
        if shuffle and not path_imgidx:
            raise MXNetError("shuffle=True needs path_imgidx")
        self._ahead = None      # the _Produced of the batch after this one
        self._handle = self._lib.MXIOCreateImageRecordIter(
            path_imgrec.encode(), idx, int(batch_size), self._h, self._w,
            self._label_width, int(bool(shuffle)), int(bool(rand_crop)),
            int(bool(rand_mirror)), int(resize), int(preprocess_threads),
            int(seed))
        if not self._handle:
            raise MXNetError("ImageRecordIter init failed: %s"
                             % native_mod.last_error())
        self._produce = _record_producer(
            self._lib, self._handle, int(batch_size),
            (self._h, self._w, self._c), self._label_width,
            bool(round_batch), self._ctx.jax_device,
            self._postprocess(np.array([mean_r, mean_g, mean_b], np.float32),
                              np.array([std_r, std_g, std_b], np.float32)))

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

    def _postprocess(self, mean, std):
        """Device-side cast/normalize/transpose — one tiny jitted
        program whose output XLA lays out for the consumer."""
        import jax
        import jax.numpy as jnp
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

        return post

    def _start(self, eng):
        """Start one production: on a worker of the native engine, or
        here when there is none."""
        out = _Produced()
        if eng is None:
            self._produce(out)
        else:
            out.var = eng.new_var()
            eng.push_async(functools.partial(self._produce, out),
                           write_vars=(out.var,), label="io_batch_upload")
        return out

    @staticmethod
    def _collect(out):
        """Wait for a production's op; its error raises here, once."""
        if out.var is None:
            return
        from ..engine import native_engine
        var, out.var = out.var, None
        eng = native_engine()
        try:
            eng.wait_for_var(var)
        finally:
            eng.delete_var(var)

    def reset(self):
        out, self._ahead = self._ahead, None
        if out is not None:
            # a discarded batch's error raises here: no next() is left
            # to meet it
            self._collect(out)
            if out.at != "mid" and out.data is not None:
                # the look-ahead already turned the epoch: one native
                # reset an epoch, and its first batch is ready
                out.at = "first"
                self._ahead = out
                return
        self._lib.MXIOReset(self._handle)

    def next(self):
        from .. import telemetry
        from ..engine import native_or_none
        eng = native_or_none()
        out, self._ahead = self._ahead, None
        if out is None or eng is None:
            # no look-ahead: produced inside a next(), this one or, with
            # no engine, the one that met the epoch marker
            handoff = "cold"
            out = out or self._start(eng)
        else:
            handoff = "ready" if out.done else "waited"
        self._collect(out)
        if out.at == "turn":
            out.at = "first"
            if out.data is not None:
                self._ahead = out
            raise StopIteration
        # Hand-off (ref: SURVEY §1 L2 "every mutation flows through the
        # engine"): this batch's op has completed on the engine — in
        # steady state it was pushed a whole step ago, so its upload and
        # normalise sit on the device ahead of the step that was
        # launched since — and the arrays carry no gate: they can enter
        # a recorded CachedOp with no wait_to_read(). The op's error
        # raised above, at this next(). The next batch's production
        # starts now, before the consumer launches its step.
        telemetry.count_event("mx_io_batches_total", handoff=handoff)
        if eng is not None:
            self._ahead = self._start(eng)
        return DataBatch([NDArray(out.data, self._ctx)],
                         [NDArray(out.label, self._ctx)], pad=out.pad,
                         provide_data=self.provide_data,
                         provide_label=self.provide_label)

    def __del__(self):
        try:
            out, self._ahead = getattr(self, "_ahead", None), None
            if out is not None:
                try:
                    # the op in flight uses the handle
                    self._collect(out)
                except Exception:
                    pass
            if getattr(self, "_handle", None):
                self._lib.MXIOFree(self._handle)
                self._handle = None
        except Exception:
            pass
