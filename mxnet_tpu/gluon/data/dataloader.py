"""DataLoader (ref: python/mxnet/gluon/data/dataloader.py).

``num_workers > 0`` forks REAL worker processes that batchify in
parallel and hand batches back through POSIX shared memory — the
reference's multiprocess workers writing into shared-memory NDArrays
(storage/cpu_shared_storage_manager.h; dataloader.py worker_loop).
TPU-native differences: one shm segment per batch (all arrays packed
at offsets) instead of per-NDArray shm chunks, and the parent uploads
straight from the mapped segment into HBM (device_put copies anyway,
so the segment is unlinked immediately after).

``thread_pool=True`` selects the old threaded prefetcher (useful when
the dataset closes over device arrays, which must not be touched in a
forked child); ``num_workers=0`` loads synchronously.
"""
from __future__ import annotations

import multiprocessing
import os
import queue
import threading
import traceback
from typing import Callable, List, Optional

import numpy as np

from ... import faultinject
from ... import ndarray as nd
from ...ndarray import NDArray
from .sampler import BatchSampler, RandomSampler, SequentialSampler

__all__ = ["DataLoader", "default_batchify_fn", "default_mp_batchify_fn"]


def default_batchify_fn(data):
    """Stack samples into a batch (ref: dataloader.py :: default_batchify_fn)."""
    if isinstance(data[0], NDArray):
        return nd.stack_list(data)
    if isinstance(data[0], tuple):
        data = zip(*data)
        return [default_batchify_fn(i) for i in data]
    data = np.asarray(data)
    return nd.array(data, dtype=data.dtype if data.dtype != np.float64
                    else np.float32)


def default_mp_batchify_fn(data):
    """Worker-side batchify: stacks into NUMPY (ref: dataloader.py ::
    default_mp_batchify_fn builds shared-memory NDArrays — here the
    numpy batch is packed into one shm segment by the worker loop; the
    parent wraps it as NDArrays)."""
    if isinstance(data[0], NDArray):
        return np.stack([d.asnumpy() for d in data])
    if isinstance(data[0], np.ndarray):
        return np.stack(data)
    if isinstance(data[0], tuple):
        data = zip(*data)
        return [default_mp_batchify_fn(i) for i in data]
    data = np.asarray(data)
    return data.astype(np.float32) if data.dtype == np.float64 else data


# ---------------------------------------------------------------------------
# shared-memory batch transport
# ---------------------------------------------------------------------------
def _flatten_batch(batch, leaves):
    """Template tree with leaf placeholders; leaves collected in order."""
    if isinstance(batch, NDArray):
        leaves.append(np.ascontiguousarray(batch.asnumpy()))
        return ("leaf", len(leaves) - 1)
    if isinstance(batch, np.ndarray):
        leaves.append(np.ascontiguousarray(batch))
        return ("leaf", len(leaves) - 1)
    if isinstance(batch, (list, tuple)):
        return ("seq", type(batch) is tuple,
                [_flatten_batch(b, leaves) for b in batch])
    if isinstance(batch, dict):
        return ("dict", [(k, _flatten_batch(v, leaves))
                         for k, v in batch.items()])
    return ("py", batch)   # scalars/strings ride the queue directly


def _pack_shm(batch):
    """Pack every array leaf of `batch` into ONE shm segment; returns
    (shm_name, descr_tree, leaf_meta)."""
    from multiprocessing import shared_memory

    leaves: List[np.ndarray] = []
    tree = _flatten_batch(batch, leaves)
    align = 64
    offs, total = [], 0
    for a in leaves:
        total = (total + align - 1) // align * align
        offs.append(total)
        total += a.nbytes
    shm = shared_memory.SharedMemory(create=True, size=max(total, 1))
    for a, off in zip(leaves, offs):
        np.ndarray(a.shape, a.dtype, buffer=shm.buf, offset=off)[...] = a
    meta = [(off, a.shape, str(a.dtype)) for a, off in zip(leaves, offs)]
    name = shm.name
    shm.close()
    # the PARENT owns the segment's lifetime (it unlinks after upload);
    # stop this process's resource_tracker from double-unlinking it at
    # worker exit
    try:
        from multiprocessing import resource_tracker
        resource_tracker.unregister("/" + name, "shared_memory")
    except Exception:
        pass
    return name, tree, meta


def _unpack_shm(name, tree, meta):
    """Parent side: map the segment, wrap leaves as NDArrays (nd.array
    copies into the device buffer), unlink."""
    from multiprocessing import shared_memory

    shm = shared_memory.SharedMemory(name=name)
    try:
        arrays = []
        for off, shape, dtype in meta:
            view = np.ndarray(shape, np.dtype(dtype), buffer=shm.buf,
                              offset=off)
            # copy OUT of the mapping before unlinking: device_put can
            # zero-copy alias host memory (CPU backend), and an aliased
            # unmapped segment segfaults at first read
            arrays.append(nd.array(view.copy(), dtype=view.dtype))

        def rebuild(t):
            kind = t[0]
            if kind == "leaf":
                return arrays[t[1]]
            if kind == "seq":
                out = [rebuild(c) for c in t[2]]
                return tuple(out) if t[1] else out
            if kind == "dict":
                return {k: rebuild(c) for k, c in t[1]}
            return t[1]

        return rebuild(tree)
    finally:
        shm.close()
        try:
            shm.unlink()
        except FileNotFoundError:
            pass


def _worker_loop(dataset, batchify_fn, task_q, res_q, seed, generation=0):
    """Worker process body (ref: dataloader.py :: worker_loop).
    `generation` counts respawns: 0 for the original pool, +1 per
    supervisor restart round (selects the fault-injection site so chaos
    tests can kill originals but spare replacements, or both)."""
    if seed is not None:
        np.random.seed(seed)
    site = "dl_worker" if generation == 0 else "dl_worker_respawn"
    while True:
        task = task_q.get()
        if task is None:
            break
        if faultinject.should_fail(site):
            os._exit(1)   # simulated OOM-kill: no result, no cleanup
        seq, indices = task
        try:
            batch = batchify_fn([dataset[i] for i in indices])
            res_q.put((seq, "ok", _pack_shm(batch)))
        except Exception:
            res_q.put((seq, "err", traceback.format_exc()))


class DataLoader:
    def __init__(self, dataset, batch_size=None, shuffle=False, sampler=None,
                 last_batch=None, batch_sampler=None, batchify_fn=None,
                 num_workers=0, pin_memory=False, pin_device_id=0,
                 prefetch=None, thread_pool=False, timeout=120):
        self._dataset = dataset
        self._timeout = timeout
        if batch_sampler is None:
            if batch_size is None:
                raise ValueError(
                    "batch_size must be specified unless batch_sampler is")
            if sampler is None:
                sampler = RandomSampler(len(dataset)) if shuffle else \
                    SequentialSampler(len(dataset))
            elif shuffle:
                raise ValueError("shuffle must not be set if sampler is given")
            if last_batch is None:
                last_batch = "keep"
            batch_sampler = BatchSampler(sampler, batch_size, last_batch)
        elif batch_size is not None or shuffle or sampler is not None or \
                last_batch is not None:
            raise ValueError(
                "batch_size/shuffle/sampler/last_batch must not be set "
                "if batch_sampler is given")
        self._batch_sampler = batch_sampler
        self._num_workers = max(0, num_workers)
        self._thread_pool = thread_pool
        self._mp = (self._num_workers > 0 and not thread_pool
                    and hasattr(os, "fork"))
        self._fork_safe_cache = None
        self._default_batchify = batchify_fn is None
        if batchify_fn is None:
            batchify_fn = default_mp_batchify_fn if self._mp \
                else default_batchify_fn
        self._batchify_fn = batchify_fn
        self._prefetch = max(0, prefetch if prefetch is not None
                             else 2 * self._num_workers)

    def __len__(self):
        return len(self._batch_sampler)

    def _make_batch(self, indices):
        return self._batchify_fn([self._dataset[i] for i in indices])

    # ------------------------------------------------------------------
    def _make_batch_inproc(self, indices):
        """In-process fallback with the WORKER-side batchify (may yield
        numpy leaves — the shm hop's format); device-wrap so degraded
        batches look exactly like _unpack_shm output."""
        def to_device(b):
            if isinstance(b, np.ndarray):
                return nd.array(b, dtype=b.dtype)
            if isinstance(b, (list, tuple)):
                out = [to_device(x) for x in b]
                return tuple(out) if isinstance(b, tuple) else out
            if isinstance(b, dict):
                return {k: to_device(v) for k, v in b.items()}
            return b
        return to_device(self._batchify_fn(
            [self._dataset[i] for i in indices]))

    def _iter_multiprocess(self, batches):
        from ...config import get as _cfg

        ctx = multiprocessing.get_context("fork")
        task_q = ctx.Queue()
        res_q = ctx.Queue()
        seed_base = np.random.randint(0, 2 ** 31 - 1)
        generation = [0]
        spawned = [0]   # monotonic: a replacement never reuses a live
                        # worker's np.random stream

        def spawn():
            i = spawned[0]
            spawned[0] += 1
            w = ctx.Process(target=_worker_loop,
                            args=(self._dataset, self._batchify_fn, task_q,
                                  res_q, seed_base + i, generation[0]),
                            daemon=True)
            w.start()
            return w

        workers = [spawn() for _ in range(self._num_workers)]
        n = len(batches)
        inflight_cap = self._num_workers + self._prefetch
        pending = {}   # seq -> batch (reorder buffer: results keep order)
        sent = 0
        max_restarts = max(0, _cfg("MXNET_DATALOADER_RESTARTS"))
        restarts = 0
        degraded = False
        try:
            while sent < min(inflight_cap, n):
                task_q.put((sent, batches[sent]))
                sent += 1
            want = 0
            waited = 0.0
            while want < n:
                if degraded:
                    # worker pool gone: serve what already arrived, load
                    # the rest in-process (slow but correct)
                    yield pending.pop(want) if want in pending \
                        else self._make_batch_inproc(batches[want])
                    want += 1
                    continue
                if want in pending:
                    if sent < n:
                        task_q.put((sent, batches[sent]))
                        sent += 1
                    yield pending.pop(want)
                    want += 1
                    waited = 0.0
                    continue
                try:
                    seq, status, payload = res_q.get(timeout=1.0)
                except queue.Empty:
                    dead = [w for w in workers if not w.is_alive()]
                    if not dead:
                        waited += 1.0
                        if self._timeout and waited >= self._timeout:
                            raise RuntimeError(
                                "DataLoader batch %d not produced within "
                                "timeout=%ss (worker alive but stuck)"
                                % (want, self._timeout))
                        continue
                    # --- worker supervision -------------------------
                    import warnings
                    codes = [w.exitcode for w in dead]
                    workers = [w for w in workers if w.is_alive()]
                    restarts += len(dead)
                    if restarts > max_restarts:
                        warnings.warn(
                            "DataLoader: worker process(es) died "
                            "(exitcodes %s) and the restart budget "
                            "(MXNET_DATALOADER_RESTARTS=%d) is spent; "
                            "degrading to in-process loading for the "
                            "rest of this epoch" % (codes, max_restarts),
                            RuntimeWarning)
                        # keep results that already landed, then retire
                        # the surviving pool
                        try:
                            while True:
                                seq, status, payload = res_q.get_nowait()
                                if status == "ok":
                                    b = _unpack_shm(*payload)
                                    if seq >= want and seq not in pending:
                                        pending[seq] = b
                        except queue.Empty:
                            pass
                        for w in workers:
                            w.terminate()
                        degraded = True
                        continue
                    generation[0] += 1
                    warnings.warn(
                        "DataLoader: respawning %d dead worker(s) "
                        "(exitcodes %s; restart %d of %d)"
                        % (len(dead), codes, restarts, max_restarts),
                        RuntimeWarning)
                    for _ in range(len(dead)):
                        workers.append(spawn())
                    # resubmit every in-flight batch not yet delivered —
                    # the dead worker's task is unknowable, so resend all
                    # of them; duplicates are detected and dropped below
                    for s in range(want, sent):
                        if s not in pending:
                            task_q.put((s, batches[s]))
                    waited = 0.0   # the replacement starts a fresh clock
                    continue
                if status == "err":
                    raise RuntimeError(
                        "DataLoader worker failed:\n%s" % payload)
                if seq < want or seq in pending:
                    _unpack_shm(*payload)   # duplicate from a resubmit:
                    continue                # release its shm segment
                pending[seq] = _unpack_shm(*payload)
                waited = 0.0
        finally:
            for _ in workers:
                try:
                    task_q.put_nowait(None)
                except Exception:
                    pass
            for w in workers:
                w.join(timeout=1.0)
                if w.is_alive():
                    w.terminate()
            # drain + release any batches the workers produced after the
            # consumer stopped early (segments would otherwise leak
            # until /dev/shm fills)
            try:
                while True:
                    seq, status, payload = res_q.get_nowait()
                    if status == "ok":
                        _unpack_shm(*payload)
            except Exception:
                pass

    def _iter_threaded(self, batches):
        out_q: "queue.Queue" = queue.Queue(maxsize=max(self._prefetch, 2))
        stop = threading.Event()

        def worker():
            # a dataset/batchify exception must surface in the consumer
            # (review r5: a swallowed error silently truncated the
            # epoch), so errors ride the queue like the mp path
            try:
                for batch_idx in batches:
                    if stop.is_set():
                        break
                    out_q.put(("ok", self._make_batch(batch_idx)))
            except Exception:
                out_q.put(("err", traceback.format_exc()))
            else:
                out_q.put(None)

        t = threading.Thread(target=worker, daemon=True)
        t.start()
        try:
            while True:
                item = out_q.get(timeout=self._timeout)
                if item is None:
                    break
                status, payload = item
                if status == "err":
                    raise RuntimeError(
                        "DataLoader worker failed:\n%s" % payload)
                yield payload
        finally:
            stop.set()

    def _fork_safe(self, batches):
        """Probe ONE sample in the parent (cached): a dataset/transform
        chain that produces NDArrays (jax-backed) must NOT run in a
        forked child — XLA's runtime mutexes are not fork-safe and the
        worker deadlocks (os.fork + multithreaded JAX). Those pipelines
        get the threaded prefetcher instead. The probe reads
        batches[0][0] (already materialized — no sampler state is
        consumed) and the verdict is cached: the chain is fixed at
        construction."""
        if self._fork_safe_cache is not None:
            return self._fork_safe_cache

        def walk(v):
            if isinstance(v, NDArray):
                return True
            if isinstance(v, (list, tuple)):
                return any(walk(x) for x in v)
            if isinstance(v, dict):
                return any(walk(x) for x in v.values())
            return False

        try:
            sample = self._dataset[batches[0][0]] if batches else None
            safe = not walk(sample)
        except Exception:
            safe = True   # the worker will surface the real error
        if not safe:
            import warnings
            warnings.warn(
                "DataLoader: the dataset/transform chain produces "
                "device-backed NDArrays, which cannot run in forked "
                "worker processes (JAX is not fork-safe); using the "
                "threaded prefetcher for num_workers=%d instead. For "
                "real multiprocess workers, keep worker-side code "
                "numpy-only." % self._num_workers, RuntimeWarning)
            if self._default_batchify:
                # the mp default builds numpy batches for the shm hop;
                # in-process batches must be NDArrays
                self._batchify_fn = default_batchify_fn
        self._fork_safe_cache = safe
        return safe

    def _iter_batches(self):
        if self._num_workers == 0:
            for batch_idx in self._batch_sampler:
                yield self._make_batch(batch_idx)
            return
        # materialize ONCE: a generator batch_sampler must not lose
        # batch 0 to the fork-safety probe (review r5)
        batches = list(self._batch_sampler)
        if self._mp and self._fork_safe(batches):
            yield from self._iter_multiprocess(batches)
        else:
            yield from self._iter_threaded(batches)

    def _stage_batch(self, batch):
        """Touch every NDArray leaf so its host->device upload is
        dispatched NOW. jax.device_put is asynchronous: reading the
        buffer handle here starts the DMA without blocking, so by the
        time the consumer reaches a read-ahead batch its arrays are
        already resident in device memory and the upload overlapped
        the previous steps' compute."""
        if isinstance(batch, NDArray):
            batch._jax()
        elif isinstance(batch, (list, tuple)):
            for v in batch:
                self._stage_batch(v)
        elif isinstance(batch, dict):
            for v in batch.values():
                self._stage_batch(v)

    def __iter__(self):
        from collections import deque

        from ... import telemetry
        from ...config import get as _cfg

        # consumer-visible batch latency: the time THIS loop blocked
        # waiting for the next batch (0 when the prefetcher was ahead);
        # the exhausted final probe is not a batch and is not recorded
        it = self._iter_batches()
        depth = max(0, int(_cfg("MXNET_PREFETCH_DEPTH")))
        if depth == 0:
            while True:
                with telemetry.span("dataloader::next", "io",
                                    hist="mx_dataloader_batch_seconds") as sp:
                    try:
                        batch = next(it)
                    except StopIteration:
                        sp.cancel()
                        return
                yield batch
            return
        # MXNET_PREFETCH_DEPTH read-ahead: keep up to `depth` batches
        # pulled AND device-staged beyond the one being consumed. The
        # refill runs after each yield (while the consumer computes),
        # so worker batchify + host->device upload of batch n+1..n+d
        # overlap step n.
        ahead: deque = deque()
        exhausted = False
        while True:
            while not exhausted and len(ahead) < depth:
                with telemetry.span("dataloader::prefetch", "io") as sp:
                    try:
                        nxt = next(it)
                    except StopIteration:
                        sp.cancel()
                        exhausted = True
                        break
                    self._stage_batch(nxt)
                ahead.append(nxt)
            with telemetry.span("dataloader::next", "io",
                                hist="mx_dataloader_batch_seconds") as sp:
                if not ahead:
                    sp.cancel()
                    return
                batch = ahead.popleft()
            yield batch
