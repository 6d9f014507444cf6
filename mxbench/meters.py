"""Counts and clocks the benchmark takes itself: XLA compiles as
``jax.monitoring`` reports them, Pallas kernels in a compiled program,
the process's start, the device's peak memory. (CompileMeter and
kernel_counts are copies of chip_smoke.py's: the program may change
its smoke, not the yardstick.)"""
from __future__ import annotations

import collections
import os
import re
import time


class CompileMeter:
    """Every XLA backend compile of the process, the tiny eager-op
    programs included. A persistent-cache hit still passes through
    here, with a small duration, and counts as a hit."""
    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring
        self.count, self.seconds, self.cache_hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, seconds, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += seconds

    def _event(self, event, **_):
        if event == self.HIT:
            self.cache_hits += 1

    def mark(self):
        return self.count, self.seconds, self.cache_hits

    def since(self, mark):
        return (self.count - mark[0], self.seconds - mark[1],
                self.cache_hits - mark[2])


def kernel_counts(compiled_text: str) -> dict:
    """tpu_custom_call sites of a compiled program by Pallas kernel
    name (the pallas_call's ``name``, kept in the op_name metadata)."""
    names = collections.Counter()
    for line in compiled_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            op_name = re.search(r'op_name="([^"]*)"', line)
            found = re.findall(r"pallas_(?!call)\w+",
                               op_name.group(1) if op_name else "")
            names[found[-1] if found else "unnamed"] += 1
    return dict(names)


def process_age_s() -> float:
    """Seconds since this process started, from /proc (the interpreter's
    own start-up and the imports before the benchmark's first line are
    inside ``setup_s``)."""
    with open("/proc/self/stat") as f:
        # field 22, counted after the parenthesised command name
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


class SetupClock:
    """``setup_s``: process start to the first measured step."""

    def __init__(self):
        self._age0 = process_age_s()
        self._t0 = time.perf_counter()

    def now(self) -> float:
        return self._age0 + (time.perf_counter() - self._t0)


def peak_bytes(devices) -> int:
    """Peak bytes held on the fullest of ``devices``. On this stack
    ``peak_bytes_in_use`` counts live arrays only: the temporaries of a
    loaded program sit in a region the runtime reserves "at the bottom
    of memory" (``bytes_reserved``), disjoint from ``bytes_in_use``
    (PR 23: the b256 BERT step reserves 10.66 GB, its memory_analysis
    temporaries are 10.70 GB). So the peak is the larger of the live
    arrays' own peak and the live arrays now plus the largest
    reservation."""
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if not stats or "peak_bytes_in_use" not in stats:
            raise RuntimeError("device %s reports no peak_bytes_in_use" % d)
        peaks.append(max(int(stats["peak_bytes_in_use"]),
                         int(stats["bytes_in_use"])
                         + int(stats.get("peak_bytes_reserved", 0))))
    return max(peaks)
