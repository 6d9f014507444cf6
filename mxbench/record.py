"""What a generator hands back to ``run.py``, and what a per-layer
metric's reader is given."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple


@dataclasses.dataclass
class Run:
    """One run of one cell. A reader under ``layer_metrics/`` takes
    this and returns a number, or None where there is nothing to read
    (no trace in this run, a one-chip cell asked about collectives)."""
    cell: Dict[str, Any]
    sizes: Dict[str, Any]               # the configuration as run
    traffic: Dict[str, Any]             # the traffic mix as run
    device_kind: str
    chips: int                          # chips the cell used
    correct: bool
    attempted: int                      # steps or requests of the window
    failed: int
    end_to_end: Dict[str, Tuple[float, str]]   # name -> (value, unit)
    window_s: float                     # wall seconds of the window
    samples: int                        # samples through the window
    flops_per_sample: float             # model FLOPs, configs/<name>.py
    peak_bytes: int                     # fullest chip, after the window
    setup_compiles: int                 # XLA backend compiles in set-up
    setup_compile_s: float
    setup_cache_hits: int
    # traced run only: host seconds a step ("wall", "feed", "step",
    # "sync") of the same window run untraced just before the trace
    untraced_s_per_step: Optional[Dict[str, float]] = None
    trace: Optional[Any] = None         # mxbench.trace.Trace (traced run)
    trace_window: Optional[Tuple[float, float]] = None   # ns

    @property
    def traced_steps(self) -> int:
        from . import trace as T
        return T.count_spans(self.trace, "mxbench/step", self.trace_window)

    @property
    def busy_s_per_step(self) -> Optional[float]:
        """Seconds device 0 was busy per traced step: the device's work
        for a step, which tracing does not change."""
        from . import trace as T
        if self.trace is None or not self.traced_steps:
            return None
        return T.total(T.busy(self.trace, 0, self.trace_window)) / 1e9 \
            / self.traced_steps
