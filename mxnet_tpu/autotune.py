"""Measurement-driven kernel auto-tuner (ROADMAP item 3, round 7).

The Pallas kernel layer (pallas_norm / pallas_dropout / pallas_attention)
and the streaming chunked CE each carry hand-picked tiling constants —
LN/dropout row-block sizes, the attention head-block `_BB`,
`MXNET_CHUNKED_CE_CHUNK`. Those defaults were chosen
for the BERT-base flagship shape on one device kind; other shapes and
chips deserve other constants, and guessing them per call site does not
scale. This module replaces the guess with the cost-model idea of
"A Learned Performance Model for TPUs" (arxiv 2008.01040) applied to
the raw features compilewatch already captures — each compiled
program's ``cost_analysis()`` FLOPs and ``memory_analysis()`` bytes —
under the EQuARX-style measured-gate discipline PR 13 established: an
analytically promising candidate only enters the table if the device
clock agrees.

Modes (``MXNET_AUTOTUNE``):

* ``off`` (default) — :func:`lookup` returns the caller's default
  untouched. Byte-identical to the pre-autotune behavior: no table, no
  probe compiles, nothing consulted.
* ``cost`` — enumerate the caller's candidate grid, drop candidates
  whose working set cannot fit the VMEM budget, AOT-compile the
  survivors (plain ``jax.jit`` — probe programs never enter the
  compilewatch steady-state records) and score a roofline
  ``max(flops/peak_flops, hbm_bytes/peak_hbm_bw)`` from the compiled
  ``cost_analysis``/``memory_analysis`` (falling back to the caller's
  analytic estimates where the backend omits fields — the CPU mesh
  omits FLOPs on some programs, so determinism comes from the analytic
  numbers being always present). Lowest roofline wins; ties break on
  candidate order, so the choice is deterministic.
* ``measure`` — cost-rank first, then confirm on the device:
  the top candidates AND the incumbent default run interleaved
  paired rounds (tools/kernel_micro.py's method — a load spike
  inflates both halves of a round and cancels in the ratio) and the
  tuned candidate is kept only if its paired-median beats the
  default's. A candidate that loses the measurement gate never enters
  the table, no matter how good its roofline looked.

Decisions persist per ``(device_kind, kernel, shape-signature)`` in a
process-wide table, optionally backed by a JSON file
(``MXNET_AUTOTUNE_CACHE``) so one tuning pass serves every later
process on the same machine. A cache entry that fails the caller's
validation (stale file, edited by hand, different kernel version) is
ignored and the default is used — a bogus table can degrade perf but
never correctness. Consumers therefore always pass a ``validate``
callable and treat :func:`lookup`'s answer as advisory.
"""
from __future__ import annotations

import json
import logging
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

__all__ = ["lookup", "Candidate", "mode", "table", "clear",
           "entry_key", "cache_path", "tuned_rows"]

_LOG = logging.getLogger("mxnet_tpu.autotune")

_LOCK = threading.RLock()
# entry_key -> {"params": dict, "mode": str, "score": float}
_TABLE: Dict[str, dict] = {}
_LOADED_FROM: Optional[str] = None    # cache file already merged in

# VMEM working-set budget for candidate feasibility — matches the ~10 MB
# double-buffered budget the hand-written _pick_rows heuristics target
# (the other ~6 MB of the 16 MB VMEM belongs to Mosaic's own pipelining).
_VMEM_BUDGET = 10 * 1024 * 1024

# Roofline denominator for HBM bytes, per device kind (bytes/s). The
# absolute numbers only matter relative to peak_flops — the roofline
# RANKS candidates, it does not predict wall time.
_HBM_BW_BY_KIND = (("v5e", 819e9), ("v5p", 2765e9), ("v4", 1228e9),
                   ("v3", 900e9), ("v6", 1600e9))
_HBM_BW_FALLBACK = 819e9
_PEAK_FLOPS_FALLBACK = 197e12


class Candidate:
    """One tuning candidate.

    params      : dict the consumer plugs into its kernel build.
    flops       : analytic FLOPs of the candidate program (fallback
                  when the compiled cost_analysis omits the field).
    hbm_bytes   : analytic HBM traffic (same fallback role).
    vmem_bytes  : analytic VMEM working set — the feasibility gate.
    build       : None, or a zero-arg callable returning
                  ``(fn, example_args)`` where ``fn(*example_args)``
                  is the candidate program. Used for the probe compile
                  (cost mode) and the paired measurement (measure
                  mode); example_args must be concrete arrays.
    opaque      : the program is a Pallas kernel. XLA's cost analysis
                  cannot see into it — an opaque custom call on the
                  chip, the interpreter's grid loop (body counted
                  once, so small blocks look cheap) on the CPU — so
                  the candidate scores on its analytic features and
                  the probe compile is a feasibility check only.
    """

    __slots__ = ("params", "flops", "hbm_bytes", "vmem_bytes", "build",
                 "opaque")

    def __init__(self, params: dict, flops: float = 0.0,
                 hbm_bytes: float = 0.0, vmem_bytes: float = 0.0,
                 build: Optional[Callable] = None,
                 opaque: bool = False):
        self.opaque = bool(opaque)
        self.params = dict(params)
        self.flops = float(flops)
        self.hbm_bytes = float(hbm_bytes)
        self.vmem_bytes = float(vmem_bytes)
        self.build = build


# ---------------------------------------------------------------------------
# mode / keys / persistence
# ---------------------------------------------------------------------------
def mode() -> str:
    from .config import get as _cfg
    m = str(_cfg("MXNET_AUTOTUNE")).lower()
    return m if m in ("off", "cost", "measure") else "off"


def _device_kind() -> str:
    try:
        import jax
        return jax.devices()[0].device_kind.replace(" ", "_")
    except Exception:
        return "unknown"


def entry_key(kernel: str, key: Dict[str, Any]) -> str:
    sig = ",".join("%s=%s" % (k, key[k]) for k in sorted(key))
    return "%s|%s|%s" % (_device_kind(), kernel, sig)


def cache_path() -> str:
    from .config import get as _cfg
    return str(_cfg("MXNET_AUTOTUNE_CACHE") or "")


def _load_cache_locked():
    """Merge the JSON cache file into the process table (once per
    path; a changed MXNET_AUTOTUNE_CACHE re-merges)."""
    global _LOADED_FROM
    path = cache_path()
    if not path or _LOADED_FROM == path:
        return
    _LOADED_FROM = path
    if not os.path.exists(path):
        return
    try:
        with open(path) as f:
            data = json.load(f)
        if isinstance(data, dict):
            for k, v in data.items():
                if isinstance(v, dict) and isinstance(
                        v.get("params"), dict):
                    _TABLE.setdefault(k, v)
    except Exception as e:
        _LOG.warning("autotune: unreadable cache %s (%s: %s) — ignored",
                     path, type(e).__name__, e)


def _save_cache_locked():
    path = cache_path()
    if not path:
        return
    try:
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = path + ".tmp.%d" % os.getpid()
        with open(tmp, "w") as f:
            json.dump(_TABLE, f, indent=1, sort_keys=True)
        os.replace(tmp, path)      # atomic publish (profiler.dump idiom)
    except Exception as e:
        _LOG.warning("autotune: cannot write cache %s (%s: %s)",
                     path, type(e).__name__, e)


def table() -> Dict[str, dict]:
    """Copy of the current tuning table (introspection/tests)."""
    with _LOCK:
        return {k: dict(v) for k, v in _TABLE.items()}


def clear():
    """Drop the in-memory table and forget the merged cache path
    (test isolation; the JSON file on disk is untouched)."""
    global _LOADED_FROM
    with _LOCK:
        _TABLE.clear()
        _LOADED_FROM = None


# ---------------------------------------------------------------------------
# scoring
# ---------------------------------------------------------------------------
def _peaks():
    """(FLOP/s, bytes/s) the roofline RANKS candidates with — never
    reported. An unknown device kind (the CPU mesh) ranks with the v5e
    pair: only the ratio of the two matters."""
    import jax
    from . import telemetry
    kind = jax.devices()[0].device_kind.lower()
    pf = telemetry.known_peak_flops() or _PEAK_FLOPS_FALLBACK
    bw = next((v for marker, v in _HBM_BW_BY_KIND if marker in kind),
              _HBM_BW_FALLBACK)
    return pf, bw


def _aot_probe(fn, example_args):
    """AOT-compile one candidate program and return (flops, bytes) from
    its cost/memory analysis — None where the backend omits a field.
    Plain jax.jit on purpose: probe programs must not look like
    steady-state recompiles to compilewatch."""
    import jax
    from .compilewatch import _extract_cost, _extract_memory
    compiled = jax.jit(fn).lower(*example_args).compile()
    flops = _extract_cost(compiled)
    mem = _extract_memory(compiled)
    hbm = sum(v for k, v in mem.items() if k != "code") or None
    return compiled, flops, hbm


def _roofline(cand: Candidate, flops, hbm, peak_flops, peak_bw) -> float:
    f = flops if flops else cand.flops
    b = hbm if hbm else cand.hbm_bytes
    return max(f / max(peak_flops, 1.0), b / max(peak_bw, 1.0))


def _score_cost(cands: Sequence[Candidate]):
    """Roofline-score every VMEM-feasible candidate; returns
    [(score, index, candidate, compiled_or_None)] sorted best-first
    (ties break on candidate order — deterministic, so enumerators
    list their preferred fallback FIRST). A candidate whose probe
    program fails to compile is DISQUALIFIED — the consumer would hit
    the same failure on the real kernel build; build=None candidates
    score on their analytic features alone."""
    peak_flops, peak_bw = _peaks()
    scored = []
    for i, c in enumerate(cands):
        if c.vmem_bytes > _VMEM_BUDGET:
            continue
        compiled = flops = hbm = None
        if c.build is not None:
            try:
                fn, args = c.build()
                compiled, flops, hbm = _aot_probe(fn, args)
                if c.opaque:
                    flops = hbm = None
            except Exception as e:
                _LOG.debug("autotune: probe compile failed for %r "
                           "(%s: %s) — candidate disqualified",
                           c.params, type(e).__name__, e)
                continue
        scored.append((_roofline(c, flops, hbm, peak_flops, peak_bw),
                       i, c, compiled))
    scored.sort(key=lambda t: (t[0], t[1]))
    return scored


def _paired_median(num, den):
    ratios = sorted(n / d for n, d in zip(num, den))
    m = len(ratios) // 2
    return ratios[m] if len(ratios) % 2 else \
        (ratios[m - 1] + ratios[m]) / 2.0


def _time_once(runner, args) -> float:
    import jax
    t0 = time.perf_counter()
    out = runner(*args)
    jax.tree_util.tree_map(
        lambda a: a.block_until_ready()
        if hasattr(a, "block_until_ready") else a, out)
    return time.perf_counter() - t0


def _measure(cand: Candidate, base: Candidate, repeats: int = 5) -> \
        Optional[float]:
    """Paired-median wall ratio candidate/default on the attached
    device (kernel_micro method: interleaved rounds). None when either
    side cannot be built."""
    if cand.build is None or base.build is None:
        return None
    try:
        c_fn, c_args = cand.build()
        b_fn, b_args = base.build()
        import jax
        c_run = jax.jit(c_fn)
        b_run = jax.jit(b_fn)
        _time_once(c_run, c_args)      # warmup compiles both
        _time_once(b_run, b_args)
        tc, tb = [], []
        for _ in range(repeats):
            tc.append(_time_once(c_run, c_args))
            tb.append(_time_once(b_run, b_args))
        return _paired_median(tc, tb)
    except Exception as e:
        _LOG.debug("autotune: measurement failed for %r (%s: %s)",
                   cand.params, type(e).__name__, e)
        return None


# ---------------------------------------------------------------------------
# the consult point
# ---------------------------------------------------------------------------
def lookup(kernel: str, key: Dict[str, Any], default: Dict[str, Any],
           candidates: Optional[Callable[[], List[Candidate]]] = None,
           validate: Optional[Callable[[Dict[str, Any]], bool]] = None,
           measure_top: int = 2) -> Dict[str, Any]:
    """Tuned params for ``(kernel, key)`` — or ``default``.

    ``off`` mode and every failure path return ``default`` untouched,
    so consumers behave byte-identically to the pre-autotune code
    unless a valid table entry exists. ``candidates`` is a lazy
    enumerator (only invoked when this signature actually needs
    tuning); ``validate`` re-checks any table entry against the
    consumer's feasibility rules (a bogus cache entry falls back to
    the default instead of crashing the kernel build).
    """
    m = mode()
    if m == "off":
        return default
    ek = entry_key(kernel, key)
    with _LOCK:
        _load_cache_locked()
        entry = _TABLE.get(ek)
    if entry is not None:
        params = entry.get("params")
        if isinstance(params, dict) and \
                (validate is None or _safe_validate(validate, params)):
            return dict(params)
        _LOG.warning("autotune: table entry for %s failed "
                     "validation (%r) — using the default", ek, params)
        return default
    if candidates is None:
        return default
    try:
        cands = list(candidates())
    except Exception as e:
        _LOG.warning("autotune: candidate enumeration failed for "
                     "%s (%s: %s) — using the default", ek,
                     type(e).__name__, e)
        return default
    # tune OUTSIDE the lock: probe compiles and paired measurement take
    # seconds, and a cache-hit lookup on another thread must not stall
    # behind them. Two threads racing the same untabled signature both
    # tune (deterministic result) and first-publish wins.
    chosen, score = _tune(m, cands, default, measure_top)
    with _LOCK:
        entry = _TABLE.get(ek)
        if entry is None:
            _TABLE[ek] = {"params": dict(chosen), "mode": m,
                          "score": score}
            _save_cache_locked()
            return dict(chosen)
        params = entry.get("params")
        if isinstance(params, dict) and \
                (validate is None or _safe_validate(validate, params)):
            return dict(params)
        return default


def _safe_validate(validate, params) -> bool:
    try:
        return bool(validate(params))
    except Exception:
        return False


def _tune(m: str, cands: List[Candidate],
          default: Dict[str, Any], measure_top: int = 2):
    """Pick params from the candidate grid (cost ranking, optionally
    measurement-confirmed). The default always competes: an empty or
    fully-infeasible grid resolves to it."""
    scored = _score_cost(cands)
    if not scored:
        return default, 0.0
    best_score, _, best, _ = scored[0]
    if m == "cost":
        return best.params, best_score
    # measure mode: the incumbent default is the bar, found in the
    # grid by params equality. If the grid does not carry the default
    # there is nothing to measure AGAINST — the gate discipline says an
    # unvetted candidate never replaces the default, so keep it.
    base = None
    for c in cands:
        if c.params == default:
            base = c
            break
    if base is None:
        _LOG.info("autotune: default %r absent from the candidate "
                  "grid — keeping it unmeasured (measure-mode gate)",
                  default)
        return default, 0.0
    picked, picked_score = default, 0.0
    best_ratio = 1.0
    for score, _, c, _ in scored[:max(1, measure_top)]:
        if c.params == default:
            continue
        ratio = _measure(c, base)
        if ratio is not None and ratio < best_ratio:
            best_ratio = ratio
            picked, picked_score = c.params, score
    if picked is default:
        _LOG.info("autotune: no candidate beat the default on the "
                  "paired measurement — keeping the default")
    return picked, picked_score


# ---------------------------------------------------------------------------
# shared consult for row-blocked elementwise kernels (pallas_norm,
# pallas_dropout): ONE candidate grid, ONE validation
# — a cached entry must clear the same sublane-floor and VMEM rules as
# a freshly picked block, so a stale/hand-edited table can degrade perf
# but never crash a kernel build (the module contract).
# ---------------------------------------------------------------------------
_ROW_GRID = (1024, 512, 256, 128, 64, 32, 16, 8)


def tuned_rows(kernel: str, M: int, C: int, esize: int, default,
               per_row_bytes: int, *, extra_bytes: int = 0,
               floor: Optional[int] = None, flops: float = 0.0,
               hbm_bytes: float = 0.0,
               probe: Optional[Callable[[int], Callable]] = None):
    """Tuned row-block size for an (M, C) sweep kernel — or
    ``default``. ``per_row_bytes`` is the VMEM working set per row
    (both buffers of the double-buffered pipeline are charged);
    ``floor`` defaults to the dtype sublane rule (16 rows below f32);
    ``probe(bm)`` builds the cost-mode probe program."""
    if floor is None:
        floor = 8 if esize >= 4 else 16

    def _fits(bm):
        return bm * per_row_bytes * 2 + extra_bytes <= _VMEM_BUDGET

    def _candidates():
        return [Candidate({"block_rows": bm}, flops=flops,
                          hbm_bytes=hbm_bytes,
                          vmem_bytes=bm * per_row_bytes * 2
                          + extra_bytes,
                          build=None if probe is None else probe(bm),
                          opaque=True)
                for bm in _ROW_GRID
                if bm >= floor and M % bm == 0]

    def _valid(params):
        bm = params.get("block_rows")
        return (isinstance(bm, int) and bm >= floor and M % bm == 0
                and _fits(bm))

    out = lookup(kernel, {"M": M, "C": C, "esize": esize},
                 {"block_rows": default}, candidates=_candidates,
                 validate=_valid)
    bm = out.get("block_rows", default)
    if bm is None:
        return default
    return bm if isinstance(bm, int) and bm >= 1 and M % bm == 0 \
        else default
