"""Structured runtime configuration (SURVEY §5.6 rebuild note).

The reference reads `MXNET_*`/`DMLC_*` environment variables ad hoc via
`dmlc::GetEnv` scattered through the C++ core (canonical list only in
docs/faq/env_var.md). Here every honored variable is DECLARED in one
place — name, type, default, docstring — and every read site routes
through :func:`get`. Reads are live (each call consults the
environment), so tests and launchers that mutate ``os.environ`` keep
working; the declaration layer adds typing, defaults, and
discoverability (``python -m mxnet_tpu.config`` prints the docs table;
``describe()`` returns it).

The ONLY other place the package touches ``os.environ`` is the
XLA_FLAGS bootstrap in :mod:`mxnet_tpu.dist` (it must mutate the
environment before the jax backend initializes — an env WRITE, not a
config read) and :func:`setenv` below (the ``mx.util.setenv`` API).
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Dict, Optional

__all__ = ["Var", "VARS", "define", "get", "getenv_raw", "setenv",
           "describe"]

_FALSY = ("0", "false", "off", "no", "")


@dataclass(frozen=True)
class Var:
    name: str
    type: type
    default: Any
    doc: str

    def parse(self, raw: Optional[str]):
        if raw is None:
            return self.default
        if self.type is bool:
            return raw.lower() not in _FALSY
        if self.type is int:
            return int(raw)
        if self.type is float:
            return float(raw)
        return raw


VARS: Dict[str, Var] = {}


def define(name: str, type: type, default: Any, doc: str) -> Var:
    v = Var(name, type, default, doc)
    VARS[name] = v
    return v


def get(name: str):
    """Typed live read of a declared variable."""
    var = VARS.get(name)
    if var is None:
        raise KeyError("undeclared config variable %r — declare it in "
                       "mxnet_tpu/config.py" % name)
    return var.parse(os.environ.get(name))


def getenv_raw(name: str, default=None):
    """Raw passthrough for UNdeclared variables (reference
    `mx.util.getenv` parity; prefer declared vars + :func:`get`)."""
    return os.environ.get(name, default)


def setenv(name: str, value: str):
    """Reference `mx.util.setenv` parity."""
    os.environ[name] = value


def environ_snapshot(prefixes: tuple) -> Dict[str, str]:
    """Sorted {name: value} of every environment variable starting
    with one of `prefixes` — the crash-bundle env capture
    (telemetry.crash_bundle). Bulk reads live here so the
    'os.environ only in config.py' discipline stays greppable."""
    return {k: os.environ[k] for k in sorted(os.environ)
            if k.startswith(prefixes)}


def apply_overrides(env: Optional[Dict[str, str]]) -> None:
    """Write `env` into os.environ — the replica-spawn path
    (serve/fleet.py replica_main): a child process applies its spec's
    env overrides (fault arming, platform pins) before any config or
    jax read. Bulk WRITES live here so the 'os.environ only in
    config.py' discipline stays greppable."""
    for k, v in (env or {}).items():
        os.environ[str(k)] = str(v)


def describe() -> str:
    """Markdown table of every declared variable (the docs page the
    reference keeps in docs/faq/env_var.md)."""
    rows = ["| variable | type | default | description |",
            "|---|---|---|---|"]
    for v in sorted(VARS.values(), key=lambda v: v.name):
        rows.append("| `%s` | %s | `%r` | %s |"
                    % (v.name, v.type.__name__, v.default, v.doc))
    return "\n".join(rows)


# ---------------------------------------------------------------------------
# The declarations. Grouped as the reference's env_var.md does.
# ---------------------------------------------------------------------------
# --- engine / scheduler (ref: MXNET_ENGINE_TYPE et al.) ---
define("MXNET_ENGINE_TYPE", str, "",
       "Set to 'NaiveEngine' for synchronous single-thread execution "
       "(deterministic debugging; ref naive_engine.cc). Default: the "
       "native threaded dependency engine.")
define("MXNET_CPU_WORKER_NTHREADS", int, 2,
       "Worker threads of the native dependency engine (ref name).")
define("MXNET_CUSTOM_OP_NUM_THREADS", int, 0,
       "Workers executing Python custom ops (ref custom-op thread "
       "pool); 0 = inherit MXNET_CPU_WORKER_NTHREADS.")
# --- compute-path toggles ---
define("MXNET_LAYOUT_OPT", bool, True,
       "NHWC layout pass on traced conv graphs (symbol/layout_opt.py; "
       "the cuDNN-NHWC analogue).")
define("MXNET_CONV_S2D", bool, True,
       "Rewrite 7x7/s2/p3 small-C stems as 2x2 space-to-depth convs "
       "(MLPerf stem; algorithm selection like cudnn_tune).")
define("MXNET_FLASH_ATTENTION", bool, True,
       "Use the Pallas flash self-attention kernel where eligible; off "
       "falls back to the unfused interleaved-matmul composition.")
define("MXNET_FUSED_BACKWARD", bool, True,
       "Fuse the deferred autograd tape (CachedOp chains) into one "
       "fwd+bwd XLA program at backward() (autograd.py).")
define("MXNET_SHARDED_AUTO_LAYOUT", bool, True,
       "Let XLA pick parameter layouts for ShardedTrainStep on TPU "
       "(AUTO layouts).")
define("MXNET_PALLAS_INTERPRET", bool, False,
       "Run Pallas kernels in interpreter mode (CPU testing).")
define("MXNET_PALLAS_LAYERNORM", bool, True,
       "Serve LayerNorm with the Pallas single-sweep fwd/bwd kernels "
       "(ops/pallas_norm.py) when the shape tiles cleanly; off (or "
       "ineligible shapes) falls back to the fused-VJP XLA path with "
       "identical formulas (docs/KERNELS.md).")
define("MXNET_PALLAS_DROPOUT", bool, True,
       "Generate dropout masks inside a Pallas kernel with the TPU "
       "hardware PRNG (ops/pallas_dropout.py): no standalone "
       "rng-bit-generator programs and no mask HBM round-trip (the "
       "backward regenerates the mask from the saved seeds). Only "
       "active on a real TPU; CPU and ineligible shapes fall back to "
       "the jax.random path.")
define("MXNET_AUTOTUNE", str, "off",
       "Kernel auto-tuner mode (mxnet_tpu/autotune.py): 'off' "
       "(default) keeps every hand-picked kernel constant — "
       "byte-identical to the untuned behavior; 'cost' picks "
       "VMEM-feasible Pallas block shapes / the CE chunk size by a "
       "deterministic roofline over each candidate program's compiled "
       "cost_analysis/memory_analysis (the arxiv 2008.01040 feature "
       "set compilewatch already captures); 'measure' additionally "
       "confirms the top candidates against the incumbent default "
       "with paired-median wall timing on the attached device — a "
       "tuned candidate must beat the default or the table keeps the "
       "default (docs/KERNELS.md 'Kernel auto-tuning').")
define("MXNET_AUTOTUNE_CACHE", str, "",
       "JSON file persisting the autotune table across processes, "
       "keyed (device_kind, kernel, shape-signature). Empty keeps "
       "decisions in-process only. Entries failing the consumer's "
       "validation (stale/hand-edited) are ignored in favor of the "
       "defaults.")
define("MXNET_CHUNKED_CE", bool, True,
       "Model-zoo BERT MLM head uses the streaming chunked LM-head "
       "cross entropy (_contrib_chunked_lm_head_ce): online-softmax "
       "over vocab chunks so the (positions, vocab) logits never fully "
       "materialize in HBM; off falls back to the dense decoder + "
       "log_softmax + pick composition (docs/KERNELS.md).")
define("MXNET_CHUNKED_CE_CHUNK", int, 4096,
       "Vocab chunk size for _contrib_chunked_lm_head_ce when the "
       "caller does not pass one (vocab is padded up to a whole number "
       "of chunks; padding rides as -1e30 bias logits).")
define("MXNET_PRNG_IMPL", str, "rbg",
       "jax PRNG implementation for random ops ('rbg' hardware PRNG or "
       "'threefry2x32').")
# --- optimizer / trainer ---
define("MXNET_OPTIMIZER_AGGREGATION_SIZE", int, 4096,
       "Multi-tensor update chunk size (ref aggregate_num; one fused "
       "program per chunk — default batches every parameter).")
define("MXNET_TRAINER_FUSED_UPDATE", bool, True,
       "Gluon hybridize+Trainer loops execute the multi-tensor "
       "optimizer INSIDE the compiled fwd+bwd program (one XLA "
       "program per step, no separate optimizer dispatch re-reading "
       "w/g/m from HBM — that program: 0.49 ms on ResNet-50, round-5 "
       "builder figure). Engages only when the kvstore resolves to "
       "the local single-device path with update_on_kvstore=False, "
       "the optimizer has a fused in-graph form (SGD), every trained "
       "parameter has grad_req='write' and no GradGuard is active; "
       "anything else falls back to the reference-idiomatic separate "
       "optimizer program. Between backward() and step() gradients "
       "are deferred; reading them through Parameter.grad()/"
       "list_grad() flushes the pending program first "
       "(docs/KERNELS.md). The program donates the buffers the step "
       "overwrites (weights, momenta, the last step's gradients, "
       "BatchNorm's running statistics) whenever their handles are "
       "their only holders, so its outputs take them and nothing is "
       "allocated; a step that finds another holder (a detach(), a "
       "same-device copy, a serving session's capture) runs the "
       "variant that donates nothing, once, and every handle taken "
       "before a step stays readable after it (docs/TRAINING.md "
       "'What the fused step donates'; mx_fused_step_total).")
define("MXNET_PREFETCH_DEPTH", int, 2,
       "DataLoader device double-buffer: stage up to this many "
       "upcoming batches into device memory ahead of the consumer "
       "(gluon/data/dataloader.py), so the host upload overlaps the "
       "previous steps' compute. 0 disables read-ahead (batches are "
       "uploaded on demand).")
define("MXNET_ZERO", bool, False,
       "ZeRO-style weight-update sharding for the data-parallel Gluon "
       "Trainer (gluon/zero.py; arxiv 2004.13336): gradients are "
       "reduce-scattered over the replica set, each replica owns a 1/N "
       "shard of the flattened parameter/optimizer-state space "
       "(momentum and Adam m/v are ALLOCATED sharded, never "
       "materialized whole), runs the update on its shard only, and "
       "the updated parameters are all-gathered back — same total comm "
       "traffic as plain allreduce (RS+AG), ~N x less optimizer-state "
       "HBM and 1/N update FLOPs per replica. Engages only when the "
       "Trainer is eligible (>=2 distinct-device replicas, in-process "
       "kvstore, dense grad_req='write' params, an optimizer with an "
       "elementwise in-graph fragment form: SGD[+momentum], Adam); "
       "anything else falls back to the replicated path with one "
       "warning (docs/ZERO.md eligibility ladder).")
define("MXNET_ZERO_DCN", int, 0,
       "With MXNET_ZERO: treat the replica set as a dcn x ici "
       "hierarchy of this many slices (must divide the replica count; "
       "0/1 = flat). The reduce-scatter/all-gather then stage over "
       "('dcn','dp') — RS(ici)->RS(dcn) and AG(dcn)->AG(ici), the "
       "arxiv 2112.01075 redistribution decomposition — so the "
       "cross-slice tier only ever carries 1/n_ici of the gradient "
       "bytes (docs/ZERO.md).")
define("MXNET_ZERO_MIN_SIZE", int, 0,
       "With MXNET_ZERO: skip sharding when the total trained "
       "parameter element count is below this (tiny models pay the "
       "RS/AG latency without a meaningful memory win); 0 shards "
       "whenever eligible.")
# --- elastic topology (parallel/reshard.py, elastic.py) ---
define("MXNET_ELASTIC", bool, False,
       "Elastic-topology training (elastic.py, docs/ELASTIC.md): the "
       "Estimator fit loop polls for a preemption notice (programmatic "
       "flag, coordination-service KV flag 'mx/elastic/preempt' via "
       "dist.py, or SIGTERM when MXNET_ELASTIC_SIGTERM is set) and, "
       "when one names a surviving device subset, reshards the live "
       "run onto it in place — drain engine work, redistribute params "
       "+ optimizer state + EF residuals through the staged "
       "parallel/reshard.py pass (arxiv 2112.01075), rebuild the "
       "kvstore mesh and watched programs, continue stepping. A failed "
       "transition degrades to checkpoint-restore "
       "(model.load_latest_checkpoint) instead of aborting.")
define("MXNET_ELASTIC_POLL", int, 1,
       "With MXNET_ELASTIC: poll for a preemption notice every this "
       "many trainer steps (1 = every step; the poll is a host-side "
       "flag check, the coordination-service KV read only happens in "
       "multi-process runs).")
define("MXNET_ELASTIC_BLOCK", int, 4 << 20,
       "Staged-redistribution block size in BYTES for "
       "parallel/reshard.py: device-to-device fragment moves are "
       "chunked so peak live memory on any device stays <= destination "
       "shard size + one staged block (the arxiv 2112.01075 bound, "
       "gated by tools/reshard_micro.py). Also caps the host staging "
       "buffer on checkpoint-restore resharding.")
define("MXNET_ELASTIC_MIN_DEVICES", int, 1,
       "With MXNET_ELASTIC: smallest survivor set a live reshard will "
       "target; a preemption notice leaving fewer devices degrades "
       "straight to checkpoint-restore (docs/ELASTIC.md).")
define("MXNET_ELASTIC_SIGTERM", bool, False,
       "With MXNET_ELASTIC: additionally install a SIGTERM handler "
       "that raises the preemption flag (survivors = the configured "
       "default shrink, see docs/ELASTIC.md). Off by default so "
       "library import never hijacks process signal handlers.")
# --- kvstore / distribution (ref: kvstore env family + DMLC_*) ---
define("MXNET_KVSTORE_QUANTIZE", str, "off",
       "Quantized gradient synchronization (parallel/quantize.py, "
       "docs/QUANTIZE.md; EQuARX, arxiv 2506.17615): 'int8' or 'fp8' "
       "puts the grad-sync WIRE payload in 1-byte blocks (per-block "
       "absmax f32 scale sidecar) composed as reduce-scatter in low "
       "precision -> shard-local dequant-accumulate in f32 -> "
       "all-gather of the re-quantized result, with per-replica "
       "error-feedback residuals carried into the next step so the "
       "scheme is convergence-safe. Wired through the kvstore grouped "
       "reduces, the MXNET_ZERO RS->update->AG program (residuals ride "
       "checkpoints) and the hierarchical dcn x ici staging. 'off' "
       "(default) keeps every sync path byte-for-byte the classic f32 "
       "one (tools/quant_micro.py gates both claims).")
define("MXNET_KVSTORE_QUANTIZE_TIER", str, "dcn",
       "Which hops of a STAGED (dcn x ici) quantized sync carry the "
       "low-precision payload: 'dcn' (default) quantizes only the "
       "cross-slice DCN hop — ICI is rarely the bottleneck — while "
       "'all' quantizes every hop. A flat single-tier sync (the plain "
       "data-parallel allreduce) is its own outermost tier and is "
       "quantized under either setting.")
define("MXNET_KVSTORE_QUANTIZE_BLOCK", int, 256,
       "Elements per absmax scale block for MXNET_KVSTORE_QUANTIZE "
       "(one f32 scale per block rides the wire: sidecar overhead "
       "4/BLOCK bytes/element; a non-finite gradient poisons at most "
       "one block, which the GradGuard check on the dequantized "
       "result then names).")
define("MXNET_KVSTORE_QUANTIZE_STOCHASTIC", bool, False,
       "Stochastic rounding for the int8 quantizer (unbiased E[q]=x "
       "instead of round-to-nearest; decorrelated per replica). fp8 "
       "mode ignores this (the e4m3 cast rounds to nearest even).")
define("MXNET_KVSTORE_BIGARRAY_BOUND", int, 1 << 19,
       "Arrays larger than this split into slices for priority "
       "propagation (P3; ref p3store_dist.h).")
define("DMLC_ROLE", str, "worker",
       "Process role in a launched cluster: scheduler|server|worker "
       "(ref ps-lite rendezvous).")
define("DMLC_PS_ROOT_URI", str, "127.0.0.1",
       "Rendezvous host (ref ps-lite).")
define("DMLC_PS_ROOT_PORT", int, 9091, "Rendezvous port (ref ps-lite).")
define("DMLC_NUM_WORKER", int, 1, "World size (ref ps-lite).")
define("DMLC_NUM_SERVER", int, 0,
       "Server count (accepted for launcher parity; the TPU backend "
       "has no parameter-server processes — SURVEY §5.8).")
define("DMLC_WORKER_ID", int, 0, "This worker's rank (ref ps-lite).")
# --- fault tolerance (docs/FAULT_TOLERANCE.md) ---
define("MXNET_CKPT_KEEP", int, 0,
       "Checkpoint retention window per prefix: keep only the newest N "
       "manifest entries and delete pruned .params files (0 = keep "
       "all; save_checkpoint's max_keep argument overrides).")
define("MXNET_DIST_INIT_TIMEOUT", float, 300.0,
       "Overall deadline in seconds for dist.initialize() rendezvous "
       "(retries with exponential backoff until this elapses, then "
       "raises MXNetError instead of hanging).")
define("MXNET_DIST_INIT_BACKOFF", float, 1.0,
       "Initial rendezvous retry backoff in seconds; doubles per "
       "attempt, capped at 30s.")
define("MXNET_DIST_INIT_RETRIES", int, 0,
       "Max rendezvous attempts for dist.initialize() (0 = unlimited "
       "until MXNET_DIST_INIT_TIMEOUT).")
define("MXNET_BARRIER_TIMEOUT", float, 600.0,
       "dist.barrier() watchdog in seconds: raise a diagnosable "
       "MXNetError instead of hanging forever on a dead rank (0 "
       "disables the watchdog).")
define("MXNET_DATALOADER_RESTARTS", int, 2,
       "Restart budget for dead DataLoader worker processes per epoch; "
       "once exhausted the loader degrades to in-process loading with "
       "a warning instead of hanging.")
define("MXNET_FAULT_INJECT", str, "",
       "Fault-injection spec 'site:prob[:max_fires],...' (e.g. "
       "'ckpt_write:0.5,dl_worker:1'); sites documented in "
       "mxnet_tpu/faultinject.py.")
define("MXNET_FAULT_INJECT_SEED", int, 0,
       "Seed for the fault-injection probability draws (deterministic "
       "chaos runs).")
# --- training guardrails (docs/GUARDRAILS.md) ---
define("MXNET_GUARD_NONFINITE", str, "off",
       "Non-finite gradient policy applied by guardrails.GradGuard at "
       "Trainer.step/Module.update: 'off' (no check), 'raise' (MXNetError "
       "naming the offending parameters), 'skip_step' (drop the update, "
       "count it), 'zero' (zero the bad gradients and proceed).")
define("MXNET_GUARD_CLIP_NORM", float, 0.0,
       "Global-gradient-norm clip threshold for GradGuard (fused into "
       "the same single per-step reduction as the finiteness check); "
       "0 disables clipping.")
define("MXNET_GUARD_LOSS_SPIKE", float, 0.0,
       "Loss-spike factor: GradGuard.observe_loss emits a 'loss_spike' "
       "guard event when the observed loss exceeds factor x the rolling "
       "mean (0 disables; reading the loss adds one host sync per "
       "observation).")
define("MXNET_GUARD_LOSS_WINDOW", int, 50,
       "Rolling window (in observations) for the GradGuard loss-spike "
       "detector.")
define("MXNET_GUARD_COMM_VOTE", bool, False,
       "Pre-allreduce finiteness vote in the dist kvstore: a non-finite "
       "gradient raises on every rank NAMING the originating rank(s) "
       "instead of silently corrupting the global model (adds one device "
       "sync plus a tiny collective per guarded call).")
define("MXNET_ENGINE_WATCHDOG", float, 0.0,
       "Native dependency-engine wait watchdog in seconds: a "
       "wait_for_var/wait_for_all exceeding the deadline dumps "
       "pending-op/var diagnostics (labels + enqueue sites) and raises "
       "MXNetError instead of hanging forever (0 disables).")
define("MXNET_KVSTORE_TIMEOUT", float, 0.0,
       "Per-call deadline in seconds for dist kvstore "
       "push/pull/pushpull collectives; a timed-out call is retried "
       "once (MXNET_KVSTORE_RETRIES) then raises a diagnosable "
       "MXNetError naming the call and rank (0 disables).")
define("MXNET_KVSTORE_RETRIES", int, 1,
       "Bounded retry budget for a timed-out dist kvstore call before "
       "MXNetError (backoff shared with the rendezvous retry helper).")
# --- telemetry (docs/OBSERVABILITY.md) ---
define("MXNET_TELEMETRY", bool, False,
       "Master switch for the runtime telemetry registry "
       "(mxnet_tpu/telemetry.py): engine op spans + per-label latency "
       "histograms, kvstore byte/latency counters, per-step phase "
       "breakdown, guard/fault/checkpoint event counters. The read is "
       "CACHED (hot-path gate) — call telemetry.refresh() after "
       "changing it mid-process. Off: near-zero overhead "
       "(tools/telemetry_micro.py asserts <5%).")
define("MXNET_TELEMETRY_HEARTBEAT", float, 0.0,
       "Period in seconds of the telemetry heartbeat line (step rate, "
       "p50/p99 step time, pending engine ops, guard-event totals, "
       "jit-cache size, compile/recompile totals) on the "
       "'mxnet_tpu.telemetry' logger; 0 disables. Requires "
       "MXNET_TELEMETRY=1.")
define("MXNET_COMPILE_WARN_N", int, 5,
       "Recompile-storm guard (mxnet_tpu/compilewatch.py; needs "
       "MXNET_TELEMETRY=1): once one watched function recompiles more "
       "than N times, warn on the 'mxnet_tpu.compilewatch' logger with "
       "the signature-diff history naming which argument changed each "
       "time (0 disables the guard).")
define("MXNET_COMPILE_STRICT", bool, False,
       "Escalate the recompile-storm guard to MXNetError: any recompile "
       "beyond MXNET_COMPILE_WARN_N raises with the attribution "
       "history instead of only warning (CI gate for shape-stable "
       "training loops).")
define("MXNET_COMMWATCH", bool, True,
       "Collective-communication profiler (mxnet_tpu/commwatch.py; "
       "needs MXNET_TELEMETRY=1): every collective issue site — "
       "kvstore local/dist reduce, GSPMD-inserted collectives of "
       "watched step programs (harvested from the compiled HLO), and "
       "the parallel/ shard_map wrappers — records op kind, mesh axis, "
       "participant count and payload bytes into mx_comm_* "
       "counters/histograms with NCCL-test-style algorithm/bus "
       "bandwidth and exposed-vs-overlapped time attribution "
       "(docs/OBSERVABILITY.md 'Communication'). Off: commwatch "
       "records nothing even with telemetry on "
       "(tools/comm_micro.py asserts the disabled path costs <5% on "
       "the collectives hot loop).")
define("MXNET_STRAGGLER_WARN", float, 0.0,
       "Fleet straggler threshold as RELATIVE per-step skew "
       "((slowest - median)/median over the ranks' mean step time): "
       "when telemetry.fleet_snapshot() merges a fleet view whose skew "
       "exceeds this, it warns on the 'mxnet_tpu.telemetry' logger "
       "naming the slowest rank and the phase (comm vs compute) that "
       "makes it slow, and counts "
       "mx_straggler_events_total{rank,phase}. 0 disables the "
       "warning (the skew gauges are still exported).")
define("MXNET_FLEET_SNAPSHOT_PERIOD", int, 0,
       "Publish + merge the cross-rank fleet snapshot every N "
       "optimizer steps (telemetry.fleet_snapshot() from mark_step — "
       "step-count driven so every rank of a synchronous job reaches "
       "the collective together; 0 disables). The merged view feeds "
       "the heartbeat's fleet section and the straggler warning "
       "(MXNET_STRAGGLER_WARN).")
define("MXNET_PEAK_FLOPS", float, 0.0,
       "Per-chip peak FLOP/s used by the mx_mfu gauge "
       "(model-flops-utilization = measured executed FLOPs per second "
       "/ peak). 0 = auto-detect from the device kind (TPU v3/v4/v5e/"
       "v6e bf16 peaks); on an unknown device kind (e.g. the CPU "
       "mesh) the gauge is not populated and telemetry.peak_flops() "
       "raises — state a peak here to get one.")
define("MXNET_MODELWATCH", bool, False,
       "Training-dynamics observability (mxnet_tpu/modelwatch.py; "
       "needs MXNET_TELEMETRY=1): per-layer gradient/param/update-"
       "ratio gauges (mx_layer_*), rolling z-score anomaly detection "
       "that NAMES a dead or exploding layer through the guard event "
       "stream, and the gradient-noise-scale meter — all computed on "
       "device by extending GradGuard's fused reduction, so a fully "
       "enabled step still costs exactly ONE host sync "
       "(tools/modelwatch_micro.py asserts it; "
       "docs/OBSERVABILITY.md 'Training dynamics').")
define("MXNET_MODELWATCH_EVERY", int, 1,
       "Sample the modelwatch statistics every N optimizer steps "
       "(1 = every step). Non-sampled steps run the plain guard "
       "reduction (still one sync when a GradGuard is active, zero "
       "otherwise); the per-layer gauges and the crash-bundle ring "
       "hold the most recent sampled step.")
define("MXNET_MODELWATCH_ZWARN", float, 6.0,
       "Rolling z-score threshold for modelwatch's exploding-layer "
       "detector: a sampled per-layer gradient norm more than this "
       "many (robustly floored) standard deviations above its rolling "
       "mean emits a 'layer_anomaly' guard event naming the layer and "
       "counts mx_modelwatch_anomalies_total{kind='exploding',param}. "
       "0 disables anomaly detection (gauges still export).")
define("MXNET_NOISE_SCALE", bool, True,
       "With MXNET_MODELWATCH on a >=2-replica data-parallel step: "
       "estimate the gradient noise scale B_simple (arxiv 1812.06162) "
       "from the per-replica pre-allreduce gradient norms (the 'small "
       "batch' estimate the dp replicas provide for free) vs the "
       "reduced global norm the guard reduction already computes — "
       "exported as the mx_grad_noise_scale gauge and the heartbeat's "
       "suggest_batch field. No extra host sync: the per-replica "
       "norms ride modelwatch's single packed read.")
define("MXNET_CRASH_BUNDLE_DIR", str, "",
       "Directory for crash postmortem bundles "
       "(telemetry.crash_bundle): when GradGuard raises on a "
       "non-finite step, the engine poisons an op, or a watchdog "
       "fires, the last K sampled steps of modelwatch vectors + "
       "heartbeat lines, the telemetry snapshot, the chrome trace, "
       "the compilewatch program table and the MXNET_*/JAX env are "
       "dumped into one atomically-published subdirectory (tmp+rename "
       "— a concurrent reader never sees a partial bundle). Empty "
       "disables (docs/OBSERVABILITY.md 'Crash bundles').")
# --- static analysis (docs/STATICCHECK.md) ---
define("MXNET_STATICCHECK", bool, False,
       "Level-2 graph checker (mxnet_tpu/staticcheck/graph_rules.py; "
       "needs MXNET_TELEMETRY=1 — it rides compilewatch's AOT path): "
       "the jaxpr of every newly compiled watched program is checked "
       "once per signature for silent bf16->f32 promotions, host "
       "callbacks, collectives in eval-mode graphs, degenerate "
       "broadcasts and non-donated update-program parameter buffers; "
       "findings are logged once per (rule, program), counted in "
       "mx_staticcheck_findings_total{rule}, and listed by "
       "staticcheck.graph_findings() / tools/mxlint.py --level graph. "
       "Off: the compile miss path pays one cached gate read "
       "(tools/staticcheck_micro.py asserts <5% on eager dispatch).")
define("MXNET_STATICCHECK_SPMD", bool, False,
       "Level-4 SPMD sharding checker — mxlint 'shardcheck' "
       "(mxnet_tpu/staticcheck/spmd_rules.py; needs MXNET_TELEMETRY=1 "
       "— it rides the same compilewatch AOT-miss hook as Level 2): "
       "every newly compiled MULTI-device watched program has its "
       "compiled HLO parsed with commwatch's replica-group parser and "
       "its input/output shardings inspected, once per signature, for "
       "GSPMD-materialized implicit all-gathers (>=1MiB fully "
       "replicated on a mesh axis, the offending input named), "
       "reshard thrash (one value crossing >=2 layouts through "
       "chained all-to-all/collective-permute/all-gather), and large "
       "dots/convs replicated over an idle mesh axis. Programs whose "
       "HLO issues cross-device collectives are additionally marked "
       "collective-issuing so MXNET_ENGINE_RACE_CHECK can flag two "
       "such programs in flight concurrently without an ordering "
       "edge or shared serializing lock (collective-interleave — the "
       "serve-deadlock class; serve/session.py). Findings flow to "
       "staticcheck.spmd_findings(), "
       "mx_staticcheck_findings_total{rule} and tools/mxlint.py "
       "--level spmd. Off: one cached gate read per compile miss, "
       "nothing on the cache-hit path (tools/staticcheck_micro.py "
       "asserts <5%).")
define("MXNET_ENGINE_RACE_CHECK", str, "",
       "Level-3 engine dependency race detector (mxnet_tpu/"
       "staticcheck/race.py): builds a happens-before model from the "
       "read/write var sets declared at engine.push_async and checks "
       "every ACTUAL NDArray touch by a running op against it — an "
       "undeclared read/write names both ops and the shared handle "
       "instead of surfacing as a nondeterministic flake. '1'/'warn' "
       "records + warns; 'raise' raises MXNetError inside the op "
       "(poisons its outputs, error-at-wait); empty/0 off — the touch "
       "points then cost one is-None check "
       "(tools/staticcheck_micro.py asserts <5% on push+wait).")
# --- serving (docs/SERVING.md) ---
define("MXNET_SERVE_BUCKETS", str, "",
       "Shape-bucket ladder for the inference engine "
       "(mxnet_tpu/serve/bucketing.py): 'b1,b2,...' batch buckets, "
       "optionally ';s1,s2,...' sequence buckets (e.g. '1,4,16;"
       "128,256,512'). Requests are padded UP to the nearest bucket so "
       "the jit cache holds one program per bucket instead of one per "
       "request shape. Empty = a power-of-two ladder derived from "
       "max_batch/max_seq at session construction.")
define("MXNET_SERVE_MAX_WAIT_MS", float, 5.0,
       "Continuous-batching assembly deadline in milliseconds "
       "(serve/scheduler.py): once the first request of a batch is "
       "waiting, the scheduler admits more requests for at most this "
       "long before dispatching the (possibly partial) batch. 0 = "
       "dispatch immediately (pure batch-1 latency mode).")
define("MXNET_SERVE_INFLIGHT", int, 2,
       "Max serve batches in flight on the dependency engine at once "
       "(serve/scheduler.py): assembly blocks past this so a slow "
       "device backs pressure up into the queues (where the shed "
       "policy sees it) instead of piling work onto the engine.")
define("MXNET_SERVE_DRAIN_S", float, 5.0,
       "Graceful-drain deadline in seconds for Scheduler.close(): "
       "queued requests are still served for this long; whatever "
       "remains is failed with the typed OverloadError (code='drain') "
       "instead of hanging a client forever.")
define("MXNET_SERVE_FLEET_KV", str, "",
       "Fleet coordination KV address as 'host:port' (serve/fleet.py): "
       "replicas publish liveness leases and routers watch them here. "
       "Points at a dist.KVServer (stdlib TCP, started by "
       "ReplicaManager or tools/fleet_report.py); empty = use the jax "
       "coordination-service client when this process is part of a "
       "dist.initialize() group, else an in-process store (single-"
       "process tests).")
define("MXNET_SERVE_FLEET_HEARTBEAT_S", float, 0.5,
       "Replica liveness heartbeat period in seconds: each replica "
       "re-publishes its TTL'd lease + health snapshot (queue depth, "
       "p99, tokens/s, bucket table) at this period, and the router "
       "polls the lease directory at the same period.")
define("MXNET_SERVE_FLEET_MISS_K", int, 3,
       "Missed-heartbeat ejection threshold: a replica whose lease is "
       "older than MISS_K * HEARTBEAT_S is treated as dead — no new "
       "work lands on it and its in-flight requests are resubmitted "
       "(zero-drop failover).")
define("MXNET_SERVE_FLEET_RETRIES", int, 2,
       "Max retries per request on a DIFFERENT replica (serve/fleet.py "
       "Router): transport failures and dead-replica failovers retry "
       "only when the request is idempotent; typed overload/drain "
       "sheds (never executed) retry regardless. A retry never "
       "extends past the tenant deadline.")
define("MXNET_SERVE_FLEET_BREAKER_FAILS", int, 3,
       "Per-replica circuit breaker: consecutive failures before the "
       "breaker opens and the replica stops receiving work until a "
       "half-open probe succeeds.")
define("MXNET_SERVE_FLEET_BREAKER_MS", float, 200.0,
       "Base circuit-breaker open time in milliseconds; doubles per "
       "re-open (exponential backoff, capped at 60x) before the next "
       "half-open probe is allowed through.")
define("MXNET_SERVE_FLEET_CONC", int, 16,
       "Router submit concurrency: max requests being driven at once "
       "by Router.submit's thread pool (Router.infer drives inline on "
       "the caller thread and does not consume these slots).")
define("MXNET_SERVE_FLEET_TIMEOUT_S", float, 30.0,
       "Default end-to-end deadline in seconds for a routed request "
       "whose tenant declares no deadline_ms; retries and hedges all "
       "charge against the same deadline.")
define("MXNET_TRACE", bool, False,
       "Master switch for distributed request tracing "
       "(mxnet_tpu/tracing.py): a TraceContext minted at the serving "
       "edge rides the wire into each replica so router attempt/hedge "
       "spans, scheduler queue/batch spans and engine execute spans "
       "assemble into one cross-process trace per sampled request. "
       "The read is CACHED (one-attr hot-path gate) — call "
       "tracing.refresh() (or telemetry.refresh(), which chains) "
       "after changing it mid-process. Off: wire frames are byte-"
       "identical to the untraced format and tools/trace_micro.py "
       "asserts <5% router+scheduler overhead.")
define("MXNET_TRACE_SAMPLE", float, 0.01,
       "Head-sampling rate in [0,1] for MXNET_TRACE: the keep/drop "
       "decision is made ONCE where the trace is minted (frontend or "
       "router edge) and carried in the context — replicas never "
       "re-flip it. Unsampled requests carry zero trace bytes on the "
       "wire. 1.0 = trace everything (tests/debugging).")
define("MXNET_TRACE_RING", int, 2048,
       "Per-process bound on buffered completed spans "
       "(tracing.record_span): overflow evicts the oldest span and "
       "counts it in the heartbeat's trace= dropped counter — drops "
       "are counted, never silent.")
define("MXNET_TRACE_EXEMPLARS", int, 4,
       "Slow-request exemplar retention per TraceStore: the N worst "
       "(longest) assembled traces are kept with full span detail and "
       "included in telemetry.crash_bundle()'s traces.json. 0 "
       "disables retention.")
define("MXNET_SERVE_HEDGE_MS", float, 0.0,
       "Hedged-request delay in milliseconds (serve/fleet.py Router): "
       "when an idempotent request has not completed after this long, "
       "a duplicate is launched on a different replica and the first "
       "completion wins (the loser is cancelled and counted in "
       "mx_fleet_hedges_total). 0 = hedging off; negative = auto "
       "(hedge at the observed fleet p99).")
# --- testing ---
define("MXNET_TEST_DEFAULT_CTX", str, "",
       "Override the default context for the test suite (the "
       "reference's gpu-suite re-run pattern; e.g. 'tpu').")
define("MXNET_TEST_ON_TPU", bool, False,
       "Run the test suite against the real chip instead of the "
       "8-virtual-device CPU mesh (tests/conftest.py).")
# --- benchmarking ---
define("MXNET_BENCH_PIPELINE", bool, False,
       "bench.py: feed every step from the native RecordIO pipeline "
       "instead of a resident batch.")
define("MXNET_PERF_DB", str, "",
       "Root directory of the performance-trajectory store "
       "(mxnet_tpu/perfwatch.py): one JSONL file per (device_kind, "
       "metric), published atomically (tmp+rename, the "
       "MXNET_AUTOTUNE_CACHE discipline). When set, every bench-JSON "
       "record emitted through tools/bench_json.py is recorded with "
       "an environment fingerprint; tools/perfwatch.py "
       "ingests/reports/gates over it. Empty = no store (emitters "
       "print JSON only).")
define("MXNET_PERFWATCH", bool, True,
       "Master switch for the bench-emit ingestion seam "
       "(perfwatch.maybe_record): recording only engages when this "
       "is on AND MXNET_PERF_DB names a store. The read is CACHED "
       "(one-bool hot-seam gate) — call perfwatch.refresh() (or "
       "telemetry.refresh(), which chains) after changing it "
       "mid-process. tools/perfwatch.py micro asserts the disabled "
       "seam costs <5% on the bench emit loop.")
define("MXNET_PERFWATCH_TOL", float, 0.05,
       "Default relative tolerance for perfwatch verdicts: the "
       "latest point must deviate from the rolling-median baseline "
       "by more than this fraction (AND clear the MAD score bar) to "
       "verdict regressed/improved — the floor that keeps a "
       "near-zero-MAD flat trajectory from alarming on noise.")
define("MXNET_PERFWATCH_TOL_OVERRIDES", str, "",
       "Per-metric tolerance overrides, 'metric=tol,metric=tol' "
       "(e.g. 'resnet50_v1_train_throughput=0.08'); a name matches "
       "itself and its derived sub-series by prefix, longest match "
       "wins over MXNET_PERFWATCH_TOL.")
define("MXNET_PERFWATCH_MAD_K", float, 3.0,
       "MAD-score bar for perfwatch verdicts: the latest point's "
       "deviation from the rolling-median baseline must exceed this "
       "many scaled MADs (1.4826 x median absolute deviation of the "
       "window) of trajectory noise. Same bar gates the change-point "
       "pass.")
define("MXNET_PERFWATCH_WINDOW", int, 8,
       "Rolling window for perfwatch baselines: the latest point is "
       "judged against the median (and MAD) of up to this many "
       "preceding points of the same (device_kind, metric) "
       "trajectory.")


def _main():
    print("# mxnet_tpu runtime configuration\n")
    print("Declared in `mxnet_tpu/config.py`; read live via "
          "`mxnet_tpu.config.get(name)`.\n")
    print(describe())


if __name__ == "__main__":
    _main()
