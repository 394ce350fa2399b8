"""Process flags, initialized from FLAGS_* environment variables.

≙ the reference's gflags layer: C++ defines flags near point of use
(FLAGS_check_nan_inf in operator.cc,
FLAGS_fraction_of_gpu_memory_to_use in platform/gpu_info.cc), and
python/paddle/fluid/__init__.py's __bootstrap__ forwards FLAGS_* env
vars into gflags via core.init_gflags. Here the registry is Python and
the env contract is identical: `FLAGS_check_nan_inf=1 python train.py`.

Flags whose mechanism belongs to XLA on this runtime (memory fractions,
mkldnn) are accepted for launch-script compatibility and documented as
no-ops rather than silently unknown.
"""

from __future__ import annotations

import os
from typing import Any, Dict

__all__ = ["FLAGS", "DEFINE_flag", "reset_flags_from_env",
           "ENV_KNOBS", "declare_env_knob", "env_knob_int",
           "env_knob_float"]


def env_knob_int(name: str, default: int) -> int:
    """Positive-int PT_* knob parse: malformed raises (a config error
    must fail loudly, not silently default), unset/non-positive falls
    back to `default`. ONE parser for every int-valued knob — the
    data pipeline and the per-op profiler both read through it."""
    raw = os.environ.get(name, "").strip()
    try:
        val = int(raw) if raw else 0
    except ValueError as e:
        raise ValueError(f"malformed {name}={raw!r}: {e}") from e
    return val if val > 0 else default


def env_knob_float(name: str, default: float) -> float:
    """Positive-float PT_* knob parse, same contract as env_knob_int:
    malformed raises, unset/non-positive/non-finite falls back to
    `default` (thresholds and ratios read through it — PT_CALIB_REPLAN_
    THRESHOLD's drift-ratio ceiling is the canonical consumer)."""
    raw = os.environ.get(name, "").strip()
    try:
        val = float(raw) if raw else 0.0
    except ValueError as e:
        raise ValueError(f"malformed {name}={raw!r}: {e}") from e
    if val != val or val in (float("inf"), float("-inf")):
        return default
    return val if val > 0 else default


class _Flags:
    def __init__(self):
        object.__setattr__(self, "_defs", {})   # name -> (type, default, help, noop)
        object.__setattr__(self, "_values", {})

    def __getattr__(self, name: str):
        if name in self._values:
            return self._values[name]
        raise AttributeError(f"undefined flag {name!r}")

    def __setattr__(self, name: str, value):
        if name not in self._defs:
            raise AttributeError(f"undefined flag {name!r}")
        typ = self._defs[name][0]
        self._values[name] = self._parse(typ, value)

    @staticmethod
    def _parse(typ, value):
        if typ is bool and isinstance(value, str):
            return value.strip().lower() in ("1", "true", "yes", "on")
        return typ(value)

    def _define(self, name, typ, default, help_str, noop=False):
        self._defs[name] = (typ, default, help_str, noop)
        env = os.environ.get(f"FLAGS_{name}")
        if env is None:
            self._values[name] = default
            return
        try:
            self._values[name] = self._parse(typ, env)
        except (TypeError, ValueError) as e:
            if noop:
                # compat flags exist to tolerate foreign launch scripts:
                # never make the package unimportable over one
                import warnings
                warnings.warn(f"ignoring malformed FLAGS_{name}={env!r}: "
                              f"{e}; using default {default!r}")
                self._values[name] = default
            else:
                raise ValueError(
                    f"malformed FLAGS_{name}={env!r}: {e}") from e

    def help(self) -> Dict[str, str]:
        return {n: d[2] + (" [no-op on this runtime]" if d[3] else "")
                for n, d in self._defs.items()}


FLAGS = _Flags()


def DEFINE_flag(name: str, typ, default: Any, help_str: str = "",
                noop: bool = False):
    FLAGS._define(name, typ, default, help_str, noop)


def reset_flags_from_env():
    """Re-read every FLAGS_* env var (tests; ≙ re-running __bootstrap__)."""
    for name, (typ, default, help_str, noop) in list(FLAGS._defs.items()):
        FLAGS._define(name, typ, default, help_str, noop)


# --- the reference's user-visible flag surface -----------------------------
DEFINE_flag("check_nan_inf", bool, False,
            "validate every executed step for nan/inf, reporting the "
            "generating primitive (≙ operator.cc:590 per-op check; here "
            "jax.experimental.checkify instruments the compiled step)")
DEFINE_flag("fraction_of_gpu_memory_to_use", float, 0.92,
            "accepted for launch-script compatibility", noop=True)
DEFINE_flag("use_mkldnn", bool, False,
            "accepted for launch-script compatibility", noop=True)
DEFINE_flag("eager_delete_scope", bool, True,
            "accepted for launch-script compatibility", noop=True)


# --- PT_* env-knob registry -------------------------------------------------
# Direct os.environ switches (read at point of use, not through FLAGS —
# most gate module-level or per-trace decisions where the FLAGS object
# would be a circular import). Every PT_* read in the package MUST be
# declared here: tools/lint.py statically cross-checks reads against this
# registry (the undeclared-env-knob rule), so a knob can't ship invisible
# to FLAGS-style discovery.

ENV_KNOBS: Dict[str, str] = {}


def declare_env_knob(name: str, help_str: str = ""):
    ENV_KNOBS[name] = help_str


declare_env_knob("PT_VERIFY",
                 "run the static program verifier (analysis/) as an "
                 "executor/transpiler pre-pass; errors raise before "
                 "compile. Default off; tests default it on")
declare_env_knob("PT_GCONV_CACHE",
                 "path of the grouped-conv autotune cache JSON "
                 "(default ~/.cache/paddle_tpu/gconv_autotune.json)")
declare_env_knob("PT_GCONV_TUNE",
                 "0|never disables grouped-conv measurement (untuned "
                 "shapes keep the native formulation)")
declare_env_knob("PT_GCONV_DENSE",
                 "always|never overrides the measured grouped-conv "
                 "formulation choice")
declare_env_knob("PT_GCONV_LAYOUT",
                 "oihw|hwio pins the dense grouped-conv formulation's "
                 "weight layout (default: the measured winner from the "
                 "same autotune entry; untuned shapes keep oihw)")
declare_env_knob("PT_FUSE",
                 "0|never disables the conv-epilogue fusion pass "
                 "(analysis/fuse.py) — the executor then runs the "
                 "original program bit-for-bit (default on)")
declare_env_knob("PT_FUSE_EPILOGUE",
                 "fused_conv2d epilogue backend: auto (per-shape "
                 "measured winner from the shared autotune cache) | "
                 "always (force the Pallas epilogue kernel) | never "
                 "(XLA lax composition only)")
declare_env_knob("PT_FUSE_TUNE",
                 "0|never disables fused-conv epilogue measurement "
                 "(untuned shapes keep the XLA lax composition)")
declare_env_knob("PT_FUSE_CACHE",
                 "path of the fused-conv autotune cache JSON (default "
                 "~/.cache/paddle_tpu/fused_conv_autotune.json)")
declare_env_knob("PT_FUSED_LSTM",
                 "never reverts the whole-sequence Pallas LSTM kernel "
                 "to the lax.scan formulation")
declare_env_knob("PT_BN_PLAIN_VJP",
                 "use plain-AD batch-norm gradients instead of the "
                 "memory-lean custom VJP (timing A/B)")
declare_env_knob("PT_XENT_PLAIN",
                 "use plain-AD softmax-xent gradients instead of the "
                 "logits-temp-free custom VJP (timing A/B)")
declare_env_knob("PT_HOST_TABLE_STRICT_LOAD",
                 "error (instead of warn) on host-table checkpoint "
                 "shard-coverage gaps")
declare_env_knob("PT_FAULT_INJECT",
                 "deterministic fault-plan injector (resilience/"
                 "faults.py): comma-separated site@trigger specs + "
                 "optional :seed=N, e.g. "
                 "'io_write_truncate@3,step_crash@7,reader_raise@2:seed=0'"
                 " — triggers are N (1-based one-shot), * (every hit), "
                 "or pFLOAT (seeded probability)")
declare_env_knob("PT_CKPT_VERIFY",
                 "0|false disables checkpoint manifest verification on "
                 "load (default on: corrupt committed serials are "
                 "quarantined and the loader falls back to the newest "
                 "serial that verifies)")
declare_env_knob("PT_CHAOS_SEED",
                 "seed forwarded to the chaos suite's probabilistic "
                 "fault plans (scripts/ci.sh chaos runs the resilience "
                 "tests under two fixed values)")
declare_env_knob("PT_GUARD",
                 "training-guardrail recovery policy (resilience/"
                 "guard.py): skip | rollback | raise (unset/0 = off). "
                 "Arms the in-graph step-health flag + guarded weight "
                 "update: an anomalous step (non-finite loss/grads, "
                 "grad-norm over PT_GUARD_MAX_GNORM) never touches the "
                 "weights. Must be set BEFORE the program is built "
                 "(optimizer.minimize instruments it)")
declare_env_knob("PT_GUARD_PATIENCE",
                 "consecutive anomalous steps before PT_GUARD=raise "
                 "raises / PT_GUARD=rollback restores the newest "
                 "verified checkpoint (default 3)")
declare_env_knob("PT_GUARD_MAX_GNORM",
                 "global-gradient-norm ceiling of the step-health flag "
                 "(default inf: only non-finite loss/grads trip the "
                 "guard); measured on raw pre-clip grads, unscaled by "
                 "the AMP loss_scale")
declare_env_knob("PT_STEP_DEADLINE_S",
                 "step watchdog (resilience/watchdog.py): a lazy fetch "
                 "materialization that does not settle within this many "
                 "seconds raises StepHungError with the stuck phase + "
                 "in-flight fetch provenance instead of hanging forever "
                 "(unset/0 = off)")
declare_env_knob("PT_SERVE_MAX_BATCH",
                 "serving engine (paddle_tpu/serving/): micro-batch "
                 "coalescing bound per dispatch (default: the serving "
                 "artifact's exported batch size; always clamped to it)")
declare_env_knob("PT_SERVE_MAX_WAIT_MS",
                 "serving engine: how long the micro-batcher holds an "
                 "under-filled batch open waiting for more requests "
                 "before dispatching anyway (default 2 ms). Bounds "
                 "added latency; raise it to trade p50 latency for "
                 "batch fill under light load")
declare_env_knob("PT_SERVE_QUEUE_DEPTH",
                 "serving engine: bounded request queue per model "
                 "(default 256). A full queue rejects fast with the "
                 "typed Overloaded error instead of queuing into "
                 "timeout")
declare_env_knob("PT_SERVE_DEADLINE_MS",
                 "serving engine: default per-request deadline (0 = "
                 "none). Expired or provably-unmeetable deadlines shed "
                 "fast with the typed DeadlineExceeded error; "
                 "per-request deadline_ms overrides")
declare_env_knob("PT_DECODE_BLOCK_SIZE",
                 "decode bundle export (io.export_decode_model): tokens "
                 "per paged-KV block (default 16). Fixed at export — the "
                 "decode-step artifact's pool shape bakes it in")
declare_env_knob("PT_DECODE_POOL_BLOCKS",
                 "decode bundle export: preallocated KV-pool blocks per "
                 "layer, INCLUDING the reserved null block 0 (default "
                 "64). Usable cache capacity is (pool_blocks-1) x "
                 "block_size tokens shared by all in-flight sequences; "
                 "under pressure the scheduler evicts lowest-priority "
                 "sequences")
declare_env_knob("PT_DECODE_MAX_SLOTS",
                 "decode bundle export: slot count of the fixed-shape "
                 "decode step = max concurrently-decoding sequences "
                 "(default 8). Continuous batching admits new sequences "
                 "into free slots of the in-flight batch")
declare_env_knob("PT_DECODE_MAX_NEW_TOKENS",
                 "decode engine: default per-request generation budget "
                 "when the request does not pass max_new_tokens "
                 "(default 64); bounded by the artifact's max_context")
declare_env_knob("PT_KV_SHARE",
                 "decode engine: 1 = copy-on-write prefix sharing "
                 "(serving/decode/prefix.py). Prompts whose prefix is "
                 "already resident ALIAS the cached KV blocks (per-block "
                 "refcounts in KVBlockPool) instead of rewriting them — "
                 "one copy backs N sessions; the first decode write into "
                 "a shared block copies it out first. Default 0: cached "
                 "prefixes outlive their sequences, which changes the "
                 "idle-pool accounting the plain engine guarantees")
declare_env_knob("PT_SPEC_DRAFT",
                 "decode engine: speculative-decoding drafter "
                 "(serving/decode/spec.py). ngram = prompt-lookup "
                 "self-drafting, self = the bundle's own prefill "
                 "(acceptance 1.0 by construction), a path = a smaller "
                 "decode bundle loaded as the drafter. Drafted tokens "
                 "verify through IDLE slots of the same fixed-shape "
                 "step; greedy acceptance keeps output token-identical "
                 "to plain decode. Unset = off")
declare_env_knob("PT_SPEC_K",
                 "decode engine: drafted tokens per speculative step "
                 "(default 4), bounded per step by idle slots, the "
                 "remaining generation budget, and max_context. Only "
                 "read when PT_SPEC_DRAFT arms a drafter")
declare_env_knob("PT_MEM_BUDGET_GB",
                 "static peak-HBM budget gate (analysis/memory.py): on "
                 "every executor compile miss the liveness-based memory "
                 "estimate runs BEFORE tracing, and an estimate over this "
                 "many GB raises the typed MemoryBudgetError carrying the "
                 "params/activations/grads/optimizer-state/kv-pool "
                 "breakdown — instead of compiling for minutes and dying "
                 "RESOURCE_EXHAUSTED on the device. PER-DEVICE gigabytes: "
                 "under a mesh the estimate prices the per-device batch "
                 "(dp feed split). Unset/0 = off; a passing budget adds "
                 "zero syncs to the hot path")
declare_env_knob("PT_COST_CHIP",
                 "chip override for the roofline cost model (analysis/"
                 "cost.py), e.g. 'tpu v5e' — lets an off-TPU host "
                 "predict step time / MFU / bound for the deployment "
                 "chip; default: the detected jax device kind")
declare_env_knob("PT_DATA_WORKERS",
                 "data pipeline (paddle_tpu/data/): decode worker-pool "
                 "width of map_batches stages that don't pass an "
                 "explicit workers= (default 2). Decode occupancy ~1.0 "
                 "in the pt_data_* metrics means raise it")
declare_env_knob("PT_DATA_BACKEND",
                 "data pipeline: decode pool backend, thread (default) "
                 "| process. Threads are right for the native decode "
                 "kernels (they release the GIL); the process pool "
                 "exists for GIL-bound pure-Python decoders, needs a "
                 "picklable decode fn, and is NOT exercised by tier-1 "
                 "tests (sandbox multiprocess limits)")
declare_env_knob("PT_DATA_PREFETCH",
                 "data pipeline: bounded queue depth of decoded batches "
                 "between the decode pool and the consumer (default "
                 "2 x workers). Bounds host RAM held in decoded "
                 "batches; too low re-serializes decode behind the "
                 "consumer")
declare_env_knob("PT_FEED_CODEC",
                 "on-wire feed codec default policy (data/codec.py): "
                 "none (default) | bf16 | int8. Batches cross the "
                 "host->device pipe encoded (int8 = per-channel "
                 "symmetric, ~4x fewer wire bytes + a tiny f32 scale "
                 "companion; bf16 = truncation, 2x) and dequantize on "
                 "device inside the jitted augment call / the traced "
                 "feed_dequant op. Per-stage Dataset.encode(policy=...) "
                 "and apply_wire_codec(policy=...) override it. int8 is "
                 "LOSSY by design: parity is a calibrated tolerance "
                 "band (docs/data.md)")
declare_env_knob("PT_FEED_WIRE_MBPS",
                 "modeled host->device feed-pipe rate in MB/s for the "
                 "roofline's host leg (analysis/cost.py predict_step): "
                 "feed bytes at the WIRE dtype divided by this rate "
                 "become a fourth leg, and when it sets the max the "
                 "declared bound is 'host' — the thin-pipe reading "
                 "an earlier remote set-up measured (~15 MB/s), now predicted. "
                 "Unset/0 = pipe not modeled (co-located hosts)")
declare_env_knob("PT_OPT_STATE_DTYPE",
                 "optimizer-state precision policy (optimizer.py): "
                 "bfloat16 stores the param-shaped moment accumulators "
                 "(Adam m/v, Momentum velocity) at bf16 — half the "
                 "optimizer-state HBM, visible to the memory estimator "
                 "and the PT_MEM_BUDGET_GB gate before compile. Update "
                 "math still runs f32 in the op kernels; params and "
                 "scalar beta-power accumulators stay f32. Must be set "
                 "BEFORE optimizer.minimize builds the accumulators. "
                 "Unset/float32 = off")
declare_env_knob("PT_TRACE",
                 "structured tracing (obs/trace.py): 1 arms span "
                 "emission across every plane — executor phases, "
                 "trainer step/epoch/checkpoint/guard events, "
                 "data-pipeline stages, the serving request lifecycle "
                 "— into a bounded in-process ring buffer; "
                 "tools/trace_dump.py writes the Chrome-trace JSON "
                 "Perfetto loads. Read per call, so it can be toggled "
                 "at runtime; the disabled path costs <= 1%. Unset/0 "
                 "= off, except that every finished PhaseTimer phase "
                 "still lands in the ring as one bare record "
                 "(docs/observability.md)")
declare_env_knob("PT_TRACE_BUF",
                 "ring-buffer capacity of the structured trace, in "
                 "events (default 65536). The buffer keeps the NEWEST "
                 "window — a long run_loop never grows memory. Read "
                 "when the ring is (re)created (obs.trace.reset)")
declare_env_knob("PT_TRACE_DIR",
                 "with PT_TRACE armed: directory for trace output — "
                 "tools/trace_dump.py defaults its JSON there, and the "
                 "Trainer opens a jax.profiler.trace session writing "
                 "device-side op attribution (the per-op named_scopes) "
                 "next to the host-side spans. Unset = host-side spans "
                 "only")
declare_env_knob("PT_OPPROF_REPEATS",
                 "per-op profiler (obs/opprof.py): each program segment "
                 "is timed as the MIN of this many settled runs after a "
                 "warm/compile pass (default 3) — the least-contended "
                 "estimate, the bench window policy at segment scale")
declare_env_knob("PT_OPPROF_SEG_OPS",
                 "per-op profiler: coalesce adjacent unit op-runs into "
                 "segments of up to this many ops (default 16) before "
                 "compiling — bounds the compile count; remat-tagged "
                 "runs stay atomic regardless. 1 = every untagged op "
                 "times individually (slow, exact)")
declare_env_knob("PT_OPPROF_TOPK",
                 "per-op profiler: how many laggard rows the pt_op_* "
                 "exposition and the bench op_attribution block carry "
                 "(default 5); tools/op_report.py --top overrides per "
                 "run")
declare_env_knob("PT_PLAN_BEAM",
                 "placement planner (analysis/planner.py): how many "
                 "ranked plans the emitted PlacementPlan artifact keeps "
                 "(default 8). The full candidate space is still "
                 "searched; the artifact's rejection log is capped at "
                 "200 entries (rejections_truncated records the "
                 "overflow, search.rejected counts them all)")
declare_env_knob("PT_PLAN_TOPOLOGY",
                 "placement planner: default device-topology override, "
                 "'chip:chips_per_host[xhosts][@dci=][@ici=][@hbm=]' — "
                 "e.g. v5e:8, v5p:4x2@dci=50 (parallel/mesh.py "
                 "Topology.parse). Lets an off-TPU host plan for the "
                 "deployment pod, like PT_COST_CHIP does for the "
                 "roofline")
declare_env_knob("PT_PLAN_PP",
                 "placement planner: pipeline-stage counts to search as "
                 "pp x dp candidates, comma-separated (e.g. '2,4'); "
                 "0 disables the pp axis. Default: every stacked-layer "
                 "divisor of an already-pipeline-transpiled program "
                 "that also divides the chip count (a program without "
                 "a pipeline op searches none — run "
                 "transpiler.pipeline_transpile BEFORE "
                 "optimizer.minimize to open the axis)")
declare_env_knob("PT_PLAN_MICROBATCH",
                 "placement planner: microbatch count pp candidates "
                 "are scheduled and priced at (default 4, clamped to "
                 "the batch; batch % microbatches must be 0). More "
                 "microbatches shrink the pipeline bubble "
                 "(S-1)/(S+M-1) but raise GPipe's activation stash — "
                 "1F1B's stash stays bounded at min(S, M)")
declare_env_knob("PT_PLAN_COLL",
                 "placement planner: pin the per-collective reduction "
                 "algorithm — ring | tree | hierarchical (where an "
                 "algorithm has no implementation for a collective it "
                 "falls back to ring). Default/auto: the planner "
                 "chooses the cheapest algorithm per collective from "
                 "the comm.py cost formulas — the searched dimension; "
                 "pin it to A/B a convention (forced-ring is the "
                 "regression baseline)")
declare_env_knob("PT_FLEET_REPLICAS",
                 "fleet tier (serving/fleet/): initial replica count "
                 "of a ReplicaPool (default 1); constructor args win")
declare_env_knob("PT_FLEET_MIN",
                 "fleet tier: scale floor — the pool (and the "
                 "autoscaler) never go below this many replicas "
                 "(default 1)")
declare_env_knob("PT_FLEET_MAX",
                 "fleet tier: scale ceiling (default 8)")
declare_env_knob("PT_FLEET_POLICY",
                 "fleet router dispatch policy for sessionless "
                 "traffic: least_loaded (default; queue-depth x "
                 "EWMA-service-time score) | round_robin. Requests "
                 "carrying a session key always route session-affine "
                 "(rendezvous hash)")
declare_env_knob("PT_CALIB_PATH",
                 "cost-model calibration artifact (analysis/"
                 "calibrate.py): path of a `tools/op_report.py --fit` "
                 "JSON. When set, predict_step / planner scoring / "
                 "rescore_plan all price through the fitted per-op-type "
                 "correction factors and the per-dispatch collective "
                 "overhead constant; a stale artifact (other chip, "
                 "unknown program fingerprint, failed floors) warns "
                 "once and prices raw. Unset = uncalibrated (the "
                 "default ~/.cache/paddle_tpu/calibration.json is a "
                 "WRITE target only, never read implicitly)")
declare_env_knob("PT_CALIB_REPLAN_THRESHOLD",
                 "drift-triggered re-planning (Trainer + obs/drift.py): "
                 "when the live pt_model_drift_ratio of the training "
                 "program sustains above this ratio for "
                 "calibrate.REPLAN_WINDOWS consecutive log windows, a "
                 "parallel Trainer re-invokes the placement planner "
                 "under the current calibration, re-transpiles, and "
                 "hot-resumes from the in-memory scope (`replan` trace "
                 "span + pt_calib_* metrics). Unset/0 = off; 1.5 means "
                 "'measured 50% over predicted'")
declare_env_knob("PT_FLEET_AUTOSCALE",
                 "1 = fleet.make_fleet attaches + starts the "
                 "metrics-driven Autoscaler (queue-depth + EWMA "
                 "signals, hysteresis; scale-up fast on sustained "
                 "depth, scale-down slow after an idle window, "
                 "bounded by PT_FLEET_MIN/PT_FLEET_MAX)")
declare_env_knob("PT_ELASTIC_TOPOLOGY",
                 "elastic training (resilience/elastic.py): the "
                 "topology that SURVIVES a preemption, same grammar as "
                 "PT_PLAN_TOPOLOGY — the supervisor re-plans onto it "
                 "on the next restart. Unset = the launch topology "
                 "shrunk by the fault sites' reported losses "
                 "(mesh_shrink halves, device_loss drops one chip)")
declare_env_knob("PT_ELASTIC_RESTARTS",
                 "elastic supervisor restart budget: bounded attempts "
                 "after the first run (default 3); exhaustion "
                 "re-raises the original training error")
declare_env_knob("PT_ELASTIC_BACKOFF_S",
                 "elastic supervisor base restart backoff in seconds "
                 "(default 0.05; exponential with seeded jitter, "
                 "capped at 30 s)")
declare_env_knob("PT_ORCH_LEASE_S",
                 "orchestrator (resilience/orchestrator.py) default "
                 "worker lease in seconds (default 10): a worker whose "
                 "lease age exceeds lease + grace is evicted — dead "
                 "handle = worker_crash, live handle = heartbeat_loss "
                 "(killed). Per-worker override via WorkerSpec.lease_s")
declare_env_knob("PT_ORCH_GRACE_S",
                 "orchestrator eviction grace window in seconds past "
                 "the lease before a silent worker is evicted "
                 "(default: half the lease)")
declare_env_knob("PT_ORCH_STOP_GRACE_S",
                 "orchestrator graceful-stop budget in seconds "
                 "(default 30): survivors get this long to checkpoint "
                 "at a step boundary and return before being killed "
                 "during a recovery or final shutdown")
declare_env_knob("PT_ORCH_EVICTIONS",
                 "orchestrator eviction budget (default 3): total "
                 "evictions tolerated across the run; exhaustion "
                 "raises OrchestratorError instead of shrinking again")
declare_env_knob("PT_ORCH_WORKER_ID",
                 "set by the subprocess runner on each spawned worker: "
                 "its worker id, consumed by "
                 "orchestrator.worker_context_from_env()")
declare_env_knob("PT_ORCH_LEASE_DIR",
                 "set by the subprocess runner on each spawned worker: "
                 "the lease directory to renew into, consumed by "
                 "orchestrator.worker_context_from_env()")
declare_env_knob("PT_ORCH_ROUND",
                 "set by the subprocess runner on each spawned worker: "
                 "the orchestration round (increments per recovery), "
                 "stamped into lease renewals")
declare_env_knob("PT_RESHARD_CHUNK_MB",
                 "streaming reshard (resilience/streaming.py) slab "
                 "size in MiB (default 64): peak host memory of the "
                 "streaming path is bounded by this budget plus a "
                 "constant, independent of variable size")
declare_env_knob("PT_RESHARD_MAX_HOST_GB",
                 "gather-reshard guardrail: refuse the in-memory "
                 "reshard path with ReshardMemoryError (naming "
                 "tools/reshard.py --stream) when the up-front host "
                 "byte estimate exceeds this many GB. Unset/0 = off")
