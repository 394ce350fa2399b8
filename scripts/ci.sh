#!/usr/bin/env bash
# CI entry (≙ paddle/scripts/paddle_build.sh: build + test in one place).
# Runs the lint gate, the full suite on the 8-device virtual CPU mesh,
# the multi-chip dryrun, and a bench sanity pass.
# Usage: scripts/ci.sh [quick|lint|chaos|perf|serve|analyze|data|obs|fusion]
#   lint  = just the lint gate
#   chaos = lint gate + the resilience suite under two fixed fault seeds
#   perf  = lint gate + the async-hot-path suite (lazy fetches, per-phase
#           timing, device-resident checkpoints, compile-cache warm
#           starts, two-stage prefetch) + the learning-probe regression
#   serve = lint gate + the online-serving suite (micro-batching, shape
#           buckets, hot reload, admission/shedding, metrics, HTTP front
#           end) + the C-API serving drivers + the autoregressive decode
#           suite (paged KV cache, continuous batching, eviction/resume
#           token identity, streaming route, prometheus exposition) +
#           the fleet-tier suite (replica pool, least-loaded/session-
#           affine routing, priority WFQ admission + lowest-class-first
#           shedding, crash failover, autoscaler hysteresis, pt_fleet_*
#           exposition) + the kv-economics suite (copy-on-write prefix
#           sharing, refcounted block pool, speculative decoding token
#           identity, pt_kv_*/pt_spec_* exposition) with its
#           schema-checked bench A/B row (capacity floor >= 2x)
#   analyze = lint gate + the static cost-model suites + schema-checked
#           tools/cost_report.py runs over the resnet / transformer /
#           decode bench programs, incl. the collective audit on the
#           MULTICHIP dryrun meshes (dp, dp x tp, dp x sp x tp) + the
#           placement planner (tools/plan.py): schema-checked plans for
#           all three builders, the calibration loop (fit suite +
#           op_report --fit -> plan --calibration round-trip, artifact
#           floor-checked), plus the predicted-vs-measured
#           rank-correlation gate over the hand-picked dryrun meshes —
#           run CALIBRATED, gating both arms' Spearman
#   obs   = lint gate + the unified-observability suite (span core,
#           cross-thread trace correctness, ring-buffer bounds,
#           drift-monitor EWMA, Chrome-trace JSON schema, pt_train_*/
#           pt_model_* families, disabled-path overhead budget) + the
#           per-op attribution suite (ledger math, coverage gaps,
#           pt_op_*/pt_build_info exposition, postmortem bundle) + an
#           exposition-format conformance check over a live scrape +
#           schema-checked tools/op_report.py attribution runs on the
#           resnet and transformer bench programs
#   fusion = lint gate + the conv-epilogue fusion suite (pass legality,
#           fused-vs-unfused fwd+bwd parity, PT_FUSE=0 bit-for-bit
#           restore, cost/memory strict decrease, conv-fusion verifier
#           pass, Pallas epilogue interpret numerics) + the shared
#           autotune-harness suite (gconv layout dimension, schema-
#           versioned cache, corruption round-trips) + a live
#           bench_resnet fused-vs-unfused A/B row schema-checked via
#           analysis/artifacts.validate_fusion_ab (speedup recorded-or-
#           explained, parity inside the declared band, attribution
#           coverage >= 90 on the fused config)
#   data  = lint gate + the production data-plane suite (pipeline
#           determinism, sharding disjointness, parallel shard readers,
#           cheap skip + checkpointable state, device-side augmentation,
#           exactly-once under reader faults, mid-epoch resume
#           bit-exactness, pt_data_* metrics) + the on-wire feed-codec
#           suite (int8/bf16 encode-decode round-trips, fused
#           dequant+augment, resume through an encode stage, the
#           wire-dtype program path, feed-wire roofline leg, bf16
#           optimizer moments) + the legacy reader / dataset-parser /
#           double-buffer suite — all thread-backend
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== lint gate (ruff + custom AST checks, tools/lint.py) =="
python tools/lint.py
if [[ "${1:-}" == "lint" ]]; then
  echo "LINT OK"
  exit 0
fi

if [[ "${1:-}" == "chaos" ]]; then
  # chaos leg: the resilience suite (fault injection, verified
  # checkpoints, preemption/resume parity) + the guardrail suite
  # (in-graph step health, guarded updates, skip/rollback/raise
  # policies, step watchdog) replayed under two fixed seeds —
  # probabilistic fault plans (site@pP) draw differently per seed, so
  # the recovery invariants are exercised on two distinct failure
  # schedules, both reproducible.
  for seed in 0 7; do
    echo "== chaos: resilience + guardrail + elastic + fleet + orchestrator suites (PT_CHAOS_SEED=$seed) =="
    # the fleet suite rides along: its router_dispatch chaos site
    # (deterministic replica-crash injection at dispatch) exercises the
    # failover/rebuild path under the same seeded harness; the elastic
    # suite drives mesh_shrink/device_loss through the supervisor's
    # restore -> re-plan -> reshard -> resume loop; the orchestrator
    # suite drives worker_crash/heartbeat_loss through the host-level
    # lease protocol (hang-vs-crash discrimination + streaming reshard)
    # the kv-economics suite rides along for its spec_verify chaos site
    # (drafter crash mid-step -> plain-decode fallback, token-identical)
    PT_CHAOS_SEED=$seed python -m pytest tests/test_resilience.py \
      tests/test_guardrails.py tests/test_elastic.py tests/test_fleet.py \
      tests/test_orchestrator.py tests/test_streaming_reshard.py \
      tests/test_kv_economics.py -q
  done
  echo "== chaos: orchestrated bench row (schema-checked, validate_orchestrated) =="
  # one real hang -> evict -> shrink -> resume measurement plus the
  # streamed-checkpoint memory contract, floored in-process: bench
  # emits floor_violations into the row and this gate refuses them
  python - << 'PYEOF'
import json, os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import bench
row = bench.bench_orchestrated(on_tpu=False, peak=1e12)
print(json.dumps(row, indent=2))
if row.get("floor_violations"):
    sys.exit("orchestrated bench row violated its floors")
PYEOF
  echo "CHAOS OK"
  exit 0
fi

if [[ "${1:-}" == "obs" ]]; then
  echo "== obs: structured tracing + unified metrics + drift monitor =="
  python -m pytest tests/test_obs.py tests/test_opprof.py -q
  echo "== obs: per-op attribution reports (schema-checked) =="
  # the measured laggard ledger joined to the cost model: the ranked
  # table must attribute the step (coverage floor lives in --check)
  for prog in resnet transformer; do
    python tools/op_report.py "$prog" --check > /dev/null
  done
  echo "== obs: Prometheus exposition conformance (live snapshot) =="
  python - <<'PY'
from paddle_tpu.obs.metrics import (REGISTRY, TrainMetrics,
                                    render_prometheus,
                                    validate_exposition)
from paddle_tpu.serving.metrics import ServingMetrics

sm = ServingMetrics()
sm.model("conformance-model").on_received(1)
sm.decode("conformance-decode").on_received()
tm = TrainMetrics("conformance")
tm.observe_step(10.0, n=1, examples=8)
REGISTRY.register("train", tm.name, tm)
text = render_prometheus(sm.snapshot())
problems = validate_exposition(text)
assert not problems, problems
families = {ln.split("{")[0] for ln in text.splitlines()
            if ln and not ln.startswith("#")}
for fam in ("pt_serve_", "pt_decode_", "pt_train_"):
    assert any(f.startswith(fam) for f in families), (fam, families)
print(f"exposition conformant: {len(text.splitlines())} lines, "
      f"{len(families)} series names")
PY
  echo "OBS OK"
  exit 0
fi

if [[ "${1:-}" == "data" ]]; then
  echo "== data: production data plane + wire codec + legacy readers =="
  python -m pytest tests/test_data_pipeline.py tests/test_data_codec.py \
    tests/test_data_plane.py -q -m 'not slow'
  echo "DATA OK"
  exit 0
fi

if [[ "${1:-}" == "serve" ]]; then
  echo "== serve: online serving engine + C-API drivers + decode + fleet =="
  python -m pytest tests/test_serving.py tests/test_capi_serving.py \
    tests/test_decode.py tests/test_fleet.py tests/test_kv_economics.py -q
  echo "== serve: kv-economics A/B row (schema-checked, validate_kv_economics) =="
  # prefix sharing must at least halve the same-prefix fleet's pool
  # residency (deterministic block accounting — a hard floor inside the
  # validator) and speculative decode must be token-identical to plain
  # greedy; the tokens/s speedup is recorded-or-explained
  python - <<'PY'
import json, os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import bench
from paddle_tpu.analysis.artifacts import validate_kv_economics
row = bench.bench_kv_economics(on_tpu=False, peak=1e12)
problems = validate_kv_economics(row)
if problems:
    raise SystemExit("KV-ECONOMICS ROW INVALID:\n  "
                     + "\n  ".join(problems)
                     + "\nrow: " + json.dumps(row, indent=1))
spec = row["spec"]
print(f"kv economics ok: capacity {row['capacity_ratio_x']}x "
      f"({row['arms']['unshared']['high_water_blocks']} -> "
      f"{row['arms']['shared']['high_water_blocks']} blocks), spec "
      f"{spec['speedup_x']}x at acceptance {spec['acceptance_rate']}"
      f"{' (explained)' if 'explanation' in spec else ''}, "
      f"token-identical both legs")
PY
  echo "SERVE OK"
  exit 0
fi

if [[ "${1:-}" == "analyze" ]]; then
  echo "== analyze: cost model + memory estimator + collective audit =="
  python -m pytest tests/test_cost_model.py tests/test_analysis.py \
    tests/test_planner.py tests/test_schedule.py tests/test_calibrate.py -q
  echo "== analyze: schema-checked cost reports (bench programs) =="
  for prog in resnet transformer decode; do
    python tools/cost_report.py "$prog" --check > /dev/null
  done
  # the dryrun meshes: per-collective byte volumes reported and
  # schema-checked on the transpiled transformer
  python tools/cost_report.py transformer --check \
    --mesh dp=8 --mesh dp=4,tp=2 --mesh dp=2,sp=2,tp=2 > /dev/null
  # the auto-pp rewrite: stage-cut table + pipelined costing
  python tools/cost_report.py transformer --check --pp 2 > /dev/null
  echo "== analyze: placement planner (schema-checked plans) =="
  # decode is inference-shaped (batch = engine slots); the training
  # builders plan at a dp-splittable batch
  python tools/plan.py resnet --batch 8 --check > /dev/null
  python tools/plan.py transformer --batch 8 --check > /dev/null
  # the pp axis: pipeline-transpiled transformer, pp x dp candidates +
  # the per-collective algorithm table, floors checked
  python tools/plan.py transformer --batch 8 --pp 2 --microbatches 4 \
    --check > /dev/null
  python tools/plan.py decode --batch 2 --infer --check > /dev/null
  echo "== analyze: calibration round-trip (op_report --fit -> plan"
  echo "   --calibration; artifact floor-checked) =="
  # BENCH_TFM_* pinned to the rank gate's GATE_CFG dims, so the fitted
  # artifact's fingerprint stamp matches the gate program exactly
  CALIB_TMP="$(mktemp -d)"
  trap 'rm -rf "$CALIB_TMP"' EXIT
  BENCH_TFM_VOCAB=64 BENCH_TFM_SEQ=256 BENCH_TFM_LAYERS=2 \
    BENCH_TFM_DMODEL=64 BENCH_TFM_HEADS=4 BENCH_TFM_DFF=256 \
    python tools/op_report.py transformer --batch 8 \
    --fit "$CALIB_TMP/calibration.json" > /dev/null
  python - "$CALIB_TMP/calibration.json" <<'PYEOF'
import json, sys
from paddle_tpu.analysis.artifacts import validate_calibration
doc = json.load(open(sys.argv[1]))
problems = validate_calibration(doc)
if problems:
    sys.exit("CALIBRATION ARTIFACT INVALID:\n  " + "\n  ".join(problems))
print(f"calibration artifact ok: version={doc['version']} "
      f"chip={doc['chip']} factors={len(doc['factors'])}")
PYEOF
  BENCH_TFM_VOCAB=64 BENCH_TFM_SEQ=256 BENCH_TFM_LAYERS=2 \
    BENCH_TFM_DMODEL=64 BENCH_TFM_HEADS=4 BENCH_TFM_DFF=256 \
    python tools/plan.py transformer \
    --calibration "$CALIB_TMP/calibration.json" --check > /dev/null
  echo "== analyze: planner rank-correlation gate (predicted vs measured"
  echo "   step-time ordering over the hand-picked dryrun meshes;"
  echo "   calibrated arm must rank no worse than raw) =="
  python tools/plan.py transformer --rank-gate \
    --calibration "$CALIB_TMP/calibration.json"
  echo "ANALYZE OK"
  exit 0
fi

if [[ "${1:-}" == "fusion" ]]; then
  echo "== fusion: conv-epilogue fusion + shared autotune harness suites =="
  python -m pytest tests/test_conv_fusion.py tests/test_gconv_autotune.py -q
  echo "== fusion: bench_resnet fused-vs-unfused A/B (schema-checked) =="
  BENCH_STEPS="${BENCH_STEPS:-2}" BENCH_BATCH="${BENCH_BATCH:-2}" \
    python - <<'PY'
import json
import bench
out = bench.bench_resnet(on_tpu=False, peak=1e12)
row = out.get("fusion_ab")
from paddle_tpu.analysis.artifacts import validate_fusion_ab
problems = validate_fusion_ab(row)
if problems:
    raise SystemExit("FUSION A/B ROW INVALID:\n  "
                     + "\n  ".join(problems)
                     + "\nrow: " + json.dumps(row, indent=1))
print(f"fusion A/B ok: {row['arms']['fused']['fused_ops']} fused ops, "
      f"speedup {row['speedup']}x"
      f"{' (explained)' if 'explanation' in row else ''}, parity delta "
      f"{row['parity']['loss_delta_rel']} (tol "
      f"{row['parity']['tolerance']}), attribution coverage "
      f"{row['op_attribution_coverage']}%")
PY
  echo "FUSION OK"
  exit 0
fi

if [[ "${1:-}" == "perf" ]]; then
  echo "== perf: async hot path + compile cache + learning probe =="
  python -m pytest tests/test_async_hotpath.py tests/test_transformer_learns.py -q
  echo "PERF OK"
  exit 0
fi

echo "== unit + integration tests (8-device virtual CPU mesh) =="
# jax's "Explicitly requested dtype int64 ... truncated" warning is promoted
# to an error: device dtypes must be chosen explicitly (32-bit), never left
# to silent truncation.
python -m pytest tests/ -x -q -W "error:Explicitly requested dtype"

echo "== multi-chip dryrun (dp x tp, dp x sp x tp, pp x dp, ep x dp) =="
python __graft_entry__.py dryrun 8

if [[ "${1:-}" != "quick" ]]; then
  echo "== bench sanity (tiny shapes, persistent compile cache on) =="
  # bench.py turns the cache on itself (core/compile_cache.py: at
  # JAX_COMPILATION_CACHE_DIR when set, else .xla_cache/ in the checkout):
  # the second CI run warm-starts every config's compile; per-config
  # JSON carries compile_cache=cold|warm
  BENCH_SANITY_OUT="${TMPDIR:-/tmp}/pt_ci_bench_sanity.json"
  BENCH_STEPS=1 BENCH_BATCH=2 python bench.py | tee "$BENCH_SANITY_OUT"
  # the static cost model must attribute EVERY training config: any
  # config that reports a measured step (ms_per_batch) must carry the
  # roofline prediction beside it (predicted_mfu_pct + declared bound)
  python - "$BENCH_SANITY_OUT" <<'PY'
import json, sys
def docs(path):
    # parse each line once; skip stray stdout lines that merely start
    # with "{" (a dict repr in a warning must not crash the scan)
    for l in open(path):
        if not l.startswith("{"):
            continue
        try:
            yield json.loads(l)
        except json.JSONDecodeError:
            continue
doc = next(d for d in docs(sys.argv[1]) if "configs" in d)
missing = [n for n, c in doc["configs"].items()
           if isinstance(c, dict) and "ms_per_batch" in c
           and not ("predicted_mfu_pct" in c and "bound" in c)]
assert not missing, f"configs without roofline prediction: {missing}"
# every measured training config carries the per-op attribution block,
# and the headline configs must have actually attributed (top_ops) —
# a laggard hunt that silently skipped resnet is not observability
no_attr = [n for n, c in doc["configs"].items()
           if isinstance(c, dict) and "ms_per_batch" in c
           and not isinstance(c.get("op_attribution"), dict)]
assert not no_attr, f"configs without op_attribution: {no_attr}"
for name in ("resnet50", "transformer"):
    attr = doc["configs"].get(name, {}).get("op_attribution", {})
    assert attr.get("top_ops"), f"{name}: op_attribution has no top_ops"
    assert attr.get("coverage_pct", 0) >= 90.0, \
        f"{name}: attribution coverage {attr.get('coverage_pct')} < 90%"
print(f"bench sanity: predicted_mfu + bound + op_attribution present on "
      f"all {sum(1 for c in doc['configs'].values() if isinstance(c, dict) and 'ms_per_batch' in c)} measured configs")
PY
fi

echo "CI OK"
