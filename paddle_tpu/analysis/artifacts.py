"""Artifact sanity: schema + physical-floor checks for measurement JSON.

Round 5's gconv autotuner cached physically impossible 0.0 ms readings
and decided kernel formulations from them (VERDICT Weak #4). The fix is
structural, not a one-off: the autotune cache is validated at save AND
at load (utils/gconv_autotune.py — poisoned entries are dropped and
re-measure); tools/verify_program.py --autotune-cache re-checks the
file after the fact. validate_bench_json holds the same floors over any
JSON document: validate_plan and validate_cost_report call it on the
cost model's predictions.

Floors: MS_FLOOR is deliberately conservative — a reading at or below
0.05 ms is indistinguishable from the failure modes chain_timer.py
documents (deduped dispatches, DCE'd loops, broken carry chains), so it
is treated as untrustworthy even though the fastest genuine kernels can
brush against it; the cost of a false rejection is one re-measure and a
native-formulation fallback, the cost of trusting a fake 0.0 is a wrong
formulation pinned forever (round 5 shipped exactly that). Nothing a
single chip runs takes >= MS_CEILING (an hour) per iteration.
"""

from __future__ import annotations

import math
from typing import Dict, List

#: readings at or below this are physically impossible on this fabric
MS_FLOOR = 0.05
#: readings above this are runaway-clock garbage, not measurements
MS_CEILING = 3.6e6


def _bad_ms(value) -> bool:
    try:
        v = float(value)
    except (TypeError, ValueError):
        return True
    return not math.isfinite(v) or v <= MS_FLOOR or v >= MS_CEILING


def check_autotune_entry(key: str, ent: dict,
                         decision_field: str = "prefers_dense",
                         ms_fields=("native_ms", "dense_ms")) -> List[str]:
    """Problems with one autotune cache entry ([] = valid).

    Parameterized per cache namespace (utils/kernel_autotune.py):
    `decision_field` is the entry key carrying that namespace's
    fallback-safe decision (gconv: prefers_dense; fused conv epilogue:
    prefers_pallas) and `ms_fields` its measured candidates. Defaults
    keep the historical gconv contract.

    Entries that *declare* themselves non-measurements are legal:
    {"error": ...} (measurement raised) and {"invalid": True} (readings
    rejected twice) both carry the decision field's fallback.
    """
    if not isinstance(ent, dict):
        return [f"{key}: entry is {type(ent).__name__}, not an object"]
    if decision_field not in ent:
        return [f"{key}: missing required field {decision_field!r}"]
    if ent.get("error") or ent.get("invalid"):
        return []
    problems = []
    for field in ms_fields:
        if field not in ent:
            problems.append(f"{key}: missing measurement field {field!r}")
        elif _bad_ms(ent[field]):
            problems.append(
                f"{key}: {field}={ent[field]!r} is outside the physical "
                f"band ({MS_FLOOR}, {MS_CEILING}) ms — impossible reading")
    return problems


def validate_autotune_cache(cache: dict,
                            decision_field: str = "prefers_dense",
                            ms_fields=("native_ms", "dense_ms")) -> List[str]:
    """Problems across a whole autotune cache dict ([] = valid).

    Accepts both the legacy flat dict and the schema-versioned
    ``{"schema": N, "entries": {...}}`` envelope (which tools pass
    through verbatim from disk)."""
    if not isinstance(cache, dict):
        return [f"cache root is {type(cache).__name__}, not an object"]
    if "schema" in cache and isinstance(cache.get("entries"), dict):
        cache = cache["entries"]
    problems: List[str] = []
    for key, ent in cache.items():
        problems.extend(check_autotune_entry(str(key), ent,
                                             decision_field, ms_fields))
    return problems


def filter_autotune_cache(cache: dict,
                          decision_field: str = "prefers_dense",
                          ms_fields=("native_ms", "dense_ms")
                          ) -> Dict[str, dict]:
    """Drop entries with impossible readings (load-time self-heal); the
    dropped keys simply re-measure on next use."""
    return {k: v for k, v in cache.items()
            if not check_autotune_entry(str(k), v, decision_field,
                                        ms_fields)}


_MS_KEY_MARKERS = ("_ms", "ms_per_batch", "ms_per_step")
_RATIO_KEY_MARKERS = ("mfu", "hfu")
#: keys marking MODEL OUTPUTS of the static cost model (analysis/cost.py)
#: rather than instrument readings: the measurement band does not apply
#: (a tiny CPU-shape config legitimately predicts microsecond steps) but
#: negative/zero work or >100% predicted utilization is still impossible.
#: "attribution" covers the per-op ledger (obs/opprof.py): its rows are
#: cost-share SLICES of a step, legitimately far below the whole-step
#: floor — validate_op_report applies the band to the ledger's total.
_PREDICTION_MARKERS = ("predict", "prediction", "attribution")
#: prediction fields that must be strictly positive: a step whose model
#: says zero flops / zero HBM traffic / zero time was mis-analyzed, the
#: cost-model analogue of the 0.0 ms autotune poisonings. (predicted_mfu
#: itself may legitimately round to 0 — only the >100% side is impossible)
_PRED_POSITIVE = ("flops", "hbm_bytes", "predicted_step_ms")
_PRED_BOUNDS = ("compute", "bandwidth", "comm", "host")


def _bad_pred_num(value) -> bool:
    try:
        v = float(value)
    except (TypeError, ValueError):
        return True
    return not math.isfinite(v) or v < 0


def validate_bench_json(doc, path: str = "$", pred: bool = False) -> List[str]:
    """Recursive floor checks over a measurement JSON document.

    Any numeric field whose key names a millisecond reading must sit in
    the physical band; MFU/HFU-style ratios must be finite and
    non-negative. Cost-model prediction fields (keys/objects naming
    "predicted"/"prediction") get prediction rules instead: finite and
    non-negative everywhere, strictly positive flops / hbm_bytes /
    predicted_step_ms (predicted_mfu may round to 0 but never exceeds
    100%), bound in {compute, bandwidth, comm, host}. Schema-agnostic
    on purpose: a layout may drift between rounds, impossible numbers
    never become legitimate.
    """
    problems: List[str] = []
    if isinstance(doc, dict):
        for k, v in doc.items():
            here = f"{path}.{k}"
            lk = str(k).lower()
            in_pred = pred or any(m in lk for m in _PREDICTION_MARKERS)
            if isinstance(v, (dict, list)):
                problems.extend(validate_bench_json(v, here, pred=in_pred))
                continue
            if lk == "bound" and isinstance(v, str):
                # the declared roofline bound — checked wherever it
                # appears: a report carries it at config level (where
                # the measured-host override lands), not only inside
                # the prediction object
                if v not in _PRED_BOUNDS:
                    problems.append(
                        f"{here}: declared bound {v!r} is not one of "
                        f"{list(_PRED_BOUNDS)}")
                continue
            if not isinstance(v, (int, float)) or isinstance(v, bool):
                continue
            if in_pred:
                if _bad_pred_num(v):
                    problems.append(
                        f"{here}: prediction value {v!r} is not a finite "
                        "non-negative number")
                elif any(m in lk for m in _PRED_POSITIVE) and float(v) <= 0:
                    problems.append(
                        f"{here}: {v!r} — zero/negative predicted work is "
                        "a mis-analyzed program, not a prediction")
                elif "mfu" in lk:
                    hi = 101.0 if "pct" in lk else 1.01
                    if float(v) > hi:
                        problems.append(
                            f"{here}: predicted utilization {v!r} exceeds "
                            f"{hi} — over-100% MFU is impossible")
            elif any(m in lk for m in _MS_KEY_MARKERS) and _bad_ms(v):
                problems.append(
                    f"{here}: {v!r} ms is outside the physical band "
                    f"({MS_FLOOR}, {MS_CEILING})")
            elif any(m in lk for m in _RATIO_KEY_MARKERS):
                # >100% hardware utilization is as impossible as a
                # 0.0 ms reading; percent-style keys (mfu_pct) cap at
                # 100, fraction-style at 1.0 (small slack for fp noise)
                hi = 101.0 if "pct" in lk else 1.01
                if not math.isfinite(float(v)) or v < 0 or v > hi:
                    problems.append(
                        f"{here}: utilization ratio {v!r} is outside "
                        f"[0, {hi}] — impossible reading")
    elif isinstance(doc, list):
        for i, v in enumerate(doc):
            problems.extend(validate_bench_json(v, f"{path}[{i}]",
                                                pred=pred))
    return problems


_PLAN_REQUIRED = ("schema_version", "kind", "batch", "topology", "ranked")
_PLAN_ENTRY_REQUIRED = ("mesh", "specs", "prediction", "peak_hbm_bytes")
#: the reduction algorithms the comm cost formulas implement. ONE
#: alphabet — comm.ALGORITHMS re-exports this tuple, so the validator
#: can never drift from the implementation (artifacts.py is the import
#: leaf: stdlib-only, everything above imports down to it)
PLAN_ALGORITHMS = ("ring", "tree", "hierarchical")
#: the microbatch schedules parallel/pipeline.py executes.
#: analysis/schedule.SCHEDULES re-exports this tuple; 1f1b first — the
#: planner's preference order among time-equal candidates (lower stash)
PLAN_SCHEDULES = ("1f1b", "gpipe")


def _check_plan_pipeline(plan: dict, here: str) -> List[str]:
    """pp-plan floors: a plan whose mesh names a pp axis > 1 must carry
    a coherent pipeline schedule record (finite bubble fraction in
    [0, 1), a stage count dividing the pp axis, positive microbatches, a
    schedule the runtime implements) and a NON-EMPTY per-collective
    algorithm table with known algorithms — a pp plan that recorded no
    schedule or no reduction choice is the placement analogue of a
    0.0 ms autotune reading."""
    problems: List[str] = []
    mesh = plan.get("mesh") or {}
    pp = mesh.get("pp") if isinstance(mesh, dict) else None
    is_pp = isinstance(pp, int) and not isinstance(pp, bool) and pp > 1
    pipe = plan.get("pipeline")
    if not is_pp:
        if pipe is not None and not isinstance(pipe, dict):
            problems.append(f"{here}.pipeline: not an object")
        return problems
    if not isinstance(pipe, dict):
        problems.append(
            f"{here}.pipeline: missing/malformed — a plan over a pp axis "
            "must record its stages/microbatches/schedule")
        pipe = {}
    bf = pipe.get("bubble_fraction")
    if not isinstance(bf, (int, float)) or isinstance(bf, bool) \
            or not math.isfinite(float(bf)) or not 0.0 <= float(bf) < 1.0:
        problems.append(
            f"{here}.pipeline.bubble_fraction: {bf!r} must be a finite "
            "fraction in [0, 1) — a full-bubble (or NaN) pipeline does "
            "no work")
    stages = pipe.get("stages")
    if not isinstance(stages, int) or isinstance(stages, bool) \
            or stages != pp:
        problems.append(
            f"{here}.pipeline.stages: {stages!r} must equal the pp axis "
            f"({pp}) — the schedule runs exactly one stage per pp device "
            "(ops/pipeline_ops.py rejects anything else at lowering)")
    mb = pipe.get("microbatches")
    if not isinstance(mb, int) or isinstance(mb, bool) or mb < 1:
        problems.append(f"{here}.pipeline.microbatches: {mb!r} must be "
                        "a positive int")
    if pipe.get("schedule") not in PLAN_SCHEDULES:
        problems.append(
            f"{here}.pipeline.schedule: {pipe.get('schedule')!r} is not "
            f"one of {list(PLAN_SCHEDULES)}")
    colls = plan.get("collectives")
    if not isinstance(colls, list) or not colls:
        problems.append(
            f"{here}.collectives: missing/empty — a pp plan must record "
            "its per-collective reduction-algorithm table")
    return problems


def validate_plan(doc) -> List[str]:
    """Floor checks for a placement-plan artifact (analysis/planner.py),
    applied at plan SAVE and LOAD like the gconv-autotune floors
    ([] = valid): schema-versioned, non-empty ranked list, and for every
    ranked plan a non-empty per-var spec table, finite strictly-positive
    predicted step time, predicted MFU <= 100%, and a per-device
    peak-HBM at or under the topology's declared chip HBM. A plan that
    fails these is the placement analogue of a 0.0 ms autotune reading —
    it must never be applied."""
    if not isinstance(doc, dict):
        return [f"plan root is {type(doc).__name__}, not an object"]
    problems = [f"$.{k}: required field missing"
                for k in _PLAN_REQUIRED if k not in doc]
    if doc.get("kind") not in (None, "placement_plan"):
        problems.append(f"$.kind: {doc.get('kind')!r} is not "
                        "'placement_plan'")
    if "schema_version" in doc and doc["schema_version"] != 1:
        problems.append(f"$.schema_version: {doc['schema_version']!r} is "
                        "not a known version (1)")
    hbm_budget = None
    topo = doc.get("topology")
    if isinstance(topo, dict):
        gb = topo.get("hbm_gb")
        if isinstance(gb, (int, float)) and not isinstance(gb, bool) \
                and math.isfinite(float(gb)) and gb > 0:
            hbm_budget = float(gb) * 1e9
        else:
            problems.append(f"$.topology.hbm_gb: {gb!r} must be a "
                            "positive finite number of gigabytes")
    ranked = doc.get("ranked")
    if "ranked" in doc and not isinstance(ranked, list):
        problems.append(f"$.ranked: {type(ranked).__name__}, not a list")
    if isinstance(ranked, list) and not ranked:
        problems.append("$.ranked: empty — a plan artifact must rank at "
                        "least one feasible placement")
    for i, plan in enumerate(ranked if isinstance(ranked, list) else ()):
        here = f"$.ranked[{i}]"
        if not isinstance(plan, dict):
            problems.append(f"{here}: not an object")
            continue
        problems.extend(f"{here}.{k}: required field missing"
                        for k in _PLAN_ENTRY_REQUIRED if k not in plan)
        mesh = plan.get("mesh")
        if isinstance(mesh, dict):
            for a, s in mesh.items():
                if not isinstance(s, int) or isinstance(s, bool) or s < 1:
                    problems.append(f"{here}.mesh.{a}: size {s!r} must be "
                                    "a positive integer")
        specs = plan.get("specs")
        if isinstance(specs, dict) and not specs:
            problems.append(f"{here}.specs: empty per-var spec table — "
                            "a plan that places nothing is not a plan")
        pred = plan.get("prediction")
        if isinstance(pred, dict):
            problems.extend(validate_bench_json(pred, f"{here}.prediction",
                                                pred=True))
            mfu = pred.get("predicted_mfu")
            if not isinstance(mfu, (int, float)) or isinstance(mfu, bool) \
                    or not math.isfinite(float(mfu)):
                problems.append(f"{here}.prediction.predicted_mfu: "
                                f"{mfu!r} is not a finite number")
        peak = plan.get("peak_hbm_bytes")
        if not isinstance(peak, (int, float)) or isinstance(peak, bool) \
                or not math.isfinite(float(peak)) or peak <= 0:
            problems.append(f"{here}.peak_hbm_bytes: {peak!r} must be a "
                            "positive finite byte count")
        elif hbm_budget is not None and float(peak) > hbm_budget:
            problems.append(
                f"{here}.peak_hbm_bytes: {float(peak) / 1e9:.2f} GB "
                f"exceeds the declared chip HBM "
                f"{hbm_budget / 1e9:.2f} GB — an over-budget plan must "
                "never rank")
        problems.extend(_check_plan_pipeline(plan, here))
        colls = plan.get("collectives")
        for j, c in enumerate(colls if isinstance(colls, list) else ()):
            algo = c.get("algorithm") if isinstance(c, dict) else None
            if algo not in PLAN_ALGORITHMS:
                problems.append(
                    f"{here}.collectives[{j}].algorithm: {algo!r} is not "
                    f"one of {list(PLAN_ALGORITHMS)}")
    return problems


_COST_REPORT_REQUIRED = ("program", "batch", "cost", "memory", "prediction")


def validate_cost_report(doc) -> List[str]:
    """Schema + floor checks for a tools/cost_report.py document
    ([] = valid). Applied by the CLI itself under --check (the
    scripts/ci.sh analyze leg) and safe to run on a loaded report."""
    if not isinstance(doc, dict):
        return [f"report root is {type(doc).__name__}, not an object"]
    problems = [f"$.{k}: required section missing"
                for k in _COST_REPORT_REQUIRED if k not in doc]
    cost = doc.get("cost")
    if isinstance(cost, dict):
        for k in ("train_flops", "train_bytes"):
            v = cost.get(k)
            if not isinstance(v, (int, float)) or _bad_pred_num(v) or v <= 0:
                problems.append(f"$.cost.{k}: {v!r} must be a positive "
                                "finite number")
    mem = doc.get("memory")
    if isinstance(mem, dict):
        v = mem.get("peak_bytes")
        if not isinstance(v, (int, float)) or _bad_pred_num(v) or v <= 0:
            problems.append(f"$.memory.peak_bytes: {v!r} must be a "
                            "positive finite number")
        for k, bv in (mem.get("breakdown") or {}).items():
            if not isinstance(bv, (int, float)) or _bad_pred_num(bv):
                problems.append(f"$.memory.breakdown.{k}: {bv!r} must be "
                                "a finite non-negative number")
    pred = doc.get("prediction")
    if isinstance(pred, dict):
        problems.extend(validate_bench_json(pred, "$.prediction",
                                            pred=True))
        for k in ("predicted_mfu", "bound"):
            if k not in pred:
                problems.append(f"$.prediction.{k}: required field missing")
    for mesh_key, comm in (doc.get("comm") or {}).items():
        if not isinstance(comm, dict):
            problems.append(f"$.comm.{mesh_key}: not an object")
            continue
        v = comm.get("total_wire_bytes")
        if not isinstance(v, (int, float)) or _bad_pred_num(v):
            problems.append(f"$.comm.{mesh_key}.total_wire_bytes: {v!r} "
                            "must be a finite non-negative number")
    return problems


_OP_REPORT_REQUIRED = ("program", "batch", "chip", "attribution")
_OP_ROW_REQUIRED = ("type", "name", "phase", "predicted_ms", "covered")


def validate_op_report(doc) -> List[str]:
    """Schema + floor checks for a tools/op_report.py document
    ([] = valid) — the per-op attribution ledger (obs/opprof.py).

    Floors (the gconv discipline at ledger scale): the attributed total
    is finite, positive and under the physical ceiling; the coverage
    gauge sits in [0, 100]; every row's predicted/measured values are
    finite and non-negative (per-op SLICES of a step legitimately sit
    under the whole-step MS_FLOOR, so the measurement band applies to
    the total, not the rows); per-op MFU never exceeds 100%; measured
    rows' shares sum to ~100% — a ledger that attributes more (or much
    less) time than it measured mis-joined somewhere.
    """
    if not isinstance(doc, dict):
        return [f"op report root is {type(doc).__name__}, not an object"]
    problems = [f"$.{k}: required field missing"
                for k in _OP_REPORT_REQUIRED if k not in doc]
    attr = doc.get("attribution")
    if not isinstance(attr, dict):
        if "attribution" in doc:
            problems.append("$.attribution: not an object")
        return problems
    total = attr.get("total_measured_ms")
    if not isinstance(total, (int, float)) or isinstance(total, bool) \
            or not math.isfinite(float(total)) or total <= 0 \
            or total >= MS_CEILING:
        problems.append(
            f"$.attribution.total_measured_ms: {total!r} must be a "
            f"positive finite reading under {MS_CEILING} ms — a ledger "
            "with no measured time attributed nothing")
    cov = attr.get("coverage_pct")
    if not isinstance(cov, (int, float)) or isinstance(cov, bool) \
            or not math.isfinite(float(cov)) or cov < 0 or cov > 100.0:
        problems.append(f"$.attribution.coverage_pct: {cov!r} must sit "
                        "in [0, 100]")
    rows = attr.get("rows")
    if not isinstance(rows, list) or not rows:
        problems.append("$.attribution.rows: empty/missing — a ledger "
                        "that names no ops is not an attribution")
        rows = []
    share_sum = 0.0
    any_measured = False
    for i, row in enumerate(rows):
        here = f"$.attribution.rows[{i}]"
        if not isinstance(row, dict):
            problems.append(f"{here}: not an object")
            continue
        problems.extend(f"{here}.{k}: required field missing"
                        for k in _OP_ROW_REQUIRED if k not in row)
        for k in ("predicted_ms", "measured_ms", "share_pct"):
            v = row.get(k)
            if v is not None and _bad_pred_num(v):
                problems.append(f"{here}.{k}: {v!r} is not a finite "
                                "non-negative number")
        mfu = row.get("mfu_pct")
        if mfu is not None and (_bad_pred_num(mfu) or float(mfu) > 101.0):
            problems.append(f"{here}.mfu_pct: {mfu!r} — per-op MFU over "
                            "100% is impossible")
        if isinstance(row.get("share_pct"), (int, float)) \
                and not isinstance(row.get("share_pct"), bool) \
                and math.isfinite(float(row["share_pct"])):
            share_sum += float(row["share_pct"])
        if row.get("measured_ms") is not None:
            any_measured = True
    if rows and not any_measured:
        problems.append("$.attribution.rows: no row carries a measured "
                        "reading — nothing was actually profiled")
    if any_measured and not (99.0 <= share_sum <= 101.0):
        problems.append(
            f"$.attribution.rows: measured shares sum to {share_sum:.2f}%"
            " — attribution must account for ~100% of the measured step")
    return problems


# ---------------------------------------------------------------------------
# cost-calibration artifact floors (analysis/calibrate.py)
# ---------------------------------------------------------------------------

#: declared validity band for a per-op-type correction factor: a factor
#: at/below the floor says the model over-predicts 20x+ (that is a
#: broken fit, not a correction), one at/above the ceiling says the
#: measurement was garbage (the 0.0 ms autotune poisoning, inverted).
#: The FIT clamps into a narrower band (calibrate.FIT_FACTOR_BAND);
#: this band is what save/load refuses outright.
CALIB_FACTOR_FLOOR = 0.05
CALIB_FACTOR_CEILING = 20.0
#: a per-dispatch collective launch overhead of a full second is not a
#: fabric constant on any hardware this repo prices — it is a clock bug
CALIB_OVERHEAD_CEILING_S = 1.0

_CALIB_REQUIRED = ("schema_version", "kind", "chip", "jax", "factors",
                   "samples", "dispatch_overhead_s")


def validate_calibration(doc) -> List[str]:
    """Floor checks for a cost-calibration artifact
    (analysis/calibrate.py), applied at SAVE and LOAD like the
    gconv-autotune floors ([] = valid): schema-versioned, every per-op-
    type factor finite and inside the declared band, every factor's fit
    sample count recorded as a positive int, the fitted per-dispatch
    collective overhead finite/non-negative/under the ceiling, and the
    chip + jax-version provenance stamped. A calibration that fails
    these is the cost-model analogue of a 0.0 ms autotune reading — it
    must never correct a prediction."""
    if not isinstance(doc, dict):
        return [f"calibration root is {type(doc).__name__}, not an object"]
    problems = [f"$.{k}: required field missing"
                for k in _CALIB_REQUIRED if k not in doc]
    if doc.get("kind") not in (None, "cost_calibration"):
        problems.append(f"$.kind: {doc.get('kind')!r} is not "
                        "'cost_calibration'")
    if "schema_version" in doc and doc["schema_version"] != 1:
        problems.append(f"$.schema_version: {doc['schema_version']!r} is "
                        "not a known version (1)")
    chip = doc.get("chip")
    if "chip" in doc and (not isinstance(chip, str) or not chip.strip()):
        problems.append(f"$.chip: {chip!r} — the fitted chip must be "
                        "stamped (stale-calibration refusal keys on it)")
    jaxv = doc.get("jax")
    if "jax" in doc and not isinstance(jaxv, str):
        problems.append(f"$.jax: {jaxv!r} is not a version string")
    factors = doc.get("factors")
    samples = doc.get("samples")
    if "factors" in doc and not isinstance(factors, dict):
        problems.append(f"$.factors: {type(factors).__name__}, not an "
                        "object")
        factors = {}
    if "samples" in doc and not isinstance(samples, dict):
        problems.append(f"$.samples: {type(samples).__name__}, not an "
                        "object")
        samples = {}
    for op_type, f in (factors or {}).items():
        if not isinstance(f, (int, float)) or isinstance(f, bool) \
                or not math.isfinite(float(f)) \
                or not CALIB_FACTOR_FLOOR < float(f) < CALIB_FACTOR_CEILING:
            problems.append(
                f"$.factors.{op_type}: {f!r} must be a finite factor "
                f"strictly inside ({CALIB_FACTOR_FLOOR}, "
                f"{CALIB_FACTOR_CEILING}) — outside the band it is a "
                "broken fit, not a correction")
        n = (samples or {}).get(op_type)
        if not isinstance(n, int) or isinstance(n, bool) or n < 1:
            problems.append(
                f"$.samples.{op_type}: {n!r} — every factor must record "
                "its positive fit sample count")
    ovh = doc.get("dispatch_overhead_s")
    if "dispatch_overhead_s" in doc and (
            not isinstance(ovh, (int, float)) or isinstance(ovh, bool)
            or not math.isfinite(float(ovh)) or float(ovh) < 0
            or float(ovh) >= CALIB_OVERHEAD_CEILING_S):
        problems.append(
            f"$.dispatch_overhead_s: {ovh!r} must be a finite "
            f"non-negative overhead under {CALIB_OVERHEAD_CEILING_S} s")
    fps = doc.get("fingerprints")
    if fps is not None:
        if not isinstance(fps, list) \
                or not all(isinstance(f, str) and f for f in fps):
            problems.append("$.fingerprints: must be a list of non-empty "
                            "program-fingerprint strings when present")
    return problems


# ---------------------------------------------------------------------------
# staged A/B floors (fleet, feed codec, conv fusion, KV economics)
# ---------------------------------------------------------------------------

#: required per-policy arm fields of the codec A/B
_CODEC_ARM_REQUIRED = ("wire_bytes_ratio", "delivered_images_per_sec")


#: per-arm fields the fleet A/B must record
_FLEET_ARM_REQUIRED = ("replicas", "requests", "rps", "p95_ms")


def validate_fleet_ab(doc) -> List[str]:
    """Floor checks for a `fleet` staged A/B ([] = valid) —
    the gconv pattern applied to the replica tier: an impossible
    reading must never be committed as a measurement.

      * every measured arm records a finite positive rps, a positive
        replica count, and per-class p95 latencies (finite, positive);
      * the throughput-scaling ratio is finite and positive (whether it
        MEETS the 2.5x acceptance is a warning on the row, not a floor
        — a genuine 1.8x is a measurement, a NaN is not);
      * the overload leg records per-class shed counts (non-negative
        ints, total > 0 — an overload leg that shed nothing measured
        nothing) and a free_shed_share in [0, 1];
      * the chaos leg records dropped_in_flight (the zero-drop count
        must be PRESENT — absence would read as 'no drops' when the
        leg never ran) and a positive completed count.
    """
    if not isinstance(doc, dict):
        return [f"fleet A/B root is {type(doc).__name__}, not an object"]
    problems: List[str] = []
    arms = doc.get("arms")
    if not isinstance(arms, dict) or len(arms) < 2:
        problems.append("$.arms: the A/B needs >= 2 measured arms")
        arms = {}
    for key, arm in arms.items():
        here = f"$.arms.{key}"
        if not isinstance(arm, dict):
            problems.append(f"{here}: not an object")
            continue
        for k in _FLEET_ARM_REQUIRED:
            if k not in arm:
                problems.append(f"{here}.{k}: required field missing")
        rps = arm.get("rps")
        if rps is not None and (_bad_pred_num(rps) or float(rps) <= 0):
            problems.append(f"{here}.rps: {rps!r} must be finite and "
                            "positive")
        nrep = arm.get("replicas")
        if nrep is not None and (not isinstance(nrep, int) or nrep < 1):
            problems.append(f"{here}.replicas: {nrep!r} must be a "
                            "positive int")
        for cls, v in (arm.get("p95_ms") or {}).items():
            if v is None or _bad_pred_num(v) or float(v) <= 0:
                problems.append(f"{here}.p95_ms.{cls}: {v!r} must be "
                                "finite and positive")
    scaling = doc.get("throughput_scaling_x")
    if scaling is None or _bad_pred_num(scaling) or float(scaling) <= 0:
        problems.append(f"$.throughput_scaling_x: {scaling!r} must be "
                        "recorded, finite, positive")
    over = doc.get("overload")
    if not isinstance(over, dict):
        problems.append("$.overload: shed leg not recorded")
    else:
        sheds = over.get("sheds_by_class")
        if not isinstance(sheds, dict) or not sheds:
            problems.append("$.overload.sheds_by_class: missing")
        else:
            bad = [f"{c}={n!r}" for c, n in sheds.items()
                   if not isinstance(n, int) or n < 0]
            if bad:
                problems.append("$.overload.sheds_by_class: "
                                f"non-counts {bad}")
            elif sum(sheds.values()) <= 0:
                problems.append(
                    "$.overload.sheds_by_class: zero total sheds — the "
                    "overload leg measured no overload")
        share = over.get("free_shed_share")
        if share is None or _bad_pred_num(share) \
                or not 0.0 <= float(share) <= 1.0:
            problems.append(f"$.overload.free_shed_share: {share!r} "
                            "must be recorded in [0, 1]")
    chaos = doc.get("chaos")
    if not isinstance(chaos, dict):
        problems.append("$.chaos: crash/scale-down leg not recorded")
    else:
        drops = chaos.get("dropped_in_flight")
        if not isinstance(drops, int) or drops < 0:
            problems.append(f"$.chaos.dropped_in_flight: {drops!r} — "
                            "the zero-drop count must be recorded as a "
                            "non-negative int")
        comp = chaos.get("completed")
        if not isinstance(comp, int) or comp <= 0:
            problems.append(f"$.chaos.completed: {comp!r} must be a "
                            "positive int")
    return problems


def validate_codec_ab(doc) -> List[str]:
    """Floor checks for a `data_codec` staged A/B ([] = valid),
    the gconv pattern applied to the codec bench: an impossible reading
    must never be committed as a measurement.

      * every measured arm's wire_bytes_ratio is finite and >= 1.0 — a
        codec that INFLATES its wire bytes (or a NaN from a zero-byte
        window) is a broken measurement, not a result;
      * delivered rates are finite and positive;
      * the end-to-end parity delta is RECORDED and finite (int8 input
        quantization is lossy by design, so the gate is a calibrated
        tolerance band — but an unrecorded or NaN delta means the parity
        leg never ran, and the ratio alone proves nothing).
    """
    if not isinstance(doc, dict):
        return [f"codec A/B root is {type(doc).__name__}, not an object"]
    problems: List[str] = []
    arms = doc.get("arms")
    if not isinstance(arms, dict) or not arms:
        problems.append("$.arms: no measured codec arms recorded")
        arms = {}
    for policy, arm in arms.items():
        here = f"$.arms.{policy}"
        if not isinstance(arm, dict):
            problems.append(f"{here}: not an object")
            continue
        for k in _CODEC_ARM_REQUIRED:
            if k not in arm:
                problems.append(f"{here}.{k}: required field missing")
        ratio = arm.get("wire_bytes_ratio")
        if ratio is not None:
            if _bad_pred_num(ratio) or float(ratio) < 1.0:
                problems.append(
                    f"{here}.wire_bytes_ratio: {ratio!r} — a wire ratio "
                    "below 1x (or non-finite) is an impossible codec "
                    "measurement")
        rate = arm.get("delivered_images_per_sec")
        if rate is not None and (_bad_pred_num(rate) or float(rate) <= 0):
            problems.append(f"{here}.delivered_images_per_sec: {rate!r} "
                            "must be finite and positive")
    parity = doc.get("parity")
    if not isinstance(parity, dict):
        problems.append("$.parity: end-to-end parity leg not recorded")
    else:
        delta = parity.get("loss_delta_rel")
        if delta is None or _bad_pred_num(delta):
            problems.append(
                f"$.parity.loss_delta_rel: {delta!r} — the parity delta "
                "must be recorded as a finite non-negative number")
        if "tolerance" not in parity:
            problems.append("$.parity.tolerance: declared tolerance band "
                            "missing")
    return problems


_FUSION_ARM_REQUIRED = ("step_ms", "steps")


def validate_fusion_ab(doc) -> List[str]:
    """Floor checks for a `fusion_ab` conv-epilogue A/B
    ([] = valid) — the same impossible-reading discipline as the codec
    and gconv validators, applied to the fusion PR's acceptance row:

      * both arms (fused / unfused) measured, finite positive step_ms,
        and the fused arm actually fused something (fused_ops >= 1 — an
        A/B where the pass rewrote nothing proves nothing);
      * speedup = unfused/fused is finite and positive; a reading below
        1.0 must carry a non-empty `explanation` (e.g. a CPU rig where
        the Pallas epilogue never engages) — recorded-or-explained,
        never silent;
      * the parity leg RAN: loss_delta_rel is a finite non-negative
        number, the tolerance band is declared, and the delta sits
        inside it — speed with broken numerics is not a result;
      * the per-op attribution on the fused config covers >= 90% of
        step time, so the conv-family MFU claim rests on attributed
        time, not a sliver.
    """
    if not isinstance(doc, dict):
        return [f"fusion A/B root is {type(doc).__name__}, not an object"]
    problems: List[str] = []
    arms = doc.get("arms")
    if not isinstance(arms, dict):
        problems.append("$.arms: no measured arms recorded")
        arms = {}
    for name in ("fused", "unfused"):
        arm = arms.get(name)
        here = f"$.arms.{name}"
        if not isinstance(arm, dict):
            problems.append(f"{here}: arm not recorded")
            continue
        for k in _FUSION_ARM_REQUIRED:
            if k not in arm:
                problems.append(f"{here}.{k}: required field missing")
        ms = arm.get("step_ms")
        if ms is not None and (_bad_pred_num(ms) or float(ms) <= 0):
            problems.append(f"{here}.step_ms: {ms!r} must be finite "
                            "and positive")
    fused_arm = arms.get("fused")
    if isinstance(fused_arm, dict):
        n = fused_arm.get("fused_ops")
        if not isinstance(n, int) or n < 1:
            problems.append(
                f"$.arms.fused.fused_ops: {n!r} — the fused arm must "
                "contain at least one fused_conv2d op, else the A/B "
                "measured the pass doing nothing")
    speedup = doc.get("speedup")
    if speedup is None or _bad_pred_num(speedup) or float(speedup) <= 0:
        problems.append(f"$.speedup: {speedup!r} must be recorded as a "
                        "finite positive number")
    elif float(speedup) < 1.0:
        expl = doc.get("explanation")
        if not isinstance(expl, str) or not expl.strip():
            problems.append(
                f"$.speedup: {float(speedup):.3f} < 1.0 with no "
                "$.explanation — a slowdown must be explained, not "
                "silently recorded")
    parity = doc.get("parity")
    if not isinstance(parity, dict):
        problems.append("$.parity: fused-vs-unfused parity leg not "
                        "recorded")
    else:
        delta = parity.get("loss_delta_rel")
        tol = parity.get("tolerance")
        if delta is None or _bad_pred_num(delta) or float(delta) < 0:
            problems.append(
                f"$.parity.loss_delta_rel: {delta!r} — the parity delta "
                "must be recorded as a finite non-negative number")
        if tol is None or _bad_pred_num(tol):
            problems.append("$.parity.tolerance: declared tolerance band "
                            "missing")
        elif delta is not None and not _bad_pred_num(delta) \
                and float(delta) > float(tol):
            problems.append(
                f"$.parity.loss_delta_rel: {delta!r} exceeds the "
                f"declared tolerance {tol!r} — the fusion changed "
                "semantics")
    cov = doc.get("op_attribution_coverage")
    if cov is None or _bad_pred_num(cov) or float(cov) < 90.0:
        problems.append(
            f"$.op_attribution_coverage: {cov!r} — the fused config's "
            "per-op attribution must cover >= 90% of step time")
    return problems


#: per-arm fields the KV-economics capacity A/B must record
_KV_ARM_REQUIRED = ("high_water_blocks", "tokens_per_s")


def validate_kv_economics(doc) -> List[str]:
    """Floor checks for a `kv_economics` A/B ([] = valid) —
    the impossible-reading discipline applied to the decode plane's
    prefix-sharing + speculative-decoding row:

      * both capacity arms (unshared / shared) measured: positive-int
        pool high-water marks, finite positive delivered tokens/s;
      * the shared arm actually SHARED (shared_hits >= 1 and
        shared_tokens >= 1 — an arm that never aliased a block measured
        the feature doing nothing) and records its CoW count;
      * capacity_ratio_x is recorded AND >= 2.0. Unlike a timing, the
        ratio is deterministic block accounting (how many pool blocks N
        same-prefix sequences touch with and without aliasing), so the
        2x acceptance target is a hard floor here, not a warning;
      * both parity bits are True — greedy acceptance is token-identical
        BY CONSTRUCTION, so a False is a correctness bug being recorded
        as a measurement, never a tradeoff;
      * the speculation leg actually drafted (drafted >= 1), accepted
        within [0, drafted], acceptance_rate finite in [0, 1], step
        counts positive ints with spec <= plain (a verified draft can
        only save dispatches, never add them);
      * speedup_x is finite and positive; a reading below 1.0 must
        carry a non-empty explanation — recorded-or-explained.
    """
    if not isinstance(doc, dict):
        return [f"kv-economics root is {type(doc).__name__}, "
                "not an object"]
    problems: List[str] = []
    arms = doc.get("arms")
    if not isinstance(arms, dict):
        problems.append("$.arms: no measured capacity arms recorded")
        arms = {}
    for name in ("unshared", "shared"):
        arm = arms.get(name)
        here = f"$.arms.{name}"
        if not isinstance(arm, dict):
            problems.append(f"{here}: arm not recorded")
            continue
        for k in _KV_ARM_REQUIRED:
            if k not in arm:
                problems.append(f"{here}.{k}: required field missing")
        hw = arm.get("high_water_blocks")
        if hw is not None and (not isinstance(hw, int) or hw < 1):
            problems.append(f"{here}.high_water_blocks: {hw!r} must be "
                            "a positive int")
        tps = arm.get("tokens_per_s")
        if tps is not None and (_bad_pred_num(tps) or float(tps) <= 0):
            problems.append(f"{here}.tokens_per_s: {tps!r} must be "
                            "finite and positive")
    shared = arms.get("shared")
    if isinstance(shared, dict):
        for k in ("shared_hits", "shared_tokens"):
            n = shared.get(k)
            if not isinstance(n, int) or n < 1:
                problems.append(
                    f"$.arms.shared.{k}: {n!r} — the shared arm must "
                    "have aliased at least one prefix, else the A/B "
                    "measured sharing doing nothing")
        cow = shared.get("cow_copies")
        if not isinstance(cow, int) or cow < 0:
            problems.append(f"$.arms.shared.cow_copies: {cow!r} must "
                            "be recorded as a non-negative int")
    ratio = doc.get("capacity_ratio_x")
    if ratio is None or _bad_pred_num(ratio):
        problems.append(f"$.capacity_ratio_x: {ratio!r} must be "
                        "recorded, finite, positive")
    elif float(ratio) < 2.0:
        problems.append(
            f"$.capacity_ratio_x: {float(ratio):.2f} < 2.0 — prefix "
            "sharing must at least halve the same-prefix fleet's pool "
            "residency (deterministic block accounting, not a timing)")
    if doc.get("capacity_token_identical") is not True:
        problems.append(
            "$.capacity_token_identical: shared-prefix outputs must be "
            "token-identical to unshared (aliased rows are the same "
            "bytes the prefill would have written)")
    spec = doc.get("spec")
    if not isinstance(spec, dict):
        problems.append("$.spec: speculation leg not recorded")
        return problems
    if spec.get("token_identical") is not True:
        problems.append(
            "$.spec.token_identical: speculative decode must be "
            "token-identical to plain greedy decode (greedy acceptance "
            "is identity-preserving by construction)")
    drafted = spec.get("drafted")
    if not isinstance(drafted, int) or drafted < 1:
        problems.append(f"$.spec.drafted: {drafted!r} — the speculation "
                        "leg never drafted; it measured nothing")
    accepted = spec.get("accepted")
    if not isinstance(accepted, int) or accepted < 0 or (
            isinstance(drafted, int) and accepted > drafted):
        problems.append(f"$.spec.accepted: {accepted!r} must be an int "
                        "in [0, drafted]")
    rate = spec.get("acceptance_rate")
    if rate is None or _bad_pred_num(rate) \
            or not 0.0 <= float(rate) <= 1.0:
        problems.append(f"$.spec.acceptance_rate: {rate!r} must be "
                        "recorded in [0, 1]")
    steps = spec.get("decode_steps")
    if not isinstance(steps, dict):
        problems.append("$.spec.decode_steps: step counts not recorded")
    else:
        for k in ("plain", "spec"):
            n = steps.get(k)
            if not isinstance(n, int) or n < 1:
                problems.append(f"$.spec.decode_steps.{k}: {n!r} must "
                                "be a positive int")
        if isinstance(steps.get("plain"), int) \
                and isinstance(steps.get("spec"), int) \
                and steps["spec"] > steps["plain"]:
            problems.append(
                f"$.spec.decode_steps: spec took {steps['spec']} steps "
                f"vs plain {steps['plain']} — a verified draft can only "
                "save dispatches, never add them")
    speedup = spec.get("speedup_x")
    if speedup is None or _bad_pred_num(speedup) or float(speedup) <= 0:
        problems.append(f"$.spec.speedup_x: {speedup!r} must be "
                        "recorded as a finite positive number")
    elif float(speedup) < 1.0:
        expl = spec.get("explanation")
        if not isinstance(expl, str) or not expl.strip():
            problems.append(
                f"$.spec.speedup_x: {float(speedup):.3f} < 1.0 with no "
                "$.spec.explanation — a slowdown must be explained, "
                "not silently recorded")
    return problems

