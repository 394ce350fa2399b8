"""Per-op performance observatory: measured device time, attributed to
program ops and JOINED to the static cost model.

The drift monitor (obs/drift.py) compares predicted-vs-measured at
whole-step granularity: it can say "this program runs 1.4x slower than
the roofline", but not WHICH ops are the laggards — and the conv-family
MFU push (ROADMAP: 31% -> 45% on ResNet-50) needs a named, quantified
laggard list, not a step-level ratio. This module builds that
attribution loop:

  1. segment block 0 at the SAME maximal-run boundaries the traced
     lowering executes (core/lowering.iter_op_runs — reuse, not a new
     analysis; remat-tagged runs stay atomic so their vjp recomputes
     exactly like the real step's), coalescing adjacent unit runs up to
     PT_OPPROF_SEG_OPS ops so the compile count stays bounded;
  2. compile each segment ONCE and time min-of-PT_OPPROF_REPEATS
     settled runs (block_until_ready) on real feeds + real scope state —
     robust on the CPU tier-1, no profiler parsing required. Forward
     segments of a training program are additionally timed through
     jax.vjp, so each segment's BACKWARD is measured too (a segment
     whose vjp cannot build falls back to the cost model's convention
     — 2x forward, 3x for remat runs — flagged `bwd_modeled`);
  3. distribute each segment's measured time across its member ops by
     their predicted cost share (analysis/cost.op_roofline_ms — the
     same per-op roofline that fills the predicted column, so the join
     is self-consistent). A segment whose members are ALL uncovered by
     the cost model is flagged a GAP: its time still appears in the
     ledger, but the attribution-coverage gauge drops below 100% — the
     `uncovered_ops` lesson, attribution gaps visible, never silently
     zero.

Each ledger row carries {op type, name, predicted_ms, measured_ms,
per-op MFU, declared bound, share of step}. Surfaces:

  * `tools/op_report.py` — the ranked laggard table CLI (`--top K`,
    `--check` schema/floor validation via analysis/artifacts.py);
  * `publish()` — a `pt_op_*` metric family (top-K laggards by measured
    share + the attribution-coverage gauge) on the unified exposition;
  * with PT_TRACE armed, the measured per-op intervals merge into the
    Chrome-trace timeline via trace.complete() (cat="opprof"), so a
    PT_TRACE_DIR dump shows host spans and device attribution in one
    Perfetto view.

Profiling is OPT-IN (a profiling run, never an executor hook): the
PT_TRACE-disabled hot path pays nothing for this module's existence.
Single-chip only — a sharded program's per-op attribution needs the
device profiler, not host segment timing.
"""

from __future__ import annotations

import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from ..analysis import cost as _cost
from ..core.program import Program, default_main_program
from ..flags import env_knob_int as _knob_int

__all__ = ["OpRow", "SegmentTiming", "OpLedger", "profile_program",
           "publish", "OpProfMetrics", "REPEATS_ENV", "SEG_OPS_ENV",
           "TOPK_ENV"]

REPEATS_ENV = "PT_OPPROF_REPEATS"
SEG_OPS_ENV = "PT_OPPROF_SEG_OPS"
TOPK_ENV = "PT_OPPROF_TOPK"

DEFAULT_REPEATS = 3
DEFAULT_SEG_OPS = 16
DEFAULT_TOPK = 5


def _rnd(v, n: int = 5):
    return round(v, n) if v is not None else None


# ---------------------------------------------------------------------------
# ledger records
# ---------------------------------------------------------------------------

@dataclass
class OpRow:
    """One program op's predicted/measured join."""

    index: int                    # block-0 op index
    op_type: str
    name: str                     # primary output var (the op's identity)
    phase: str                    # forward | optimizer
    segment: int                  # owning segment id
    predicted_ms: float           # per-op roofline (train total: fwd+bwd)
    measured_ms: Optional[float]  # attributed share (fwd+bwd), None if
    #                               the segment could not be measured
    measured_fwd_ms: Optional[float] = None
    measured_bwd_ms: Optional[float] = None
    mxu_flops: int = 0            # train-total MXU flops (MFU numerator)
    mfu_pct: Optional[float] = None            # measured per-op MFU
    predicted_mfu_pct: Optional[float] = None
    bound: str = "bandwidth"      # per-op roofline leg
    share_pct: Optional[float] = None          # share of profiled step
    covered: bool = True          # cost-model coverage of THIS op

    def to_dict(self) -> dict:
        return {"index": self.index, "type": self.op_type,
                "name": self.name, "phase": self.phase,
                "segment": self.segment,
                "predicted_ms": _rnd(self.predicted_ms),
                "measured_ms": _rnd(self.measured_ms),
                "measured_fwd_ms": _rnd(self.measured_fwd_ms),
                "measured_bwd_ms": _rnd(self.measured_bwd_ms),
                "mfu_pct": _rnd(self.mfu_pct, 2),
                "predicted_mfu_pct": _rnd(self.predicted_mfu_pct, 2),
                "bound": self.bound,
                "share_pct": _rnd(self.share_pct, 3),
                "covered": self.covered}


@dataclass
class SegmentTiming:
    """One compiled-and-timed op range [start, stop)."""

    seg_id: int
    start: int
    stop: int
    phase: str                    # forward | optimizer
    tag: Optional[str]            # remat_scope tag (atomic runs)
    op_types: List[str]
    measured_fwd_ms: Optional[float] = None
    measured_bwd_ms: Optional[float] = None
    bwd_modeled: bool = False     # vjp unavailable: bwd = 2x fwd
    gap: bool = False             # every member uncovered by the model
    error: Optional[str] = None   # segment could not compile/run

    @property
    def measured_ms(self) -> Optional[float]:
        if self.measured_fwd_ms is None:
            return None
        return self.measured_fwd_ms + (self.measured_bwd_ms or 0.0)

    def to_dict(self) -> dict:
        return {"seg_id": self.seg_id, "start": self.start,
                "stop": self.stop, "phase": self.phase, "tag": self.tag,
                "n_ops": len(self.op_types),
                "op_types": list(self.op_types),
                "measured_fwd_ms": (round(self.measured_fwd_ms, 5)
                                    if self.measured_fwd_ms is not None
                                    else None),
                "measured_bwd_ms": (round(self.measured_bwd_ms, 5)
                                    if self.measured_bwd_ms is not None
                                    else None),
                "bwd_modeled": self.bwd_modeled, "gap": self.gap,
                "error": self.error}


@dataclass
class OpLedger:
    """The ranked predicted-vs-measured join for one program."""

    program: str
    batch: int
    chip: str
    train: bool
    rows: List[OpRow] = field(default_factory=list)
    segments: List[SegmentTiming] = field(default_factory=list)
    total_measured_ms: float = 0.0
    total_predicted_ms: float = 0.0
    coverage_pct: float = 100.0   # share of measured time attributed to
    #                               cost-model-covered segments
    fused_step_ms: Optional[float] = None   # the real one-dispatch step
    uncovered_ops: List[str] = field(default_factory=list)
    #: full program fingerprint (not the 12-char display name) — the
    #: calibration fit stamps it into the artifact's provenance so a
    #: program-specific calibration can refuse a foreign program
    fingerprint: Optional[str] = None

    def ranked(self) -> List[OpRow]:
        """Rows by measured time, laggards first (unmeasured rows last,
        by predicted)."""
        return sorted(self.rows,
                      key=lambda r: (r.measured_ms is None,
                                     -(r.measured_ms or 0.0),
                                     -r.predicted_ms))

    def top(self, k: int = DEFAULT_TOPK) -> List[OpRow]:
        return self.ranked()[:max(k, 1)]

    def summary(self, top: Optional[int] = None) -> dict:
        """The compact block publish() exports."""
        k = top if top is not None else _knob_int(TOPK_ENV, DEFAULT_TOPK)
        return {
            "program": self.program,
            "coverage_pct": round(self.coverage_pct, 2),
            "segments_errored": sum(1 for s in self.segments if s.error),
            "total_measured_ms": round(self.total_measured_ms, 4),
            "fused_step_ms": (round(self.fused_step_ms, 4)
                              if self.fused_step_ms is not None else None),
            "top_ops": [
                {"name": r.name, "type": r.op_type,
                 "measured_ms": (round(r.measured_ms, 5)
                                 if r.measured_ms is not None else None),
                 "predicted_ms": round(r.predicted_ms, 5),
                 "share_pct": (round(r.share_pct, 2)
                               if r.share_pct is not None else None),
                 "mfu_pct": (round(r.mfu_pct, 2)
                             if r.mfu_pct is not None else None),
                 "bound": r.bound}
                for r in self.top(k)],
        }

    def to_dict(self) -> dict:
        return {
            "program": self.program, "batch": self.batch,
            "chip": self.chip, "train": self.train,
            "fingerprint": self.fingerprint,
            "total_measured_ms": round(self.total_measured_ms, 4),
            "total_predicted_ms": round(self.total_predicted_ms, 4),
            "coverage_pct": round(self.coverage_pct, 2),
            "fused_step_ms": (round(self.fused_step_ms, 4)
                              if self.fused_step_ms is not None else None),
            "uncovered_ops": list(self.uncovered_ops),
            "segments": [s.to_dict() for s in self.segments],
            "rows": [r.to_dict() for r in self.ranked()],
        }


# ---------------------------------------------------------------------------
# segmentation (the lowering's own boundaries, coalesced)
# ---------------------------------------------------------------------------

def _segments_for(ops, fwd_stop: int, n_ops: int, seg_ops: int):
    """(start, stop, phase, tag) segments: the lowering's maximal runs
    (core/lowering.iter_op_runs), with adjacent UNIT runs coalesced up
    to `seg_ops` ops so the per-segment compile count stays bounded.
    Remat-tagged runs are atomic (their vjp must recompute like the
    real step), the autodiff pseudo-op is skipped, and no segment
    crosses the forward/optimizer boundary."""
    from ..core.lowering import iter_op_runs
    out = []

    def emit_phase(start, stop, phase):
        pend_i = None
        pend_n = 0
        for i, j, tag in iter_op_runs(ops, start, stop):
            if tag is not None:
                if pend_i is not None:
                    out.append((pend_i, i, phase, None))
                    pend_i = None
                out.append((i, j, phase, tag))
                continue
            if pend_i is None:
                pend_i, pend_n = i, 0
            pend_n += j - i
            if pend_n >= seg_ops:
                out.append((pend_i, j, phase, None))
                pend_i = None
        if pend_i is not None:
            out.append((pend_i, stop, phase, None))

    emit_phase(0, fwd_stop, "forward")
    if fwd_stop < n_ops:
        emit_phase(fwd_stop + 1, n_ops, "optimizer")
    return out


# ---------------------------------------------------------------------------
# profiling
# ---------------------------------------------------------------------------

def _time_call(fn, args, repeats: int):
    """Compile/warm once, then min of `repeats` settled runs, in ms.
    Returns (ms, warm_output)."""
    import jax
    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        r = fn(*args)
        jax.block_until_ready(r)
        best = min(best, time.perf_counter() - t0)
    return best * 1e3, out


def _synthesize(block, name: str, batch: int):
    """Zeros shaped like VarDesc `name` at its device dtype — how the
    profiler fills inputs no earlier segment produced (the @GRAD feeds
    of an optimizer segment, fetch-threaded pools)."""
    import jax.numpy as jnp
    from ..core.types import device_dtype, np_dtype
    v = block.var(name)
    shape = tuple(batch if int(d) == -1 else int(d) for d in (v.shape or ()))
    return jnp.zeros(shape, np_dtype(device_dtype(v.dtype)))


def _seg_reads_writes(ops, start: int, stop: int):
    reads: List[str] = []
    defined: set = set()
    writes: List[str] = []
    for op in ops[start:stop]:
        for n in op.input_names():
            if n not in defined and n not in reads:
                reads.append(n)
        for n in op.output_names():
            defined.add(n)
            if n not in writes:
                writes.append(n)
    return reads, writes


def _make_seg_fn(ops, start: int, stop: int, block, in_names, out_names,
                 amp):
    """A pure fn(dict of inputs) -> tuple(outputs) tracing ops[start:
    stop] through the SAME run_op_range the executor's lowering uses
    (remat runs checkpoint identically)."""
    import jax
    from ..core import lowering
    from ..core.registry import ExecContext

    def seg_fn(vals: Dict[str, object]):
        ctx = ExecContext(jax.random.PRNGKey(0), is_test=False)
        ctx.amp_dtype = amp
        e = dict(vals)
        e = lowering.run_op_range(ops, start, stop, e, ctx, block)
        return tuple(e[n] for n in out_names)

    return seg_fn


def _vjp_ms(seg_fn, inputs, warm_outs, repeats: int):
    """Measured forward+backward ms of one segment: jax.vjp over the
    float outputs with unit cotangents, float-only grads returned (int
    inputs produce float0 cotangents jit cannot ship)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    float_idx = [i for i, y in enumerate(warm_outs)
                 if jnp.issubdtype(jnp.result_type(y), jnp.floating)]
    if not float_idx:
        raise ValueError("no float outputs to differentiate")

    def fwd_float(vals):
        ys = seg_fn(vals)
        return tuple(ys[i] for i in float_idx)

    f0 = jax.dtypes.float0

    def fwdbwd(vals, cts):
        ys, pull = jax.vjp(fwd_float, vals)
        grads = pull(cts)
        flat = [g for g in jax.tree_util.tree_leaves(grads)
                if g.dtype != f0]
        return ys, tuple(flat)

    # shape/dtype-only inspection of the warm outputs — no host sync
    cts = tuple(np.ones(np.shape(warm_outs[i]), warm_outs[i].dtype)
                for i in float_idx)
    ms, _ = _time_call(jax.jit(fwdbwd), (inputs, cts), repeats)
    return ms


def _fused_step_ms(program, feed_arrays, state, repeats: int):
    """The real one-dispatch step (build_step_fn, no fetches), for the
    honesty line beside the profiled sum: separately-compiled segments
    lose cross-segment fusion and pay per-dispatch overhead, so the
    profiled total is an upper bound on the fused step."""
    import jax
    from ..core import lowering
    step, _ = lowering.build_step_fn(program, list(feed_arrays), [],
                                     sorted(state))
    fn = jax.jit(step)
    rng = jax.random.PRNGKey(0)
    ms, _ = _time_call(fn, (dict(state), dict(feed_arrays), rng), repeats)
    return ms


def profile_program(program: Optional[Program] = None,
                    feed: Optional[dict] = None, scope=None,
                    batch: Optional[int] = None,
                    repeats: Optional[int] = None,
                    seg_ops: Optional[int] = None, chip=None,
                    name: Optional[str] = None,
                    fused_step: bool = True,
                    publish_metrics: bool = True) -> OpLedger:
    """Measure + attribute one program's per-op device time.

    feed: host arrays for the program's data vars (missing ones are
    synthesized as zeros). scope: holds the persistable state (a scope
    the startup program initialized); absent vars synthesize as zeros —
    timing does not depend on values. batch: substituted for dynamic -1
    dims (default: inferred from the first feed array's leading dim).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ..core.lowering import AUTODIFF_OP
    from ..core.types import device_dtype, np_dtype
    from . import trace as obs_trace

    program = program or default_main_program()
    block = program.global_block
    ops = block.ops
    amp = program.amp_dtype
    feed = dict(feed or {})
    repeats = repeats if repeats is not None else _knob_int(
        REPEATS_ENV, DEFAULT_REPEATS)
    seg_ops = seg_ops if seg_ops is not None else _knob_int(
        SEG_OPS_ENV, DEFAULT_SEG_OPS)
    chip = chip or _cost.resolve_chip()
    if batch is None:
        batch = next((int(np.shape(v)[0]) for v in feed.values()
                      if np.shape(v)), 1)

    bwd_idx = next((i for i, o in enumerate(ops)
                    if o.type == AUTODIFF_OP), None)
    train = bwd_idx is not None
    fwd_stop = bwd_idx if bwd_idx is not None else len(ops)

    # -- the starting environment: feeds + scope state ----------------------
    env: Dict[str, object] = {}
    for fname, val in feed.items():
        try:
            v = block.var(fname)
        except KeyError:
            continue
        arr = np.asarray(val)  # host-sync: ok — host feed conversion
        want = np_dtype(device_dtype(v.dtype))
        if arr.dtype != want:
            arr = arr.astype(want)
        env[fname] = jnp.asarray(arr)
    state: Dict[str, object] = {}
    read_names = {n for op in ops for n in op.input_names()}
    for vname in sorted(read_names):
        try:
            v = block.var(vname)
        except KeyError:
            continue
        if not v.persistable or vname in env:
            continue
        sv = scope.find_var(vname) if scope is not None \
            and scope.has_var(vname) else None
        state[vname] = sv if sv is not None else _synthesize(block, vname,
                                                             batch)
    env.update(state)
    # mirror the lowering's AMP entry: f32 feeds and declared params run
    # at the compute dtype inside the forward; the f32 masters return
    # for the optimizer suffix below
    orig_params: Dict[str, object] = {}
    if amp is not None and train:
        from ..core.types import CODEC_SCALE_SUFFIX
        adt = jnp.dtype(amp)
        for k in list(feed):
            if k in env and not k.endswith(CODEC_SCALE_SUFFIX) \
                    and jnp.result_type(env[k]) == jnp.float32:
                env[k] = env[k].astype(adt)
        for p in ops[bwd_idx].attrs.get("params", ()):
            if p in env and jnp.result_type(env[p]) == jnp.float32:
                orig_params[p] = env[p]
                env[p] = env[p].astype(adt)

    # -- per-op predicted costs --------------------------------------------
    ctx = _cost._Ctx(block, batch, amp)
    op_costs: Dict[int, _cost.OpCost] = {}
    for i, op in enumerate(ops):
        if op.type == AUTODIFF_OP:
            continue
        try:
            op_costs[i] = _cost._op_cost_ctx(op, ctx)
        except KeyError:
            op_costs[i] = _cost.OpCost(covered=False)

    segments: List[SegmentTiming] = []
    rows: List[OpRow] = []
    uncovered: List[str] = []

    seg_specs = _segments_for(ops, fwd_stop, len(ops), seg_ops)
    restored_masters = False
    for seg_id, (start, stop, phase, tag) in enumerate(seg_specs):
        if phase == "optimizer" and not restored_masters:
            env.update(orig_params)   # optimizer updates the f32 masters
            restored_masters = True
        seg = SegmentTiming(seg_id, start, stop, phase, tag,
                            [ops[k].type for k in range(start, stop)])
        reads, writes = _seg_reads_writes(ops, start, stop)
        # synthesize anything no earlier segment produced (@GRAD feeds,
        # loss-scale scalars) — zeros, value-independent timing
        for rname in reads:
            if rname in env:
                continue
            try:
                env[rname] = _synthesize(block, rname, batch)
            except KeyError:
                pass
        in_names = [n for n in reads if n in env]
        seg_fn = None
        warm = None
        for names in (in_names, sorted(env)):
            # sub-block ops (dynamic_rnn/while) read captured values the
            # OpDesc does not declare; retry with the full environment
            try:
                fn = _make_seg_fn(ops, start, stop, block, names, writes,
                                  amp)
                inputs = {n: env[n] for n in names}
                ms, warm = _time_call(jax.jit(fn), (inputs,), repeats)
                seg_fn, seg.measured_fwd_ms = fn, ms
                break
            except Exception as e:   # noqa: BLE001 — per-segment fallback
                seg.error = f"{type(e).__name__}: {e}"
        if seg_fn is not None:
            seg.error = None
            env.update(zip(writes, warm))
            if train and phase == "forward":
                try:
                    seg.measured_bwd_ms = max(
                        _vjp_ms(seg_fn, inputs, warm, repeats)
                        - seg.measured_fwd_ms, 0.0)
                except Exception:   # noqa: BLE001 — model the convention:
                    # 2x forward, 3x for remat runs (the backward re-runs
                    # their forward once more) — the same multipliers the
                    # attribution weights below use
                    seg.measured_bwd_ms = (
                        3.0 if tag is not None else 2.0
                    ) * seg.measured_fwd_ms
                    seg.bwd_modeled = True
        member_costs = {k: op_costs.get(k, _cost.OpCost(covered=False))
                        for k in range(start, stop)}
        seg.gap = bool(member_costs) and all(
            not c.covered for c in member_costs.values())
        segments.append(seg)

        # -- join: distribute measured time by predicted cost share --------
        remat = tag is not None
        fwd_w: Dict[int, float] = {}
        bwd_w: Dict[int, float] = {}
        op_bound: Dict[int, str] = {}
        for k, c in member_costs.items():
            ms_k, op_bound[k] = _cost.op_roofline_ms(c, chip)
            fwd_w[k] = ms_k
            # backward ~ 2x forward; remat segments re-run their forward
            # once more inside the backward (recompute)
            bwd_w[k] = ms_k * (3.0 if remat else 2.0)
        sum_fw = sum(fwd_w.values())
        sum_bw = sum(bwd_w.values())
        n_members = max(len(member_costs), 1)
        for k, c in member_costs.items():
            op = ops[k]
            outs = op.output_names()
            is_fwd_phase = phase == "forward"
            pred_bwd = bwd_w[k] if (train and is_fwd_phase) else 0.0
            predicted = fwd_w[k] + pred_bwd
            mf = mb = measured = None
            if seg.measured_fwd_ms is not None:
                fshare = (fwd_w[k] / sum_fw if sum_fw > 0
                          else 1.0 / n_members)
                mf = seg.measured_fwd_ms * fshare
                if seg.measured_bwd_ms is not None:
                    bshare = (bwd_w[k] / sum_bw if sum_bw > 0
                              else 1.0 / n_members)
                    mb = seg.measured_bwd_ms * bshare
                measured = mf + (mb or 0.0)
            mxu = c.mxu_flops * (3 if (train and is_fwd_phase) else 1)
            bound = op_bound[k]
            # measured per-op MFU: capped at the hardware ceiling — a
            # cost-share slice smaller than the op's own compute floor
            # is an attribution artifact, and >100% MFU is impossible
            mfu = (min(100.0, 100.0 * mxu / (measured / 1e3)
                       / chip.peak_flops)
                   if measured else None)
            pmfu = (100.0 * mxu / (predicted / 1e3) / chip.peak_flops
                    if predicted > 0 else None)
            rows.append(OpRow(
                index=k, op_type=op.type,
                name=outs[0] if outs else f"{op.type}.{k}",
                phase=phase, segment=seg_id, predicted_ms=predicted,
                measured_ms=measured, measured_fwd_ms=mf,
                measured_bwd_ms=mb, mxu_flops=mxu, mfu_pct=mfu,
                predicted_mfu_pct=pmfu, bound=bound,
                share_pct=None, covered=c.covered))
            if not c.covered and op.type not in uncovered:
                uncovered.append(op.type)

    total_measured = sum(s.measured_ms or 0.0 for s in segments)
    total_predicted = sum(r.predicted_ms for r in rows)
    gap_ms = sum(s.measured_ms or 0.0 for s in segments if s.gap)
    if total_measured > 0:
        coverage = 100.0 * (total_measured - gap_ms) / total_measured
        for r in rows:
            if r.measured_ms is not None:
                r.share_pct = 100.0 * r.measured_ms / total_measured
    else:
        # nothing measured: 100% would let a run where EVERY segment
        # failed sail through coverage gates with zero actual readings —
        # exactly the silently-zero failure mode this module exists to
        # prevent. Any gap or error reports 0.
        coverage = (0.0 if any(s.gap or s.error for s in segments)
                    else 100.0)

    fused_ms = None
    if fused_step:
        try:
            feed_arrays = {k: env[k] for k in feed if k in env}
            fused_ms = _fused_step_ms(program, feed_arrays, state, repeats)
        except Exception:   # noqa: BLE001 — honesty line, never fatal
            fused_ms = None

    try:
        fp = str(program.fingerprint())
    except Exception:   # noqa: BLE001
        fp = None
    pname = name or (fp[:12] if fp else "program")
    ledger = OpLedger(program=pname, batch=batch, chip=chip.name,
                      train=train, rows=rows, segments=segments,
                      total_measured_ms=total_measured,
                      total_predicted_ms=total_predicted,
                      coverage_pct=coverage, fused_step_ms=fused_ms,
                      uncovered_ops=uncovered, fingerprint=fp)

    # merge the measured intervals into the Chrome-trace timeline: with
    # PT_TRACE armed (and PT_TRACE_DIR set for the device profile), the
    # Perfetto view shows host spans and device attribution together
    if obs_trace.enabled():
        for s in segments:
            if s.measured_ms is not None:
                obs_trace.complete(
                    f"opprof:seg{s.seg_id}", s.measured_ms / 1e3,
                    cat="opprof", phase=s.phase, n_ops=len(s.op_types),
                    gap=s.gap)
        for r in ledger.top(_knob_int(TOPK_ENV, DEFAULT_TOPK)):
            if r.measured_ms is not None:
                obs_trace.complete(
                    f"op:{r.op_type}:{r.name}", r.measured_ms / 1e3,
                    cat="opprof", predicted_ms=round(r.predicted_ms, 5),
                    bound=r.bound)

    if publish_metrics:
        publish(ledger)
    return ledger


# ---------------------------------------------------------------------------
# pt_op_* metric family
# ---------------------------------------------------------------------------

class OpProfMetrics:
    """A frozen ledger summary as a metrics provider: top-K laggards by
    measured share + the attribution-coverage gauge, rendered as the
    pt_op_* family by obs/metrics.render_prometheus."""

    def __init__(self, name: str, summary: dict):
        self.name = name
        self._summary = summary

    def snapshot(self) -> dict:
        return dict(self._summary)


#: strong refs — the REGISTRY holds providers weakly, and a published
#: ledger must outlive the profiling call that produced it. LRU-bounded
#: like the drift monitor: a long-lived service profiling rebuilt
#: programs (fingerprint changes with any graph change) must not grow
#: memory — or the scrape — forever with rows for dead programs.
MAX_PUBLISHED = 64
_PUBLISHED: "OrderedDict[str, OpProfMetrics]" = OrderedDict()


def publish(ledger: OpLedger, name: Optional[str] = None) -> OpProfMetrics:
    """Register the ledger's summary on the unified metrics plane
    (section "op") — one scrape then carries the laggard list beside
    pt_train_* / pt_model_*."""
    from .metrics import REGISTRY
    key = name or ledger.program
    prov = OpProfMetrics(key, ledger.summary())
    _PUBLISHED[key] = prov
    _PUBLISHED.move_to_end(key)
    while len(_PUBLISHED) > MAX_PUBLISHED:
        old_key, _old = _PUBLISHED.popitem(last=False)
        REGISTRY.unregister("op", old_key)
    REGISTRY.register("op", key, prov)
    return prov
