"""Liveness-based static peak-HBM estimation + the pre-compile budget gate.

Walks block 0 the same way the lowering will trace it (the def-use walk
the verifier already does) and prices what the compiled step keeps
resident, WITHOUT compiling anything:

  * params            — f32 master weights (persistable parameters)
  * optimizer_state   — accumulators (velocity/moments/…), identified by
                        the shared iter_optimizer_state_inputs definition
  * grads             — parameter cotangents (f32, alive through the
                        optimizer suffix)
  * activations       — the autodiff residual watermark: every forward
                        value some backward rule needs, minus what remat
                        segments recompute instead of save
  * kv_pools          — paged decode KV pools (KPool/VPool slots)
  * feeds             — per-step input arrays, priced at each feed's
                        RECORDED dtype — which for wire-codec programs
                        (data/codec.py apply_wire_codec) is the narrow
                        wire dtype, so the estimate sees the codec's
                        resident-feed saving for free

The estimate is cross-checked against `tools/remat_memory_report.py`'s
compiled `memory_analysis()` artifacts (docs/artifacts/remat_memory_*)
in tests/test_cost_model.py — the contract is within 15% of the
measured peak on the transformer configs, remat on AND off.

The budget gate: `PT_MEM_BUDGET_GB` makes every executor compile-miss
run `enforce_budget` BEFORE tracing — a program whose static estimate
exceeds the budget raises the typed `MemoryBudgetError` carrying the
per-category breakdown, instead of compiling for minutes and dying
RESOURCE_EXHAUSTED on the device. A passing budget costs one host-side
IR walk per compile (never per step) and touches no device state.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

from ..core.program import (Program, default_main_program,
                            iter_optimizer_state_inputs)
from ..core.lowering import post_forward_reads
from .cost import (AUTODIFF_OP, RESHAPE_ALIAS_OPS, device_nbytes,
                   dtype_nbytes, _prod, _shape)

__all__ = ["MemoryEstimate", "MemoryBudgetError", "estimate_memory",
           "budget_from_env", "batch_shard_factor", "enforce_budget",
           "loop_body_steps"]

_F32 = 4


# ---------------------------------------------------------------------------
# which forward values does the backward need? (the VJP-residual table)
# ---------------------------------------------------------------------------
# For each op type: the input/output slots whose values are saved as
# residuals of the autodiff. Matmul-class ops save their activation
# operands (dW reads them); normalization and most nonlinearities save
# their input; flash attention saves q/k/v + out (+ the small lse);
# index/alias/add ops save nothing. Unknown ops conservatively save
# their inputs (over-estimation fails safe for a budget gate).

_SAVES_IN = {
    "mul": ("X", "Y"), "matmul": ("X", "Y"),
    "conv2d": ("Input",), "depthwise_conv2d": ("Input",),
    "conv3d": ("Input",), "conv2d_transpose": ("Input",),
    "conv3d_transpose": ("Input",),
    # conv epilogue fusion (analysis/fuse.py): the fused backward needs
    # the conv Input (dW) plus ONE activation-sized residual — the
    # epilogue VJP saves the pre-BN conv output, same size as Output,
    # modeled below via _SAVES_OUT. The unfused chain's extra saves
    # (batch_norm X = the conv output AND relu Out) are gone: fusing
    # drops one full activation residual per chain from the estimate.
    "fused_conv2d": ("Input",),
    "scaled_dot_product_attention": ("Q", "K", "V"),
    "layer_norm": ("X",), "batch_norm": ("X",), "rms_norm": ("X",),
    "gelu": ("X",), "tanh": ("X",), "sigmoid": ("X",), "swish": ("X",),
    "elu": ("X",), "softplus": ("X",), "leaky_relu": ("X",),
    "relu6": ("X",), "softsign": ("X",), "square": ("X",),
    "elementwise_mul": ("X", "Y"), "elementwise_div": ("X", "Y"),
    "elementwise_max": ("X", "Y"), "elementwise_min": ("X", "Y"),
    "softmax_with_cross_entropy": ("Logits",),
    "cross_entropy": ("X",),
    "sequence_softmax": ("X",),
}

_SAVES_OUT = {
    "relu": ("Out",), "softmax": ("Out",), "exp": ("Out",),
    "scaled_dot_product_attention": ("Out",),
    "fused_conv2d": ("Output",),
}

#: ops whose backward needs nothing from the forward (index/alias/
#: linear ops — their VJP is shape motion or identity)
_SAVES_NOTHING = frozenset({
    "elementwise_add", "elementwise_sub", "scale", "cast", "reshape",
    "reshape2", "transpose", "transpose2", "squeeze", "squeeze2",
    "unsqueeze", "unsqueeze2", "flatten", "flatten2", "slice", "concat",
    "split", "stack", "gather", "lookup_table", "mean", "reduce_sum",
    "reduce_mean", "sum", "fill_constant", "dropout", "pool2d",
    "embedding", "one_hot", "top_k", "accuracy", "assign", "shape",
    "rotary_embedding",   # linear in X: its VJP rotates back by Positions
    "pad", "pad2d", "uniform_random", "gaussian_random",
    # wire-codec dequant (data/codec.py): its inputs are stop-gradient
    # feeds — the backward needs nothing from it
    "feed_dequant",
})


def _residual_reads(op) -> List[str]:
    if op.type in _SAVES_NOTHING:
        return []
    slots_in = _SAVES_IN.get(op.type)
    slots_out = _SAVES_OUT.get(op.type, ())
    names: List[str] = []
    if slots_in is None and op.type not in _SAVES_OUT:
        # unknown op: assume its backward reads all inputs (fail-safe
        # over-estimate for the budget gate)
        names.extend(op.input_names())
    elif slots_in:
        for s in slots_in:
            names.extend(op.inputs.get(s, ()))
    for s in slots_out:
        names.extend(op.outputs.get(s, ()))
    return names


# ---------------------------------------------------------------------------
# the estimate
# ---------------------------------------------------------------------------

@dataclass
class MemoryEstimate:
    breakdown: Dict[str, int] = field(default_factory=dict)
    #: comparable to compiled.memory_analysis().temp_size_in_bytes
    temp_bytes: int = 0
    #: comparable to argument_size_in_bytes (donated state + feeds)
    state_bytes: int = 0
    #: the headline: everything resident at the step's worst moment
    peak_bytes: int = 0
    details: Dict[str, int] = field(default_factory=dict)

    @property
    def peak_gb(self) -> float:
        return self.peak_bytes / 1e9

    def to_dict(self) -> dict:
        return {"peak_bytes": int(self.peak_bytes),
                "peak_gb": round(self.peak_gb, 3),
                "temp_bytes": int(self.temp_bytes),
                "state_bytes": int(self.state_bytes),
                "breakdown": {k: int(v) for k, v in self.breakdown.items()},
                "details": {k: int(v) for k, v in self.details.items()}}


def _classify(program: Program) -> Tuple[Set[str], Set[str], Set[str],
                                         Set[str]]:
    """(param names, optimizer-state names, kv-pool names incl. output
    aliases, kv-pool STORAGE names) over block 0 — storage excludes the
    KOut/VOut aliases of donated input pools so a pool is priced once."""
    block = program.global_block
    acc = {a for _, a in iter_optimizer_state_inputs(block)}
    params = {v.name for v in block.vars.values()
              if (v.is_parameter or v.persistable) and v.name not in acc}
    kv = set()
    kv_alias = set()
    for op in block.ops:
        if op.type in ("paged_attention", "paged_kv_write"):
            for slot in ("KPool", "VPool"):
                kv.update(op.inputs.get(slot, ()))
            # KOut/VOut alias the donated input pools (the decode engine
            # threads them device-resident) — same buffer, never a second
            # copy, but they must still CLASSIFY as pool storage so the
            # activation watermark doesn't price a whole-pool temporary
            for slot in ("KOut", "VOut"):
                kv_alias.update(op.outputs.get(slot, ()))
    # storage = pool names that are NOT some write op's output: the
    # updated pools (and their downstream readers) alias the donated
    # originals, so each physical pool prices exactly once
    return params, acc, kv | kv_alias, kv - kv_alias


def estimate_memory(program: Optional[Program] = None, batch: int = 1,
                    train: Optional[bool] = None) -> MemoryEstimate:
    """Static peak-HBM estimate for one step of block 0 at `batch`.

    train=None auto-detects from the autodiff marker. The activation
    model is the autodiff residual watermark (see module docstring);
    remat segments keep only their boundary values plus the largest
    single segment's recompute working set — the same segmentation
    run_op_range applies (maximal runs of one remat_scope tag).
    """
    program = program or default_main_program()
    block = program.global_block
    amp = program.amp_dtype
    params, acc_names, kv_names, kv_storage = _classify(program)
    ops = block.ops
    bwd_idx = next((i for i, o in enumerate(ops)
                    if o.type == AUTODIFF_OP), None)
    has_bwd = bwd_idx is not None if train is None else bool(
        train and bwd_idx is not None)
    fwd_stop = bwd_idx if bwd_idx is not None else len(ops)

    def nbytes(name) -> int:
        return _prod(_shape(block, name, batch)) * device_nbytes(
            block.var(name), amp)

    def safe_nbytes(name) -> int:
        try:
            return nbytes(name)
        except KeyError:
            return 0

    # -- state / feeds / pools --------------------------------------------
    param_bytes = sum(safe_nbytes_raw(block, n, batch) for n in params)
    opt_bytes = sum(safe_nbytes_raw(block, n, batch) for n in acc_names)
    kv_bytes = sum(safe_nbytes(n) for n in kv_storage)
    feed_bytes = 0
    for v in block.vars.values():
        if getattr(v, "is_data", False) and v.name not in kv_names:
            feed_bytes += safe_nbytes(v.name)

    # -- residual watermark over the forward -------------------------------
    # segment id per op: the lowering's own run boundaries
    # (core/lowering.iter_op_runs — the grouping run_op_range
    # checkpoints); None = not rematerialized
    from ..core.lowering import iter_op_runs
    seg_of: List[Optional[int]] = []
    seg_id = -1
    for i, j, tag in iter_op_runs(ops, 0, fwd_stop):
        if tag is None:
            seg_of.extend([None] * (j - i))
        else:
            seg_id += 1
            seg_of.extend([seg_id] * (j - i))

    # names read at or after op i (later forward ops + the optimizer
    # suffix). Only the sets at remat segment ends are ever consumed, so
    # one reverse sweep keeps a single running union and snapshots it at
    # exactly those indices — O(total reads), not a per-op copied set
    snap_at: Set[int] = {fwd_stop}
    for i in range(fwd_stop):
        sid = seg_of[i]
        if sid is not None and (i + 1 == fwd_stop or seg_of[i + 1] != sid):
            snap_at.add(i + 1)
    running: Set[str] = set(post_forward_reads(block))
    read_after_at: Dict[int, Set[str]] = {fwd_stop: set(running)}
    for i in range(fwd_stop - 1, -1, -1):
        running.update(ops[i].input_names())
        if i in snap_at:
            read_after_at[i] = set(running)

    def is_activation(name) -> bool:
        if name in params or name in acc_names or name in kv_names:
            return False
        try:
            v = block.var(name)
        except KeyError:
            return False
        if getattr(v, "is_data", False) or v.persistable:
            return False
        return True

    # reshape-family outputs alias their input buffer (XLA bitcasts):
    # a residual saved under both names is ONE buffer, so residuals are
    # deduplicated by canonical (alias-root) name
    alias_root: Dict[str, str] = {}
    for i in range(fwd_stop):
        op = ops[i]
        if (op.type in RESHAPE_ALIAS_OPS and op.inputs.get("X")
                and op.output_names()):
            src = op.inputs["X"][0]
            for out in op.output_names():
                alias_root[out] = alias_root.get(src, src)

    def canon(name: str) -> str:
        return alias_root.get(name, name)

    residuals: Set[str] = set()          # saved outside remat segments
    seg_resid: Dict[int, Set[str]] = {}  # saved inside each segment
    seg_boundary: Dict[int, Set[str]] = {}
    produced_in_seg: Dict[int, Set[str]] = {}
    lse_extra = 0
    for i in range(fwd_stop):
        op = ops[i]
        sid = seg_of[i]
        if has_bwd:
            saves = [canon(n) for n in _residual_reads(op)
                     if is_activation(n)]
            if op.type == "scaled_dot_product_attention":
                # the flash kernel's saved logsumexp: [B, H, S] f32
                try:
                    q = _shape(block, op.inputs["Q"][0], batch)
                    lse_extra += q[0] * q[2] * q[1] * _F32
                except (KeyError, IndexError):
                    pass
        else:
            saves = []
        if sid is None:
            residuals.update(saves)
        else:
            seg_resid.setdefault(sid, set()).update(saves)
            produced_in_seg.setdefault(sid, set()).update(
                canon(n) for n in op.output_names())
        # a value produced inside a segment but read after it is a
        # checkpoint output — saved regardless of the remat policy
        if sid is not None:
            seg_end = i + 1 == fwd_stop or seg_of[i + 1] != sid
            if seg_end:
                after = {canon(n) for n in read_after_at[i + 1]}
                boundary = {n for n in produced_in_seg.get(sid, ())
                            if n in after and is_activation(n)}
                seg_boundary[sid] = boundary

    # pipeline sub-block residuals: the auto-pp rewrite (transpiler/
    # pipeline_transpiler.py) hides its layer bodies in a sub-block the
    # block-0 walk cannot see, so each of the L stacked layers saves its
    # own residual set (GPipe semantics: every microbatch's forward runs
    # before any backward). Inner param-slice placeholders are excluded
    # (param_vars attr — weights, not activations). The planner's
    # per-stage model (analysis/schedule.pipeline_memory) divides this
    # term by stages x the schedule's microbatch stash bound.
    pipe_resid = 0
    if has_bwd:
        for i in range(fwd_stop):
            op = ops[i]
            if op.type != "pipeline":
                continue
            attrs = op.attrs or {}
            try:
                sub = program.blocks[int(attrs["sub_block"])]
            except (KeyError, IndexError, TypeError, ValueError):
                continue
            skip = set(attrs.get("param_vars", ()))
            per_layer = 0
            seen: Set[str] = set()
            for o in sub.ops:
                for n in _residual_reads(o):
                    if n in skip or n in seen:
                        continue
                    seen.add(n)
                    try:
                        v = sub.var(n)
                    except KeyError:
                        continue
                    if v.is_parameter or v.persistable \
                            or getattr(v, "is_data", False):
                        continue
                    per_layer += _prod(_shape(sub, n, batch)) \
                        * device_nbytes(v, amp)
                if o.type == "scaled_dot_product_attention":
                    # the flash kernel's saved logsumexp, per layer
                    try:
                        q = _shape(sub, o.inputs["Q"][0], batch)
                        per_layer += q[0] * q[2] * q[1] * _F32
                    except (KeyError, IndexError):
                        pass
            layers = int(attrs.get("num_stages", 1)) \
                * int(attrs.get("layers_per_stage", 1))
            pipe_resid += per_layer * layers

    resid_bytes = (sum(safe_nbytes(n) for n in residuals) + lse_extra
                   + pipe_resid)
    boundary_bytes = sum(safe_nbytes(n) for s in seg_boundary.values()
                         for n in s)
    seg_work = 0
    for sid, names in seg_resid.items():
        inner = names - seg_boundary.get(sid, set())
        seg_work = max(seg_work, sum(safe_nbytes(n) for n in inner))

    # -- backward-side components ------------------------------------------
    grad_bytes = 0
    if has_bwd:
        bop = ops[bwd_idx]
        for p in bop.attrs.get("params", ()):
            try:
                v = block.var(p)
            except KeyError:
                continue
            # master-dtype cotangents (f32 for f32 params)
            grad_bytes += _prod(_shape(block, p, batch)) * dtype_nbytes(
                v.dtype)
    # AMP: the compute path materializes low-precision copies of the f32
    # masters; they stay alive while backward still needs W for dX
    cast_bytes = 0
    if has_bwd and amp:
        for p in params:
            try:
                v = block.var(p)
            except KeyError:
                continue
            if str(v.dtype) == "float32":
                cast_bytes += _prod(_shape(block, p, batch)) * dtype_nbytes(
                    amp)
    def fwd_ops_incl_pipeline():
        """(op, blk, skip) over the forward INCLUDING pipeline sub-block
        bodies: backward transients (the largest cotangent, the
        attention score-map scratch) materialize inside the stage body
        too, and layers differentiate one at a time, so the MAX below is
        the right aggregation — one sub-block layer stands for all L.
        skip = the stage's param-slice placeholders (weights, never
        cotangent-bearing activations)."""
        for i in range(fwd_stop):
            op = ops[i]
            yield op, block, frozenset()
            if op.type == "pipeline":
                attrs = op.attrs or {}
                try:
                    sub = program.blocks[int(attrs["sub_block"])]
                except (KeyError, IndexError, TypeError, ValueError):
                    continue
                skip = frozenset(attrs.get("param_vars", ()))
                for o in sub.ops:
                    yield o, sub, skip

    def sub_act_bytes(blk, name, skip) -> int:
        """Bytes of an activation-class value in `blk` (0 when it is a
        param/persistable/feed/placeholder or unresolvable)."""
        if name in skip:
            return 0
        if blk is block:
            if not is_activation(name):
                return 0
        else:
            try:
                v = blk.var(name)
            except KeyError:
                return 0
            if v.is_parameter or v.persistable \
                    or getattr(v, "is_data", False):
                return 0
        try:
            return _prod(_shape(blk, name, batch)) * device_nbytes(
                blk.var(name), amp)
        except KeyError:
            return 0

    # the largest single cotangent the backward materializes (the
    # [tokens, vocab] dlogits for LM programs), priced at the DEVICE
    # dtype: the memory-lean custom VJPs (ops/nn_ops.py softmax-xent)
    # emit dlogits in the logits dtype, never an f32 scatter temp
    cot_bytes = 0
    if has_bwd:
        for op, blk, skip in fwd_ops_incl_pipeline():
            for n in op.output_names():
                cot_bytes = max(cot_bytes, sub_act_bytes(blk, n, skip))
    # attention backward scratch: differentiating one attention layer
    # stages up to the full [B, H, Sq, Sk] score map at device dtype
    # (the XLA fallback materializes it exactly; the Pallas kernel tiles
    # it but its dS/recompute window peaks at the same order). Layers
    # are differentiated one at a time, so only the LARGEST single op
    # counts — at long context this term dominates every per-token
    # residual (8k: 2.1 GB vs 0.6 GB of saved residuals).
    attn_scratch = 0
    if has_bwd:
        for op, blk, _skip in fwd_ops_incl_pipeline():
            if op.type == "scaled_dot_product_attention":
                try:
                    q = _shape(blk, op.inputs["Q"][0], batch)
                    k = _shape(blk, op.inputs["K"][0], batch)
                    nb = device_nbytes(blk.var(op.inputs["Q"][0]), amp)
                    attn_scratch = max(attn_scratch,
                                       q[0] * q[2] * q[1] * k[1] * nb)
                except (KeyError, IndexError):
                    continue

    # -- watermarks --------------------------------------------------------
    # Three arms, max wins — modeling XLA's liveness-driven schedule:
    #   fwd    everything saved so far peaks at the autodiff boundary
    #          (inside a remat segment the working set rides on top)
    #   bwd    at the start of the backward all residuals are still
    #          alive and the largest transient (the big cotangent OR one
    #          attention layer's score-map scratch) coexists with them;
    #          remat segments add their recompute working set
    #   tail   by the end of the backward residuals are freed but every
    #          parameter cotangent, the AMP weight copies, and the last
    #          big transient coexist before the optimizer consumes them
    # Grads do NOT stack on the bwd arm: XLA interleaves each weight
    # update as its grad settles (latency-hiding scheduler), so full
    # residuals and full grads never coexist — modeling them additively
    # overshot the measured bs16 artifact peaks by 40-50%.
    fwd_wm = resid_bytes + boundary_bytes + seg_work
    bwd_wm = (resid_bytes + boundary_bytes + seg_work
              + max(cot_bytes, attn_scratch))
    tail_wm = grad_bytes + cast_bytes + cot_bytes
    temp = max(fwd_wm, bwd_wm, tail_wm) if has_bwd else fwd_wm

    state = param_bytes + opt_bytes
    peak = state + feed_bytes + kv_bytes + temp
    est = MemoryEstimate(
        breakdown={"params": param_bytes, "optimizer_state": opt_bytes,
                   "activations": temp - (grad_bytes if has_bwd else 0),
                   "grads": grad_bytes, "kv_pools": kv_bytes,
                   "feeds": feed_bytes},
        temp_bytes=temp, state_bytes=state + feed_bytes, peak_bytes=peak,
        details={"residual_bytes": resid_bytes,
                 "pipeline_residual_bytes": pipe_resid,
                 "remat_boundary_bytes": boundary_bytes,
                 "remat_working_bytes": seg_work,
                 "amp_cast_bytes": cast_bytes,
                 "largest_cotangent_bytes": cot_bytes,
                 "fwd_watermark": fwd_wm, "bwd_watermark": bwd_wm})
    return est


def safe_nbytes_raw(block, name, batch) -> int:
    """Bytes at the var's RECORDED dtype (no AMP narrowing) — state
    arrays live at master precision."""
    try:
        v = block.var(name)
    except KeyError:
        return 0
    return _prod(_shape(block, name, batch)) * dtype_nbytes(v.dtype)


# ---------------------------------------------------------------------------
# the budget gate
# ---------------------------------------------------------------------------

class MemoryBudgetError(RuntimeError):
    """Raised BEFORE compile when the static peak-HBM estimate exceeds
    PT_MEM_BUDGET_GB. Carries the per-category breakdown so the report
    names what to shrink (batch, remat, optimizer choice) instead of a
    bare number."""

    def __init__(self, estimate: MemoryEstimate, budget_gb: float):
        self.estimate = estimate
        self.budget_gb = float(budget_gb)
        self.breakdown = dict(estimate.breakdown)
        cats = ", ".join(f"{k}={v / 1e9:.2f}GB"
                         for k, v in estimate.breakdown.items() if v)
        super().__init__(
            f"static peak-HBM estimate {estimate.peak_gb:.2f} GB exceeds "
            f"PT_MEM_BUDGET_GB={budget_gb:g} (pre-compile gate; "
            f"breakdown: {cats})")


def budget_from_env() -> Optional[float]:
    raw = os.environ.get("PT_MEM_BUDGET_GB", "").strip()
    if not raw:
        return None
    try:
        v = float(raw)
    except ValueError:
        raise ValueError(f"malformed PT_MEM_BUDGET_GB={raw!r}: not a "
                         "number of gigabytes") from None
    return v if v > 0 else None


def batch_shard_factor(program: Program, axis_sizes: Dict[str, int]) -> int:
    """Mesh-axis factor by which the feed batch dim (dim 0) is sharded —
    what divides per-device feed/activation residency. Mirrors the
    ParallelExecutor's placement: feeds WITHOUT an explicit placement
    fact batch-split over the dp axis by default (SplitLoDTensor), and
    explicit batch-dim facts take the max on top."""
    factor = int(axis_sizes.get("dp", 1))
    for v in program.global_block.vars.values():
        if not getattr(v, "is_data", False):
            continue
        spec = getattr(v, "sharding", None)
        if not spec or spec[0] is None:
            continue
        entry = spec[0]
        axes = entry if isinstance(entry, (list, tuple)) else (entry,)
        f = 1
        for a in axes:
            f *= int(axis_sizes.get(a, 1))
        factor = max(factor, f)
    return factor


def enforce_budget(program: Program, batch: int = 1,
                   mesh=None) -> Optional[MemoryEstimate]:
    """The executor pre-compile gate: no-op unless PT_MEM_BUDGET_GB is
    set (one env read); otherwise estimate and raise MemoryBudgetError
    on breach. Pure host-side IR walk — never touches device state, so
    a passing budget adds zero syncs to the hot path.

    PT_MEM_BUDGET_GB is a PER-DEVICE budget: with a mesh, the estimate
    prices the per-device batch (global batch / the feed vars' batch-dim
    shard factor) so a dp-sharded program that fits each chip is not
    falsely refused. Params/optimizer state stay whole-program (they are
    replicated under pure dp; under tp/ZeRO the estimate is an upper
    bound — conservative-safe)."""
    budget = budget_from_env()
    if budget is None:
        return None
    est = estimate_memory(program,
                          batch=_per_device_batch(program, batch, mesh))
    if est.peak_bytes > budget * 1e9:
        raise MemoryBudgetError(est, budget)
    return est


def _per_device_batch(program: Program, batch: int, mesh) -> int:
    if mesh is not None and batch > 1:
        from .comm import mesh_axis_sizes
        shards = batch_shard_factor(program, mesh_axis_sizes(mesh))
        if shards > 1 and batch % shards == 0:
            # indivisible batches degrade to replication in the PE feed
            # placement, so only an exact split prices per-device
            batch //= shards
    return batch


def loop_body_steps(program: Program, batch: int = 1, mesh=None,
                    bytes_limit: Optional[int] = None) -> int:
    """How many steps `run_loop`'s scan body holds where the caller names
    no `unroll`: two where the state and TWICE a step's temporaries are
    under the device's memory, one otherwise, and one where the backend
    gives no limit (the CPU's).

    A second step in the body saves a scan iteration in two, which a
    short step feels (on a v5e 23 us a step of 0.38 ms at the 2-layer
    `transformer_lm`, 2 us of 9 at an MLP), and its buffers are live
    beside the first's: where that does not fit, the compiler recomputes
    activations to make room, and at the 1.3B train cell that was the
    head's logits product every step, 14 ms of 266 (PERF.md section 6, PR
    50). Twice the temporaries is more than a second step adds (0.9 GiB
    of 6.2 there), so the rule errs to one step. `bytes_limit`: the
    device's `memory_stats()["bytes_limit"]` (the mesh's first device, or
    `jax.devices()[0]`) unless given, as a test that compiles for a
    described chip has to. Compile-miss only, the same host IR walk as
    `enforce_budget`."""
    if bytes_limit is None:
        import jax
        device = (mesh.devices.flat[0] if mesh is not None
                  else jax.devices()[0])
        bytes_limit = (device.memory_stats() or {}).get("bytes_limit")
    if not bytes_limit:
        return 1
    est = estimate_memory(program,
                          batch=_per_device_batch(program, batch, mesh))
    return 2 if est.state_bytes + 2 * est.temp_bytes < bytes_limit else 1
