"""Per-op analytical cost model + roofline prediction.

The repo's only static cost surface used to be `utils/flops.py`, which
counted forward matmul-class FLOPs and nothing else — so every
gap-closing PR guessed at whether a config was compute-, bandwidth-,
comm-, or host-bound. This module subsumes it: for every block-0 op it
derives

  * `mxu_flops`    — matmul-class work (2 flops/MAC, the MFU convention),
  * `vector_flops` — elementwise/normalization/reduction (VPU) work,
  * `bytes_read` / `bytes_written` — HBM traffic at the op's *device*
    dtype (AMP programs count float32 activations at the amp width),

from the program IR + inferred shapes — the same Program/Block/OpDesc
walk the verifier (verifier.py) and the memory estimator (memory.py)
use, so one analysis layer sees the whole program the way the
executor's pre-pass does.

The roofline layer (`predict_step`) combines those totals with per-chip
peak numbers (PEAK_TABLE) and — given a mesh — the collective audit's
byte volumes (comm.py) into a predicted step time, a predicted MFU, and
a declared bound (`compute | bandwidth | comm`).

Conventions and limits (shared with utils/flops.py, which now shims to
this module):

  * backward ≈ 2x forward for both flops and bytes (dW + dX each cost
    one forward-equivalent) — the standard training multiplier; remat
    segments add their forward flops once more (recompute).
  * ops inside control-flow sub-blocks are not modeled (trip counts are
    dynamic); the RNN benches keep explicit per-config formulas.
  * paged_attention is bounded at FULL context (block_tables width x
    block size): a static model cannot see runtime context lengths, so
    the estimate is the capacity-shaped upper bound.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ..core.program import Program, default_main_program

AUTODIFF_OP = "autodiff"

__all__ = ["OpCost", "ProgramCost", "ChipSpec", "Prediction", "cost_entry",
           "op_cost", "program_cost", "chip_spec_for", "resolve_chip",
           "predict_step", "roofline_step", "PEAK_TABLE",
           "program_feed_bytes", "feed_wire_mbps", "op_roofline_ms",
           "predict_grouped_conv_ms"]


# ---------------------------------------------------------------------------
# per-op cost records
# ---------------------------------------------------------------------------

@dataclass
class OpCost:
    """One op's forward cost. flops split by execution unit (MXU matmul
    work vs VPU vector work) because only MXU flops enter MFU; bytes are
    HBM traffic assuming each named tensor is read/written once (XLA
    fusion makes this an upper bound for elementwise chains)."""

    mxu_flops: int = 0
    vector_flops: int = 0
    bytes_read: int = 0
    bytes_written: int = 0
    #: False = no registered entry; the op was default-modeled as pure
    #: elementwise traffic. The report surfaces these so coverage gaps
    #: are visible instead of silently zero (the utils/flops.py failure
    #: mode this module subsumes).
    covered: bool = True

    @property
    def flops(self) -> int:
        return self.mxu_flops + self.vector_flops

    @property
    def bytes_total(self) -> int:
        return self.bytes_read + self.bytes_written

    def __add__(self, other: "OpCost") -> "OpCost":
        return OpCost(self.mxu_flops + other.mxu_flops,
                      self.vector_flops + other.vector_flops,
                      self.bytes_read + other.bytes_read,
                      self.bytes_written + other.bytes_written,
                      self.covered and other.covered)


@dataclass
class ProgramCost:
    """Whole-program totals + per-op table (block 0)."""

    forward: OpCost
    backward: OpCost
    optimizer: OpCost
    #: forward flops recomputed in the backward by remat segments
    remat_recompute_flops: int = 0
    #: the MXU share of that recompute (the roofline's compute leg runs
    #: on MXU peak, so vector recompute must not inflate it)
    remat_recompute_mxu_flops: int = 0
    per_op: List[Tuple[int, str, OpCost]] = field(default_factory=list)
    uncovered_ops: List[str] = field(default_factory=list)
    has_backward: bool = False

    @property
    def train(self) -> OpCost:
        return self.forward + self.backward + self.optimizer

    @property
    def forward_flops(self) -> int:
        return self.forward.flops

    @property
    def train_flops(self) -> int:
        """Model train flops (MFU numerator convention): recompute is
        NOT useful work, so remat does not enter this number."""
        return self.train.flops

    @property
    def train_bytes(self) -> int:
        return self.train.bytes_total


# ---------------------------------------------------------------------------
# shape/dtype helpers
# ---------------------------------------------------------------------------

_DTYPE_NBYTES = {
    "float64": 8, "int64": 8, "uint64": 8,
    "float32": 4, "int32": 4, "uint32": 4,
    "bfloat16": 2, "float16": 2, "int16": 2, "uint16": 2,
    "int8": 1, "uint8": 1, "bool": 1,
}


def dtype_nbytes(dtype: str) -> int:
    return _DTYPE_NBYTES.get(str(dtype), 4)


def device_nbytes(var, amp: Optional[str]) -> int:
    """Bytes per element as the compiled step sees the value: AMP casts
    float32 activations/params to the amp dtype inside the trace."""
    if amp and str(var.dtype) == "float32":
        return dtype_nbytes(amp)
    return dtype_nbytes(var.dtype)


def _shape(block, name, batch) -> tuple:
    v = block.var(name)
    return tuple(batch if d == -1 else int(d) for d in v.shape)


def _prod(xs) -> int:
    return int(np.prod(xs, dtype=np.int64)) if xs else 1


def var_bytes(block, name, batch, amp=None) -> int:
    v = block.var(name)
    return _prod(_shape(block, name, batch)) * device_nbytes(v, amp)


class _Ctx:
    """Bound helpers handed to cost entries."""

    __slots__ = ("block", "batch", "amp", "_wire_narrow")

    def __init__(self, block, batch, amp):
        self.block, self.batch, self.amp = block, batch, amp
        self._wire_narrow = None

    @property
    def wire_narrow(self):
        """{decoded-var name: wire dtype} for feed_dequant outputs
        (data/codec.py). XLA fuses the elementwise dequant into each
        consumer, so every read of the decoded batch is PHYSICALLY a
        read of the narrow payload from HBM — pricing those reads at the
        wire dtype models the fusion, the same way RESHAPE_ALIAS_OPS
        zero-pricing models bitcasts. Lazily built once per walk."""
        if self._wire_narrow is None:
            wn = {}
            for op in self.block.ops:
                if op.type == "feed_dequant":
                    try:
                        x = self.block.var(op.inputs["X"][0])
                    except KeyError:
                        continue
                    for out in op.output_names():
                        wn[out] = str(x.dtype)
                elif op.type in RESHAPE_ALIAS_OPS and op.inputs.get("X"):
                    # bitcasts carry the fused narrow read through: a
                    # flatten of the decoded batch is still the int8
                    # payload in HBM
                    src = wn.get(op.inputs["X"][0])
                    if src is not None:
                        for out in op.output_names():
                            wn[out] = src
            self._wire_narrow = wn
        return self._wire_narrow

    def shape(self, name):
        return _shape(self.block, name, self.batch)

    def elems(self, name):
        return _prod(self.shape(name))

    def nbytes(self, name):
        wire = self.wire_narrow.get(name)
        if wire is not None:
            return self.elems(name) * dtype_nbytes(wire)
        return var_bytes(self.block, name, self.batch, self.amp)

    def io_bytes(self, op, read_slots=None, write_slots=None):
        reads = [n for slot, ns in op.inputs.items()
                 if read_slots is None or slot in read_slots for n in ns]
        writes = [n for slot, ns in op.outputs.items()
                  if write_slots is None or slot in write_slots for n in ns]
        return (sum(self.nbytes(n) for n in reads),
                sum(self.nbytes(n) for n in writes))


# ---------------------------------------------------------------------------
# entry registry
# ---------------------------------------------------------------------------

_COST: Dict[str, Callable] = {}


def cost_entry(*types: str):
    """Register fn(op, ctx) -> OpCost for the named op types. See
    docs/analysis.md "Cost model" for the how-to-add recipe."""

    def deco(fn):
        for t in types:
            if t in _COST:
                raise ValueError(f"cost entry for {t!r} registered twice")
            _COST[t] = fn
        return fn

    return deco


#: the reshape-alias op family: outputs alias their input buffer (XLA
#: bitcasts). ONE definition shared by the cost model (zero HBM cost),
#: the memory estimator's residual dedup, and the collective audit's
#: spec carry — add new alias-class ops here, nowhere else.
RESHAPE_ALIAS_OPS = frozenset({
    "reshape", "reshape2", "squeeze", "squeeze2", "unsqueeze",
    "unsqueeze2", "flatten", "flatten2",
})

#: ops with no HBM cost at all: aliases/metadata (XLA compiles reshapes
#: to bitcasts) and the executor-injected pseudo-ops
_FREE_OPS = RESHAPE_ALIAS_OPS | frozenset({
    "feed", "fetch", AUTODIFF_OP,
    "step_health", "shape", "increment", "assign",
})

#: per-element vector-flop weight for elementwise-ish ops (default 1)
_VECTOR_WEIGHT = {
    "gelu": 10, "tanh": 6, "sigmoid": 4, "swish": 6, "softplus": 6,
    "elu": 4, "exp": 4, "log": 4, "softmax": 5,
    "layer_norm": 8, "batch_norm": 8, "softmax_with_cross_entropy": 8,
    "rms_norm": 6, "rotary_embedding": 6,
    "cross_entropy": 4, "dropout": 2,
}

#: ops DELIBERATELY modeled as 1-flop/element traffic — the right cost,
#: not a coverage gap. Everything else falling through to the default is
#: reported in uncovered_ops, so a genuinely unmodeled op stays visible
#: against a quiet baseline instead of drowning in elementwise noise.
_ELEMENTWISE_OPS = frozenset({
    "elementwise_add", "elementwise_sub", "elementwise_mul",
    "elementwise_div", "elementwise_max", "elementwise_min", "relu",
    "relu6", "leaky_relu", "softsign", "square", "sqrt", "abs", "scale",
    "cast", "clip", "mean", "sum", "reduce_sum", "reduce_mean",
    "reduce_max", "reduce_min", "square_error_cost", "slice", "concat",
    "split", "stack", "gather", "pad", "pad2d", "one_hot", "top_k",
    "accuracy", "transpose", "transpose2", "sequence_softmax",
    "uniform_random", "gaussian_random", "fill_constant", "embedding",
    "arange",
})


def _op_cost_ctx(op, ctx: _Ctx) -> OpCost:
    if op.type in _FREE_OPS:
        return OpCost()
    fn = _COST.get(op.type)
    if fn is not None:
        return fn(op, ctx)
    r, w = ctx.io_bytes(op)
    out_elems = sum(ctx.elems(n) for n in op.output_names())
    weight = _VECTOR_WEIGHT.get(op.type, 1)
    known = op.type in _VECTOR_WEIGHT or op.type in _ELEMENTWISE_OPS
    return OpCost(vector_flops=out_elems * weight, bytes_read=r,
                  bytes_written=w, covered=known)


def op_cost(op, block, batch: int = 1, amp: Optional[str] = None) -> OpCost:
    """Forward cost of one op. Ops without a registered entry are
    modeled as pure elementwise traffic; covered=False only for op types
    outside the curated elementwise/weighted tables."""
    return _op_cost_ctx(op, _Ctx(block, batch, amp))


# ---------------------------------------------------------------------------
# matmul-class entries (MXU)
# ---------------------------------------------------------------------------

@cost_entry("conv2d", "depthwise_conv2d", "conv3d")
def _conv_cost(op, ctx):
    out = ctx.shape(op.outputs["Output"][0])
    w = ctx.shape(op.inputs["Filter"][0])
    # out [N, Cout, *spatial]; w [Cout, Cin/g, *k]
    flops = 2 * _prod(out) * _prod(w[1:])
    r, wr = ctx.io_bytes(op)
    return OpCost(mxu_flops=flops, bytes_read=r, bytes_written=wr)


@cost_entry("fused_conv2d")
def _fused_conv_cost(op, ctx):
    # conv2d + BN(+add)(+relu) collapsed into one op (analysis/fuse.py):
    # identical MXU work to the conv it absorbed, epilogue vector work at
    # batch_norm's per-element weight (+1 each for the folded add/relu) —
    # and, the point of the fusion, io_bytes over the op's ACTUAL slots:
    # the conv output / BN Y / add out intermediates no longer exist, so
    # their HBM round-trips drop out of the model structurally. The
    # strict-decrease regression in tests/test_conv_fusion.py pins this
    # against the unfused chain.
    out = ctx.shape(op.outputs["Output"][0])
    w = ctx.shape(op.inputs["Filter"][0])
    flops = 2 * _prod(out) * _prod(w[1:])
    a = op.attrs or {}
    weight = _VECTOR_WEIGHT["batch_norm"] \
        + (1 if a.get("with_add") else 0) + (1 if a.get("act") else 0)
    r, wr = ctx.io_bytes(op)
    return OpCost(mxu_flops=flops, vector_flops=weight * _prod(out),
                  bytes_read=r, bytes_written=wr)


@cost_entry("conv2d_transpose", "conv3d_transpose")
def _conv_t_cost(op, ctx):
    x = ctx.shape(op.inputs["Input"][0])
    w = ctx.shape(op.inputs["Filter"][0])
    flops = 2 * _prod(x) * _prod(w[1:])
    r, wr = ctx.io_bytes(op)
    return OpCost(mxu_flops=flops, bytes_read=r, bytes_written=wr)


@cost_entry("mul")
def _mul_cost(op, ctx):
    x = ctx.shape(op.inputs["X"][0])
    y = ctx.shape(op.inputs["Y"][0])
    xn = (op.attrs or {}).get("x_num_col_dims", 1)
    yn = (op.attrs or {}).get("y_num_col_dims", 1)
    flops = 2 * _prod(x[:xn]) * _prod(x[xn:]) * _prod(y[yn:])
    r, w = ctx.io_bytes(op)
    return OpCost(mxu_flops=flops, bytes_read=r, bytes_written=w)


@cost_entry("matmul")
def _matmul_cost(op, ctx):
    x = ctx.shape(op.inputs["X"][0])
    out = ctx.shape(op.outputs["Out"][0])
    if (op.attrs or {}).get("transpose_X"):
        k = x[-2] if len(x) >= 2 else x[-1]
    else:
        k = x[-1]
    r, w = ctx.io_bytes(op)
    return OpCost(mxu_flops=2 * _prod(out) * int(k), bytes_read=r,
                  bytes_written=w)


@cost_entry("scaled_dot_product_attention")
def _sdpa_cost(op, ctx):
    q = ctx.shape(op.inputs["Q"][0])
    kv = ctx.shape(op.inputs["K"][0])
    b, sq, h, d = q
    sk = kv[1]
    # QK^T + PV at 2 flops/MAC; softmax is vector work over the S^2 map
    mxu = 2 * 2 * b * h * sq * sk * d
    vec = 5 * b * h * sq * sk
    # flash kernel: q/k/v read once, out written once — the S^2 score
    # matrix never touches HBM (kernels/flash_attention.py)
    r, w = ctx.io_bytes(op)
    return OpCost(mxu_flops=mxu, vector_flops=vec, bytes_read=r,
                  bytes_written=w)


@cost_entry("moe_gated_ffn")
def _moe_gated_cost(op, ctx):
    # dropless top-k: every routed (row, expert) pair through three
    # D x H matrices, plus the router; no capacity padding to count
    # (ops/moe_ops.py: rows sorted by expert, k*N rows of matmul)
    x = ctx.shape(op.inputs["X"][0])
    e, d, h = ctx.shape(op.inputs["WGate"][0])
    n, k = _prod(x[:-1]), int(op.attrs["top_k"])
    mxu = 2 * n * k * 3 * d * h + 2 * n * d * e
    vec = n * (5 * e + 6 * k * h)          # softmax, silu-and-gate
    # traffic: the activations once, and the weights of the experts a
    # call can touch (all of them once n*k reaches E)
    w_bytes = ctx.nbytes(op.inputs["WGate"][0]) // e
    reads = (ctx.nbytes(op.inputs["X"][0])
             + ctx.nbytes(op.inputs["RouterW"][0])
             + 3 * min(e, n * k) * w_bytes)
    return OpCost(mxu_flops=mxu, vector_flops=vec, bytes_read=reads,
                  bytes_written=ctx.nbytes(op.outputs["Out"][0]))


def paged_max_context(op, block) -> int:
    """Static context bound of a paged decode op: block-table width x
    tokens per block (the pool's dim 1)."""
    bt = tuple(int(d) for d in block.var(op.inputs["BlockTables"][0]).shape)
    pool = tuple(int(d) for d in block.var(op.inputs["KPool"][0]).shape)
    return int(bt[-1]) * int(pool[1])


@cost_entry("paged_attention")
def _paged_attn_cost(op, ctx):
    # Q [S, 1, H, D] — one token per slot; attended span bounded by the
    # block table capacity (runtime context_lens are data, not IR)
    q = ctx.shape(op.inputs["Q"][0])
    slots, _, h, d = q
    span = paged_max_context(op, ctx.block)
    mxu = 2 * 2 * slots * h * span * d
    vec = 5 * slots * h * span
    # traffic (the gather-based decode path: paged_attention.py
    # paged_attention_reference): jnp.take streams each resident pool —
    # HBM moves whole pages regardless of which rows the tables hit —
    # then MATERIALIZES the gathered [slots, span, H, D] copy, which
    # the attention contraction reads back. Per pool that is a pool
    # stream + a copy write + a copy read, for K and for V. The
    # original entry priced one optimistic min(pool, gather) pass and
    # came in ~45x under measurement on the decode report (every peer
    # op sat at ~10-40x dispatch overhead; this one was off-family) —
    # per-decode-step KV bytes are the dominant cost of the decode
    # plane, and a model that misses them by an order of magnitude
    # mis-ranks every serving plan. The residual constant factor rides
    # on the measured calibration layer like every other op.
    kv_nbytes = device_nbytes(ctx.block.var(op.inputs["KPool"][0]), ctx.amp)
    pool_elems = ctx.elems(op.inputs["KPool"][0])
    gather_elems = slots * span * h * d
    reads = (2 * (pool_elems + gather_elems) * kv_nbytes
             + ctx.nbytes(op.inputs["Q"][0])
             + ctx.nbytes(op.inputs["BlockTables"][0])
             + ctx.nbytes(op.inputs["ContextLens"][0]))
    writes = (2 * gather_elems * kv_nbytes
              + ctx.nbytes(op.outputs["Out"][0]))
    return OpCost(mxu_flops=mxu, vector_flops=vec, bytes_read=reads,
                  bytes_written=writes)


@cost_entry("paged_kv_write")
def _paged_write_cost(op, ctx):
    # scatter ONE K/V row per slot into its page: the written rows plus
    # index traffic — never a whole-pool copy (donation aliases the pool)
    row_bytes = ctx.nbytes(op.inputs["K"][0]) + ctx.nbytes(op.inputs["V"][0])
    idx = (ctx.nbytes(op.inputs["BlockTables"][0])
           + ctx.nbytes(op.inputs["ContextLens"][0]))
    return OpCost(bytes_read=row_bytes + idx, bytes_written=row_bytes)


@cost_entry("feed_dequant")
def _feed_dequant_cost(op, ctx):
    # the wire-codec boundary (data/codec.py): reads the feed at its
    # RECORDED wire dtype (int8/bf16 — that is the whole point) plus the
    # tiny scale. The decoded output — and every downstream read of it —
    # is priced at the wire dtype too (ctx.wire_narrow): XLA fuses the
    # elementwise dequant into its consumers, so the f32 batch never
    # round-trips HBM as its own buffer. ~2 vector flops/element
    # (cast + scale multiply).
    r, w = ctx.io_bytes(op)
    return OpCost(vector_flops=2 * ctx.elems(op.outputs["Out"][0]),
                  bytes_read=r, bytes_written=w)


@cost_entry("pipeline")
def _pipeline_cost(op, ctx):
    # the auto-pp rewrite (transpiler/pipeline_transpiler.py): one layer
    # body in a sub-block, executed num_stages x layers_per_stage times
    # over the full batch (microbatching splits WHEN work runs, not how
    # much) — so the op prices as the sub-block's per-layer cost times
    # the stacked layer count, keeping pipelined and inline programs
    # comparable. Inner vars carry occurrence-0 shapes (batch dim -1
    # substitutes ctx.batch); names the sub-block lacks resolve through
    # the parent chain (shared masks/scales).
    attrs = op.attrs or {}
    sub = ctx.block.program.blocks[int(attrs["sub_block"])]
    inner = _Ctx(sub, ctx.batch, ctx.amp)
    layer = OpCost()
    for o in sub.ops:
        try:
            layer = layer + _op_cost_ctx(o, inner)
        except KeyError:
            continue
    n = int(attrs.get("num_stages", 1)) * int(attrs.get(
        "layers_per_stage", 1))
    return OpCost(mxu_flops=layer.mxu_flops * n,
                  vector_flops=layer.vector_flops * n,
                  bytes_read=layer.bytes_read * n,
                  bytes_written=layer.bytes_written * n,
                  covered=layer.covered)


@cost_entry("lookup_table")
def _lookup_cost(op, ctx):
    ids = ctx.elems(op.inputs["Ids"][0])
    w = ctx.block.var(op.inputs["W"][0])
    width = int(w.shape[-1])
    nb = device_nbytes(w, ctx.amp)
    gathered = ids * width * nb
    return OpCost(bytes_read=gathered + ctx.nbytes(op.inputs["Ids"][0]),
                  bytes_written=gathered)


@cost_entry("pool2d")
def _pool_cost(op, ctx):
    out = ctx.elems(op.outputs["Out"][0])
    ksize = (op.attrs or {}).get("ksize") or (op.attrs or {}).get(
        "pool_size") or [1]
    if not isinstance(ksize, (list, tuple)):
        ksize = [ksize, ksize]
    r, w = ctx.io_bytes(op)
    return OpCost(vector_flops=out * _prod(ksize), bytes_read=r,
                  bytes_written=w)


# optimizer update ops: pure vector passes over param-sized state.
# weights ~= arithmetic ops per element in the update rule.
_OPT_VECTOR_WEIGHT = {"sgd": 2, "momentum": 4, "adam": 12, "adagrad": 6,
                      "adamax": 10, "adadelta": 10, "rmsprop": 8,
                      "decayed_adagrad": 8, "ftrl": 10, "proximal_gd": 4}


def _optimizer_cost(op, ctx):
    r, w = ctx.io_bytes(op)
    elems = ctx.elems(op.inputs["Param"][0])
    weight = _OPT_VECTOR_WEIGHT.get(op.type, 6)
    return OpCost(vector_flops=elems * weight, bytes_read=r,
                  bytes_written=w)


for _t in _OPT_VECTOR_WEIGHT:
    cost_entry(_t)(_optimizer_cost)


# ---------------------------------------------------------------------------
# program totals
# ---------------------------------------------------------------------------

def _remat_tagged(op) -> bool:
    return op.attrs.get("remat_scope") is not None


def program_cost(program: Optional[Program] = None, batch: int = 1,
                 train: Optional[bool] = None) -> ProgramCost:
    """Cost totals for block 0 at `batch` (dynamic -1 dims substitute
    it). train=None auto-detects from the autodiff marker; train=False
    forces inference accounting (no backward even if the marker exists).
    """
    program = program or default_main_program()
    block = program.global_block
    amp = program.amp_dtype
    bwd_idx = next((i for i, o in enumerate(block.ops)
                    if o.type == AUTODIFF_OP), None)
    has_bwd = bwd_idx is not None if train is None else bool(
        train and bwd_idx is not None)
    fwd_stop = bwd_idx if bwd_idx is not None else len(block.ops)

    fwd = OpCost()
    opt = OpCost()
    remat_flops = 0
    remat_mxu = 0
    per_op: List[Tuple[int, str, OpCost]] = []
    uncovered: List[str] = []
    ctx = _Ctx(block, batch, amp)  # one walk context: the wire-narrow
    for i, op in enumerate(block.ops):  # map builds once, not per op
        if op.type == AUTODIFF_OP:
            continue
        try:
            c = _op_cost_ctx(op, ctx)
        except KeyError:
            # var pruned/renamed (cloned program slices): skip that op
            continue
        per_op.append((i, op.type, c))
        if not c.covered and op.type not in uncovered:
            uncovered.append(op.type)
        if i < fwd_stop:
            fwd = fwd + c
            if has_bwd and _remat_tagged(op):
                remat_flops += c.flops
                remat_mxu += c.mxu_flops
        else:
            opt = opt + c

    if has_bwd:
        # dW + dX each cost one forward-equivalent in flops AND traffic;
        # remat additionally re-runs its segments' forward (counted
        # separately — recompute is not model work for MFU)
        bwd = OpCost(mxu_flops=2 * fwd.mxu_flops,
                     vector_flops=2 * fwd.vector_flops,
                     bytes_read=2 * fwd.bytes_read,
                     bytes_written=2 * fwd.bytes_written)
    else:
        bwd = OpCost()
        opt = OpCost()  # no optimizer suffix without a backward
    pc = ProgramCost(forward=fwd, backward=bwd, optimizer=opt,
                     remat_recompute_flops=remat_flops,
                     remat_recompute_mxu_flops=remat_mxu, per_op=per_op,
                     uncovered_ops=uncovered, has_backward=has_bwd)
    return pc


# ---------------------------------------------------------------------------
# roofline
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ChipSpec:
    """Per-chip peaks. Flops are the bf16 MXU peak (the benched dtype);
    hbm_gbps is the published HBM bandwidth; ici_gbps the per-link ICI
    bandwidth used for collective time; hbm_gb the per-chip HBM capacity
    (the placement planner's per-device memory budget)."""

    name: str
    peak_flops: float
    hbm_gbps: float
    ici_gbps: float
    hbm_gb: float = 16.0


#: published per-chip peaks; the CPU entry exists so off-TPU runs emit
#: finite (clearly-labeled) predictions instead of crashing the report.
#: A device that is not in the table is an error, never a default: a
#: utilization over a guessed peak is not a measurement
PEAK_TABLE: Tuple[ChipSpec, ...] = (
    ChipSpec("tpu v5 lite", 197e12, 819.0, 186.0, 16.0),
    ChipSpec("tpu v5e", 197e12, 819.0, 186.0, 16.0),
    ChipSpec("tpu v5p", 459e12, 2765.0, 600.0, 95.0),
    ChipSpec("tpu v5", 459e12, 2765.0, 600.0, 95.0),
    ChipSpec("tpu v4", 275e12, 1228.0, 268.0, 32.0),
    ChipSpec("tpu v6", 918e12, 1640.0, 448.0, 32.0),
    ChipSpec("cpu", 1e12, 50.0, 10.0, 16.0),
)


def chip_spec_for(device_kind: str) -> ChipSpec:
    kind = (device_kind or "").lower()
    for spec in PEAK_TABLE:
        if spec.name in kind:
            return spec
    raise ValueError(
        f"device kind {device_kind!r} is not in PEAK_TABLE "
        f"({sorted(s.name for s in PEAK_TABLE)}): add its published "
        "peaks before reporting a utilization on it")


def resolve_chip(device=None) -> ChipSpec:
    """PT_COST_CHIP overrides the detected chip (so an off-TPU host can
    predict for the deployment chip); otherwise the given/default jax
    device's kind selects from PEAK_TABLE (unknown kinds raise)."""
    override = os.environ.get("PT_COST_CHIP", "").strip()
    if override:
        return chip_spec_for(override)
    if device is None:
        import jax
        device = jax.devices()[0]
    return chip_spec_for(getattr(device, "device_kind", str(device)))


def calibration_scale(per_op, chip: ChipSpec, calibration=None) -> float:
    """The whole-program correction the per-op-type factors imply: the
    RAW-roofline-ms-weighted mean factor over `per_op` (ProgramCost
    .per_op — (index, op_type, OpCost) triples). Weighting by each op's
    raw roofline share makes the scale exactly the calibrated-sum /
    raw-sum ratio — a factor on an op that is 60% of the step moves the
    step 60% as far as the factor says, and ops the fit never measured
    (factor 1.0) dilute it honestly. 1.0 when uncalibrated or when
    nothing has weight (an empty program prices raw)."""
    if calibration is None or not per_op:
        return 1.0
    total = 0.0
    corrected = 0.0
    for _idx, op_type, c in per_op:
        ms, _bound = op_roofline_ms(c, chip)
        total += ms
        corrected += ms * calibration.factor(op_type)
    return corrected / total if total > 0.0 else 1.0


def roofline_step(hw_mxu_flops: float, hbm_bytes: float,
                  model_mxu_flops: float, n_dev: int, chip: ChipSpec,
                  t_comm_s: float, calibration=None, per_op=None):
    """The shared roofline: per-device compute/HBM legs vs an
    already-priced comm leg, overlap-as-max step time, the bound
    tie-break, and predicted MFU. ONE definition — predict_step and the
    placement planner (analysis/planner.py) must price the same
    roofline, or search rankings silently diverge from the
    bench/cost_report predictions for the identical program.

    Returns (t_compute_s, t_hbm_s, t_step_s, bound, predicted_mfu).
    hw_mxu_flops is hardware MXU work (model + remat recompute);
    model_mxu_flops is the MFU numerator (recompute excluded).

    A Calibration (with the program's ProgramCost.per_op triples)
    scales BOTH device legs by calibration_scale — one measured
    whole-program correction, so the bound tie-break between compute
    and bandwidth is unchanged (one factor scales both) and MFU falls
    exactly as far as the fabric measured slower. The comm leg arrives
    already calibrated: the CALLER scales its wire part by the same
    calibration_scale (the fit cannot observe collectives, and a
    partially-scaled roofline would not stay monotone in the raw one)
    and adds the measured per-dispatch constants unscaled."""
    scale = calibration_scale(per_op, chip, calibration)
    t_compute = scale * (hw_mxu_flops / n_dev) / chip.peak_flops
    t_hbm = scale * (hbm_bytes / n_dev) / (chip.hbm_gbps * 1e9)
    t = max(t_compute, t_hbm, t_comm_s, 1e-12)
    # tie-break: compute wins any tie; comm beats bandwidth only strictly
    if t_compute >= t_hbm and t_compute >= t_comm_s:
        bound = "compute"
    elif t_comm_s > t_hbm:
        bound = "comm"
    else:
        bound = "bandwidth"
    mfu = min((model_mxu_flops / n_dev) / (t * chip.peak_flops), 1.0)
    return t_compute, t_hbm, t, bound, mfu


def op_roofline_ms(c: OpCost, chip: ChipSpec, op_type: str = None,
                   calibration=None) -> Tuple[float, str]:
    """ONE op's roofline time on `chip`: max of the MXU-compute and
    HBM-traffic legs (the same two device legs roofline_step overlaps
    for the whole program), in ms, plus the leg that set it. The per-op
    profiler (obs/opprof.py) uses this both as each op's predicted_ms
    and as the weight that distributes a measured segment's time across
    its member ops — so the ledger's predicted column and its
    attribution shares come from one formula.

    With a Calibration and the op's type, the measured per-op-type
    correction factor multiplies the time (the bound stays the raw
    leg: one factor scales both legs, so their order is unchanged)."""
    t_compute = c.mxu_flops / chip.peak_flops
    t_hbm = c.bytes_total / (chip.hbm_gbps * 1e9)
    bound = "compute" if t_compute >= t_hbm else "bandwidth"
    ms = max(t_compute, t_hbm) * 1e3
    if calibration is not None and op_type:
        ms *= calibration.factor(op_type)
    return ms, bound


def predict_grouped_conv_ms(n, cin, h, w, cout, groups, stride, k=3,
                            dtype: str = "float32",
                            chip: Optional[ChipSpec] = None,
                            train: bool = True) -> float:
    """Roofline prediction for one grouped conv2d shape — the static
    side of the gconv autotune harness (utils/gconv_autotune.py), which
    records each candidate formulation's measured ms NEXT TO this
    prediction so every cache entry carries its own predicted-vs-
    measured delta. train=True models the harness's fwd+dW chain step
    (~2 forward-equivalents — the chained loss differentiates w.r.t.
    the filter only)."""
    chip = chip or resolve_chip()
    sh, sw = (stride if isinstance(stride, (tuple, list))
              else (stride, stride))
    ho, wo = max(int(h) // int(sh), 1), max(int(w) // int(sw), 1)
    flops = 2 * n * ho * wo * cout * (cin // max(groups, 1)) * k * k
    nb = dtype_nbytes(dtype)
    traffic = (n * cin * h * w + cout * (cin // max(groups, 1)) * k * k
               + n * cout * ho * wo) * nb
    mult = 2 if train else 1
    t = max(mult * flops / chip.peak_flops,
            mult * traffic / (chip.hbm_gbps * 1e9))
    return t * 1e3


@dataclass
class Prediction:
    flops: int
    hbm_bytes: int
    comm_bytes: int
    t_compute_ms: float
    t_bandwidth_ms: float
    t_comm_ms: float
    predicted_step_ms: float
    predicted_mfu: float
    bound: str
    chip: str
    #: bytes one step's feeds push through the host->device pipe, at the
    #: feeds' RECORDED dtype — the wire dtype for codec-rewritten
    #: programs (data/codec.py), so the model sees the codec's win
    #: before it is measured
    feed_wire_bytes: int = 0
    #: the host-pipe leg: feed_wire_bytes / PT_FEED_WIRE_MBPS (0 when
    #: the knob is unset — co-located hosts upload at PCIe rates and the
    #: leg vanishes under the device legs)
    t_feed_ms: float = 0.0

    def to_dict(self) -> dict:
        return {
            "flops": int(self.flops), "hbm_bytes": int(self.hbm_bytes),
            "comm_bytes": int(self.comm_bytes),
            "t_compute_ms": round(self.t_compute_ms, 4),
            "t_bandwidth_ms": round(self.t_bandwidth_ms, 4),
            "t_comm_ms": round(self.t_comm_ms, 4),
            "predicted_step_ms": round(self.predicted_step_ms, 4),
            "predicted_mfu": round(self.predicted_mfu, 4),
            "bound": self.bound, "chip": self.chip,
            "feed_wire_bytes": int(self.feed_wire_bytes),
            "t_feed_ms": round(self.t_feed_ms, 4),
        }


def program_feed_bytes(program: Optional[Program] = None,
                       batch: int = 1) -> int:
    """Bytes one step's feeds push through the host->device pipe, at
    each feed's RECORDED dtype — the wire dtype for codec-rewritten
    programs (data/codec.py apply_wire_codec), and deliberately NOT the
    AMP device dtype: the entry cast happens on device, after the wire.
    Paged KV pools are device-resident (fetch->feed threading) and never
    cross the pipe, so they are excluded like memory.py's feed
    breakdown."""
    program = program or default_main_program()
    block = program.global_block
    pool_names = set()
    for op in block.ops:
        if op.type in ("paged_attention", "paged_kv_write"):
            for slot in ("KPool", "VPool"):
                pool_names.update(op.inputs.get(slot, ()))
    total = 0
    for v in block.vars.values():
        if getattr(v, "is_data", False) and v.name not in pool_names:
            try:
                total += _prod(_shape(block, v.name, batch)) \
                    * dtype_nbytes(v.dtype)
            except KeyError:
                continue
    return total


def feed_wire_mbps() -> float:
    """PT_FEED_WIRE_MBPS: the modeled host->device pipe rate in MB/s
    (0/unset = pipe not modeled — the feed leg drops out). Lets a
    thin-pipe host (a ~15 MB/s host->device link) see the codec's win in
    predict_step before measuring it."""
    raw = os.environ.get("PT_FEED_WIRE_MBPS", "").strip()
    if not raw:
        return 0.0
    try:
        v = float(raw)
    except ValueError:
        raise ValueError(f"malformed PT_FEED_WIRE_MBPS={raw!r}: not a "
                         "number of MB/s") from None
    return v if v > 0 else 0.0


def predict_step(program: Optional[Program] = None, batch: int = 1,
                 chip: Optional[ChipSpec] = None, mesh=None,
                 train: Optional[bool] = None,
                 comm_report=None, calibration=None) -> Prediction:
    """Roofline prediction for one step of block 0.

    The device legs overlap on real hardware (XLA's latency-hiding
    scheduler), so the step estimate is the MAX, and the bound is the
    leg that set it. predicted_mfu = model_flops / (t * peak) is <= the
    hardware ceiling by construction. With a mesh, per-device flops and
    bytes divide by the device count and comm comes from the collective
    audit (comm.py); pass an already-computed `comm_report` (CommReport)
    to reuse it instead of re-auditing.

    Under PT_FEED_WIRE_MBPS a fourth leg models the host->device feed
    pipe at the feeds' wire dtype (program_feed_bytes): when it sets the
    max, the declared bound is `host` — the thin-pipe reading BENCH r05
    measured, now predicted. Unset, the leg is 0 and predictions are
    byte-identical to before.

    `calibration`: None reads the ambient PT_CALIB_PATH artifact
    (calibrate.default_calibration — unset env means raw, exactly the
    pre-calibration numbers); `calibrate.RAW` forces raw; an explicit
    Calibration is staleness-checked (chip + program fingerprint) and
    falls back to raw with one warning if it does not apply. Applied:
    the device legs scale by the measured per-op-type factors
    (roofline_step) and the audited collective set pays the fitted
    per-dispatch overhead once on the comm leg (one combined dispatch
    group per step — the XLA collective-combiner behavior PR 15's rank
    gate documented).
    """
    chip = chip or resolve_chip()
    from . import calibrate
    if calibration is None:
        calibration = calibrate.default_calibration()
    try:
        fp = (program or default_main_program()).fingerprint()
    except Exception:   # noqa: BLE001 — a fingerprint failure prices raw
        fp = None
    cal = calibrate.resolve(calibration, chip=chip.name, fingerprint=fp,
                            context="predict_step")
    pc = program_cost(program, batch=batch, train=train)
    flops = pc.train.mxu_flops + pc.train.vector_flops
    # hardware MXU work: the model flops plus the remat segments' forward
    # re-run ONCE inside the backward (the HFU-style numerator; vector
    # recompute runs on the VPU and must not inflate the MXU leg)
    mxu = pc.train.mxu_flops + pc.remat_recompute_mxu_flops
    hbm = pc.train_bytes
    comm_bytes = 0
    n_dev = 1
    n_coll = 0
    if comm_report is not None:
        axes = dict(comm_report.axis_sizes)
        n_dev = max(1, _prod(list(axes.values())))
        comm_bytes = comm_report.total_bytes
        n_coll = len(comm_report.collectives)
    elif mesh is not None:
        from .comm import audit_collectives, mesh_axis_sizes
        axes = mesh_axis_sizes(mesh)
        n_dev = max(1, _prod(list(axes.values())))
        report = audit_collectives(program, axes, batch=batch)
        comm_bytes = report.total_bytes
        n_coll = len(report.collectives)
    # fabric scale first, measured dispatch constant second: the fit
    # cannot observe collectives (profiles are single-device), so the
    # wire leg rides the SAME fitted scale as the device legs — scaling
    # only the legs the fit saw would let the bound flip to an unscaled
    # leg and break the monotone raw->calibrated property the rank gate
    # pins. The per-dispatch constant then adds UNSCALED: it is a
    # wall-clock reading, not a modeled time.
    t_comm = (comm_bytes / (chip.ici_gbps * 1e9)
              * calibration_scale(pc.per_op, chip, cal))
    if cal is not None and n_coll:
        # ONE per-dispatch overhead for the whole audited set: XLA's
        # collective combiner folds a step's inline collectives into a
        # single dispatch group (planner._score prices the same way;
        # scan-resident ppermutes, which dispatch per tick, pay per hop
        # there)
        t_comm += cal.dispatch_overhead_s
    t_compute, t_hbm, t, bound, mfu = roofline_step(
        mxu, hbm, pc.train.mxu_flops, n_dev, chip, t_comm,
        calibration=cal, per_op=pc.per_op)
    feed_bytes = program_feed_bytes(program, batch=batch)
    mbps = feed_wire_mbps()
    t_feed = feed_bytes / (mbps * 1e6) if mbps else 0.0
    if t_feed > t:
        # the pipe is one serial host leg (not per-device): when it
        # dominates even the overlapped device legs, the step is
        # host-bound and MFU re-derives against the longer step
        t = t_feed
        bound = "host"
        mfu = min((pc.train.mxu_flops / n_dev) / (t * chip.peak_flops),
                  1.0)
    return Prediction(flops=flops, hbm_bytes=hbm, comm_bytes=comm_bytes,
                      t_compute_ms=t_compute * 1e3,
                      t_bandwidth_ms=t_hbm * 1e3, t_comm_ms=t_comm * 1e3,
                      predicted_step_ms=t * 1e3,
                      predicted_mfu=mfu, bound=bound,
                      chip=chip.name,
                      feed_wire_bytes=feed_bytes,
                      t_feed_ms=t_feed * 1e3)
