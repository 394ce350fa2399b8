"""Sharding-aware static collective audit.

Given a program + a mesh (or a plain {axis: size} dict), enumerate every
collective the sharded execution implies — all-reduce / all-gather /
reduce-scatter / all-to-all / ppermute — with its byte volume, WITHOUT
compiling anything. "Synthesizing Optimal Parallelism Placement and
Reduction Strategies" (PAPERS.md) shows collective choice and placement
are statically derivable from program + mesh; this module is that
derivation over the same VarDesc.sharding placement facts the shard-check
verifier pass and the GSPMD lowering consume.

The audit does a lightweight forward sharding propagation over block 0
(annotated params/feeds seed it; per-op transfer functions push per-dim
axis sets through the graph) and classifies each induced collective:

  intentional    the placement the transpiler derives on purpose —
                 Megatron partial-sum reductions at row-parallel matmuls,
                 vocab-sharded embedding combines, dp gradient sync,
                 ring/Ulysses sequence-parallel attention exchanges.
  accidental     resharding nobody asked for: an op with no sharding rule
                 consuming a tensor sharded on a non-batch dim forces
                 GSPMD to materialize (all-gather) the full value every
                 step. The classic: a column-parallel logits projection
                 feeding softmax_with_cross_entropy — the vocab-sharded
                 logits are silently gathered, and the "distributed"
                 projection costs MORE than the replicated one.

Accidental collectives surface as `accidental-all-gather` WARNING
diagnostics through the `collective-audit` verifier pass (it runs only
when the caller supplies a mesh — ParallelExecutor's pre-pass and the
transpiler post-condition gate do; the single-chip executor has no mesh
to audit against).

Byte conventions (ring algorithms, the TPU ICI default):
  all_reduce      wire = 2 (n-1)/n x payload   (reduce-scatter + all-gather)
  all_gather      wire = (n-1)/n x full gathered size
  reduce_scatter  wire = (n-1)/n x payload
  all_to_all      wire = (n-1)/n x payload
  ppermute (ring) wire = (n-1)   x per-step shard (the full rotation)
`wire_bytes` is PER DEVICE — the number the roofline's comm leg divides
by ICI bandwidth (cost.predict_step).

Reduction-algorithm synthesis (PAPERS: "Synthesizing Optimal Parallelism
Placement and Reduction Strategies on Hierarchical Systems"): the ring
convention above is only ONE implementation. `collective_time_s` prices
each collective under three algorithms and `choose_algorithms` picks the
cheapest per collective — the planner's searched dimension:

  ring          bandwidth-optimal: wire/bw + steps x hop latency
                (steps = 2(n-1) for all_reduce, n-1 otherwise). Wins
                large payloads; pays n-1 latencies.
  tree          latency-optimal: ~2 full-payload traversals of a
                ceil(log2 n)-deep binomial tree for all_reduce (one for
                gather/scatter). Wins small, latency-bound collectives.
  hierarchical  for groups SPANNING hosts: ICI reduce-scatter inside
                each host, a DCI ring over the 1/intra shard across
                hosts, ICI all-gather back — only payload/intra ever
                crosses the slow tier, beating a flat ring (which pays
                the DCI rate on every hop) whenever DCI < ICI.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

from ..core.program import Program, default_main_program
#: the searched per-collective algorithm alphabet — ONE definition,
#: owned by artifacts.py (the stdlib import leaf) so the plan validator
#: and these cost formulas can never drift
from .artifacts import PLAN_ALGORITHMS as ALGORITHMS
from .cost import (AUTODIFF_OP, RESHAPE_ALIAS_OPS, _prod, _shape,
                   device_nbytes, dtype_nbytes)
from .verifier import WARNING, Diagnostic, verifier_pass

__all__ = ["Collective", "CommReport", "audit_collectives",
           "mesh_axis_sizes", "ALGORITHMS", "collective_time_s",
           "choose_algorithm", "choose_algorithms", "group_host_split"]


def mesh_axis_sizes(mesh) -> Dict[str, int]:
    """Normalize a jax Mesh / {axis: size} dict to {axis: size}."""
    if isinstance(mesh, dict):
        return {str(k): int(v) for k, v in mesh.items()}
    shape = getattr(mesh, "shape", None)
    if shape is not None:
        return {str(k): int(v) for k, v in dict(shape).items()}
    raise TypeError(f"mesh must be a Mesh or {{axis: size}} dict, "
                    f"got {type(mesh).__name__}")


@dataclass(frozen=True)
class Collective:
    """One statically-derived collective."""

    kind: str            # all_reduce | all_gather | reduce_scatter | ...
    axes: Tuple[str, ...]
    group: int           # devices participating (product of axis sizes)
    payload_bytes: int   # logical payload per participating device
    wire_bytes: int      # per-device ICI traffic (ring convention)
    op_idx: Optional[int]
    op_type: str
    var: str
    intentional: bool
    reason: str

    def to_dict(self) -> dict:
        return {"kind": self.kind, "axes": list(self.axes),
                "group": self.group,
                "payload_bytes": int(self.payload_bytes),
                "wire_bytes": int(self.wire_bytes),
                "op_idx": self.op_idx, "op_type": self.op_type,
                "var": self.var, "intentional": self.intentional,
                "reason": self.reason}


@dataclass
class CommReport:
    collectives: List[Collective] = field(default_factory=list)
    axis_sizes: Dict[str, int] = field(default_factory=dict)

    @property
    def total_bytes(self) -> int:
        """Per-device wire bytes across every collective (the roofline
        comm leg)."""
        return sum(c.wire_bytes for c in self.collectives)

    @property
    def flagged(self) -> List[Collective]:
        return [c for c in self.collectives if not c.intentional]

    @property
    def by_kind(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for c in self.collectives:
            out[c.kind] = out.get(c.kind, 0) + c.wire_bytes
        return out

    def to_dict(self) -> dict:
        return {"axis_sizes": dict(self.axis_sizes),
                "total_wire_bytes": int(self.total_bytes),
                "by_kind": {k: int(v) for k, v in self.by_kind.items()},
                "flagged": len(self.flagged),
                "collectives": [c.to_dict() for c in self.collectives]}


# ---------------------------------------------------------------------------
# sharding-spec algebra
# ---------------------------------------------------------------------------
# A spec is a tuple (one entry per dim) of frozensets of mesh-axis names;
# the empty set means replicated on that dim. Only axes present in the
# mesh with size > 1 survive normalization — spec_for in the lowering
# drops absent axes the same way.

Spec = Tuple[frozenset, ...]


def _normalize(sharding, rank: int, sizes: Dict[str, int]) -> Spec:
    dims: List[frozenset] = []
    spec = sharding or ()
    for d in range(rank):
        entry = spec[d] if d < len(spec) else None
        if entry is None:
            dims.append(frozenset())
            continue
        axes = entry if isinstance(entry, (list, tuple)) else (entry,)
        dims.append(frozenset(a for a in axes
                              if int(sizes.get(a, 1)) > 1))
    return tuple(dims)


def _replicated(rank: int) -> Spec:
    return tuple(frozenset() for _ in range(rank))


def _is_sharded(spec: Optional[Spec]) -> bool:
    return bool(spec) and any(spec)


def _factor(axes, sizes: Dict[str, int]) -> int:
    f = 1
    for a in axes:
        f *= int(sizes.get(a, 1))
    return f


def _spec_factor(spec: Optional[Spec], sizes: Dict[str, int]) -> int:
    if not spec:
        return 1
    f = 1
    for axes in spec:
        f *= _factor(axes, sizes)
    return f


# rank-preserving ops a sharded activation flows through untouched —
# the same alphabet the transpiler's Megatron trace follows
_ELEMENTWISE_THROUGH = frozenset({
    "elementwise_add", "elementwise_sub", "elementwise_mul",
    "elementwise_div", "elementwise_max", "elementwise_min",
    "scale", "cast", "dropout", "relu", "gelu", "tanh", "sigmoid",
    "swish", "relu6", "leaky_relu", "elu", "softsign", "softplus",
    "square", "exp", "log", "clip", "layer_norm", "batch_norm",
    "rms_norm", "rotary_embedding",
})

#: ops with no data movement / no sharding consequence
_IGNORED = frozenset({
    "feed", "fetch", "shape", "increment", "assign", "fill_constant",
    AUTODIFF_OP, "step_health",
})

_MATMUL_TYPES = ("mul", "matmul")


class _Audit:
    def __init__(self, program: Program, sizes: Dict[str, int], batch: int):
        self.program = program
        self.block = program.global_block
        self.sizes = {k: int(v) for k, v in sizes.items()}
        self.batch = batch
        self.amp = program.amp_dtype
        self.out: List[Collective] = []
        self.spec: Dict[str, Spec] = {}

    # -- helpers ----------------------------------------------------------
    def nbytes(self, name: str) -> int:
        v = self.block.var(name)
        return _prod(_shape(self.block, name, self.batch)) \
            * device_nbytes(v, self.amp)

    def local_bytes(self, name: str) -> int:
        """Bytes of the per-device shard under the propagated spec."""
        return self.nbytes(name) // max(
            1, _spec_factor(self.spec.get(name), self.sizes))

    def get_spec(self, name: str) -> Spec:
        s = self.spec.get(name)
        if s is not None:
            return s
        try:
            v = self.block.var(name)
        except KeyError:
            return ()
        s = _normalize(getattr(v, "sharding", None), len(v.shape or ()),
                       self.sizes)
        self.spec[name] = s
        return s

    def emit(self, kind: str, axes, payload: int, *, op_idx, op_type, var,
             intentional: bool, reason: str):
        axes = tuple(sorted(set(axes)))
        n = _factor(axes, self.sizes)
        if n <= 1 or payload <= 0:
            return
        if kind == "all_reduce":
            wire = 2 * (n - 1) * payload // n
        elif kind in ("all_gather", "reduce_scatter", "all_to_all"):
            wire = (n - 1) * payload // n
        elif kind == "ppermute":
            # ring rotation: the per-step shard forwards n-1 times
            wire = (n - 1) * payload
        else:
            wire = payload
        self.out.append(Collective(kind, axes, n, int(payload), int(wire),
                                   op_idx, op_type, var, intentional,
                                   reason))

    # -- per-op transfer functions ----------------------------------------
    def _matmul(self, i, op):
        x_name = op.inputs["X"][0]
        y_name = op.inputs["Y"][0]
        out_name = op.outputs["Out" if "Out" in op.outputs else "Output"][0]
        x_spec = self.get_spec(x_name)
        y_spec = self.get_spec(y_name)
        x_shape = _shape(self.block, x_name, self.batch)
        y_shape = _shape(self.block, y_name, self.batch)
        if op.type == "mul":
            xn = (op.attrs or {}).get("x_num_col_dims", 1)
            yn = (op.attrs or {}).get("y_num_col_dims", 1)
            x_contract = frozenset().union(*x_spec[xn:]) if x_spec[xn:] \
                else frozenset()
            y_contract = frozenset().union(*y_spec[:yn]) if y_spec[:yn] \
                else frozenset()
            out_lead = x_spec[:xn]
            y_out = y_spec[yn:]
        else:  # matmul: [..., m, k] x [..., k, n]
            tx = bool((op.attrs or {}).get("transpose_X"))
            ty = bool((op.attrs or {}).get("transpose_Y"))
            x_contract = x_spec[-2 if tx else -1] if x_spec else frozenset()
            y_contract = y_spec[-1 if ty else -2] if len(y_spec) >= 2 \
                else frozenset()
            out_lead = x_spec[:-1] if x_spec else ()
            y_out = (y_spec[-2 if ty else -1],) if y_spec else (frozenset(),)
        out_rank = len(self.block.var(out_name).shape or ())
        out_spec = list(out_lead) + list(y_out)
        out_spec = (tuple(out_spec[:out_rank])
                    + tuple(frozenset() for _ in
                            range(out_rank - len(out_spec))))

        contract_axes = x_contract | y_contract
        if contract_axes:
            # a sharded contraction dim -> per-device partial products +
            # an all-reduce of the output. Intentional when the operands'
            # contraction shardings AGREE (the Megatron column->row
            # pairing, or a weight whose activation stayed replicated);
            # when they name DIFFERENT axes GSPMD must first all-gather
            # one operand.
            if x_contract and y_contract and x_contract != y_contract:
                self.emit("all_gather", x_contract, self.nbytes(x_name),
                          op_idx=i, op_type=op.type, var=x_name,
                          intentional=False,
                          reason=f"contraction dims of {x_name!r} and "
                                 f"{y_name!r} are sharded over different "
                                 f"axes ({sorted(x_contract)} vs "
                                 f"{sorted(y_contract)}) — one operand is "
                                 "gathered before the matmul")
            out_bytes = self.nbytes(out_name) // max(
                1, _spec_factor(tuple(out_spec), self.sizes))
            self.emit("all_reduce", contract_axes, out_bytes, op_idx=i,
                      op_type=op.type, var=out_name, intentional=True,
                      reason="partial-sum reduction of a contraction over "
                             f"sharded axes {sorted(contract_axes)} "
                             "(row-parallel matmul)")
        self.spec[out_name] = tuple(out_spec)

    def _lookup(self, i, op):
        w_name = op.inputs["W"][0]
        ids_name = op.inputs["Ids"][0]
        out_name = op.outputs["Out"][0]
        w_spec = self.get_spec(w_name)
        vocab_axes = w_spec[0] if w_spec else frozenset()
        ids_spec = self.get_spec(ids_name)
        out_rank = len(self.block.var(out_name).shape or ())
        out_spec = list(ids_spec)[:out_rank - 1]
        out_spec += [frozenset()] * (out_rank - len(out_spec))
        if vocab_axes:
            # vocab-sharded table: masked local gather + all-reduce of the
            # gathered rows across the vocab shards
            out_bytes = self.nbytes(out_name) // max(
                1, _spec_factor(tuple(out_spec), self.sizes))
            self.emit("all_reduce", vocab_axes, out_bytes, op_idx=i,
                      op_type=op.type, var=out_name, intentional=True,
                      reason="vocab-sharded embedding combine over "
                             f"{sorted(vocab_axes)}")
        self.spec[out_name] = tuple(out_spec)

    def _attention(self, i, op):
        q_name = op.inputs["Q"][0]
        out_name = op.outputs["Out"][0]
        q_spec = self.get_spec(q_name)
        sp_mode = (op.attrs or {}).get("sp_mode") or "none"
        seq_axes = q_spec[1] if len(q_spec) > 1 else frozenset()
        kv_names = [op.inputs[s][0] for s in ("K", "V") if op.inputs.get(s)]
        if sp_mode in ("ring", "ulysses") and seq_axes:
            kv_local = sum(self.local_bytes(n) for n in kv_names)
            if sp_mode == "ring":
                # K/V shards rotate the full ring: each device forwards
                # every other shard once (payload = one per-step shard)
                self.emit("ppermute", seq_axes, kv_local, op_idx=i,
                          op_type=op.type, var=q_name, intentional=True,
                          reason="ring attention K/V rotation over "
                                 f"{sorted(seq_axes)}")
            else:
                # Ulysses: q,k,v reshard seq->heads, out reshards back
                moved = (self.local_bytes(q_name) * 2
                         + sum(self.local_bytes(n) for n in kv_names))
                self.emit("all_to_all", seq_axes, moved, op_idx=i,
                          op_type=op.type, var=q_name, intentional=True,
                          reason="Ulysses seq<->heads reshard over "
                                 f"{sorted(seq_axes)}")
        elif seq_axes:
            # sequence-sharded K/V consumed by a NON-sp attention op:
            # every device needs the full sequence — GSPMD gathers it
            for n in kv_names or [q_name]:
                self.emit("all_gather", seq_axes, self.nbytes(n), op_idx=i,
                          op_type=op.type, var=n, intentional=False,
                          reason=f"attention consumes sequence-sharded "
                                 f"{n!r} without an sp rewrite (sp_mode="
                                 f"{sp_mode!r}) — the full sequence is "
                                 "gathered every step")
        self.spec[out_name] = q_spec

    def _default(self, i, op):
        """No sharding rule. Leading-dim (batch/sequence) sharding flows
        through — unknown ops are overwhelmingly per-element along those
        dims — but a sharded LAST dim (the feature/vocab axis an op
        mixes) forces GSPMD to materialize the full value: the accidental
        all-gather. The classic: a column-parallel logits projection
        feeding softmax_with_cross_entropy."""
        ref_name, ref_spec, ref_shape = None, (), ()
        for name in op.input_names():
            spec = self.get_spec(name)
            if len(spec) > 1 and spec[-1]:
                axes = spec[-1]
                self.emit("all_gather", axes, self.nbytes(name), op_idx=i,
                          op_type=op.type, var=name, intentional=False,
                          reason=f"op {op.type!r} has no sharding rule for "
                                 f"{name!r} sharded over {sorted(axes)} on "
                                 "its last dim — GSPMD gathers the full "
                                 "tensor every step")
            if ref_name is None and _is_sharded(spec) and self._has(name):
                ref_name, ref_spec = name, spec
                ref_shape = _shape(self.block, name, self.batch)
        for n in op.output_names():
            if not self._has(n):
                continue
            out_shape = _shape(self.block, n, self.batch)
            spec = []
            for d in range(len(out_shape)):
                keep = (d < len(ref_spec) - 1 and d < len(ref_shape)
                        and ref_shape[d] == out_shape[d]
                        and d < len(out_shape) - 1)
                spec.append(ref_spec[d] if keep else frozenset())
            self.spec[n] = tuple(spec)

    def _elementwise(self, i, op):
        in_names = list(op.input_names())
        specs = [self.get_spec(n) for n in in_names]
        ref = next((s for s in specs if _is_sharded(s)), None)
        if ref is not None:
            for n, s in zip(in_names, specs):
                if not _is_sharded(s) or s == ref or len(s) != len(ref):
                    continue
                # two operands sharded differently on the same dims: one
                # is resharded (gathered) to match the other
                diff = [d for d in range(len(s))
                        if s[d] and ref[d] and s[d] != ref[d]]
                if diff:
                    axes = frozenset().union(*(s[d] for d in diff))
                    self.emit("all_gather", axes, self.nbytes(n), op_idx=i,
                              op_type=op.type, var=n, intentional=False,
                              reason=f"operands of {op.type!r} are sharded "
                                     "over different axes on dim(s) "
                                     f"{diff} — {n!r} is resharded")
        for n in op.output_names():
            self.spec[n] = ref if ref is not None else \
                (specs[0] if specs else ())

    def _reshape(self, op):
        """Shape motion keeps the sharding of the leading dims whose
        sizes survive unchanged (the [B, S, ...] head of the transformer
        reshape chains — exactly what GSPMD propagates through a
        bitcast); anything past the first resized dim is forgotten."""
        src = op.inputs.get("X", [None])[0]
        src_spec = self.get_spec(src) if src else ()
        src_shape = _shape(self.block, src, self.batch) if src \
            and self._has(src) else ()
        for n in op.output_names():
            if not self._has(n):
                continue
            out_shape = _shape(self.block, n, self.batch)
            spec = []
            for d in range(len(out_shape)):
                if (d < len(src_shape) and d < len(src_spec)
                        and src_shape[d] == out_shape[d]):
                    spec.append(src_spec[d])
                else:
                    spec.extend([frozenset()]
                                * (len(out_shape) - len(spec)))
                    break
            self.spec[n] = tuple(spec)

    def _transpose(self, op):
        src = op.inputs.get("X", [None])[0]
        src_spec = self.get_spec(src) if src else ()
        perm = (op.attrs or {}).get("axis") or (op.attrs or {}).get("perm")
        for n in op.output_names():
            if not self._has(n):
                continue
            rank = len(self.block.var(n).shape or ())
            if perm and len(perm) == len(src_spec) == rank:
                self.spec[n] = tuple(src_spec[int(p)] for p in perm)
            else:
                self.spec[n] = _replicated(rank)

    def _has(self, name) -> bool:
        try:
            self.block.var(name)
            return True
        except KeyError:
            return False

    # -- gradient sync -----------------------------------------------------
    def _grad_sync(self, bwd_idx: int, zero: bool):
        dp = int(self.sizes.get("dp", 1))
        if dp <= 1:
            return
        bop = self.block.ops[bwd_idx]
        for p in bop.attrs.get("params", ()):
            if not self._has(p):
                continue
            v = self.block.var(p)
            # grads shard like their parameter (tp slices stay local);
            # the dp axis is what the sync reduces over
            local = _prod(_shape(self.block, p, self.batch)) \
                * dtype_nbytes(v.dtype)
            local //= max(1, _spec_factor(self.get_spec(p), self.sizes))
            if zero:
                self.emit("reduce_scatter", ("dp",), local, op_idx=bwd_idx,
                          op_type=AUTODIFF_OP, var=p, intentional=True,
                          reason="ZeRO gradient reduce-scatter over dp")
                self.emit("all_gather", ("dp",), local, op_idx=bwd_idx,
                          op_type=AUTODIFF_OP, var=p, intentional=True,
                          reason="ZeRO updated-shard all-gather over dp")
            else:
                self.emit("all_reduce", ("dp",), local, op_idx=bwd_idx,
                          op_type=AUTODIFF_OP, var=p, intentional=True,
                          reason="data-parallel gradient sync")

    # -- the walk ----------------------------------------------------------
    def run(self, zero: bool) -> CommReport:
        ops = self.block.ops
        bwd_idx = next((i for i, o in enumerate(ops)
                        if o.type == AUTODIFF_OP), None)
        fwd_stop = bwd_idx if bwd_idx is not None else len(ops)
        fwd_psums: List[Collective] = []
        for i in range(fwd_stop):
            op = ops[i]
            if op.type in _IGNORED:
                continue
            before = len(self.out)
            if op.type in _MATMUL_TYPES:
                self._matmul(i, op)
            elif op.type == "lookup_table":
                self._lookup(i, op)
            elif op.type == "scaled_dot_product_attention":
                self._attention(i, op)
            elif op.type in _ELEMENTWISE_THROUGH:
                self._elementwise(i, op)
            elif op.type in RESHAPE_ALIAS_OPS:
                self._reshape(op)
            elif op.type in ("transpose", "transpose2"):
                self._transpose(op)
            else:
                self._default(i, op)
            fwd_psums.extend(c for c in self.out[before:]
                             if c.intentional and c.kind == "all_reduce"
                             and c.op_type in _MATMUL_TYPES)
        if bwd_idx is not None:
            # each forward partial-sum has a mirrored backward reduction:
            # the row-parallel matmul's dX is computed locally, but the
            # paired column-parallel matmul's dX is a partial sum over the
            # same axes (Megatron's g/f conjugate pair)
            for c in fwd_psums:
                op = ops[c.op_idx]
                x_name = op.inputs["X"][0]
                self.emit("all_reduce", c.axes, self.local_bytes(x_name),
                          op_idx=c.op_idx, op_type=op.type + "_grad",
                          var=x_name, intentional=True,
                          reason="backward partial-sum of dX (mirror of "
                                 "the forward row-parallel reduction)")
            self._grad_sync(bwd_idx, zero)
        report = CommReport(self.out, dict(self.sizes))
        return report


def audit_collectives(program: Optional[Program] = None, mesh=None,
                      batch: int = 1, zero: bool = False) -> CommReport:
    """Statically enumerate the collectives one step of block 0 implies
    on `mesh` (a jax Mesh or {axis: size} dict; purely host-side — no
    devices are touched, so auditing an 8-way mesh from a laptop works).

    zero=True prices ZeRO-style gradient sync (reduce-scatter +
    all-gather) instead of plain dp all-reduce
    (ParallelExecutor ReduceStrategy.Reduce).
    """
    program = program or default_main_program()
    sizes = mesh_axis_sizes(mesh) if mesh is not None else {}
    return _Audit(program, sizes, batch).run(zero)


# ---------------------------------------------------------------------------
# reduction-algorithm synthesis: ring vs tree vs hierarchical
# ---------------------------------------------------------------------------

#: per-hop launch latency, the term that makes small collectives
#: latency-bound (where tree beats ring). ICI is the on-board fabric;
#: DCI hops cross the data-center network.
ICI_HOP_LATENCY_S = 1e-6
DCI_HOP_LATENCY_S = 25e-6

#: collective kinds a tree schedule implements (a ring rotation or an
#: all-to-all shuffle has no tree form)
_TREE_KINDS = frozenset({"all_reduce", "all_gather", "reduce_scatter"})


def group_host_split(sizes: Dict[str, int], axes: Sequence[str],
                     chips_per_host: int) -> Tuple[int, int]:
    """(intra, inter): how a collective group over `axes` splits across
    hosts — `intra` members share a host, `inter` hosts participate
    (intra x inter = group size). Computed by enumerating the member ids
    of the group containing device 0 under the row-major mesh layout
    (the same id arithmetic as distributed.axis_spans_hosts, made exact
    for multi-axis groups). A ragged split — members per host uneven —
    conservatively reports (1, n): everything priced at the slow tier.
    """
    names = list(sizes)
    sz = [int(sizes[a]) for a in names]
    ids = [0]
    for a in axes:
        if a not in names or int(sizes[a]) <= 1:
            continue
        i = names.index(a)
        stride = 1
        for s in sz[i + 1:]:
            stride *= s
        ids = [b + j * stride for b in ids for j in range(sz[i])]
    n = max(1, len(ids))
    cph = max(1, int(chips_per_host))
    by_host: Dict[int, int] = {}
    for d in ids:
        by_host[d // cph] = by_host.get(d // cph, 0) + 1
    intra = by_host.get(0, 1)
    if len(set(by_host.values())) != 1 or n % intra:
        return 1, n
    return intra, n // intra


def _ring_steps(kind: str, n: int) -> int:
    return 2 * (n - 1) if kind == "all_reduce" else (n - 1)


def per_dispatch_overhead_s(calibration=None) -> float:
    """The fitted per-dispatch launch+sync constant a collective pays
    ON TOP of the wire/latency formulas below — 0.0 uncalibrated (the
    pre-calibration numbers, exactly). One place defines it so the
    planner's scan-resident ppermute leg (hops x this — the PR-15 rank-
    gate gap: a pipeline pays it once per scan TICK, which the pure
    byte model cannot see) and collective_time_s price the same
    constant."""
    if calibration is None:
        return 0.0
    return float(calibration.dispatch_overhead_s)


def collective_time_s(c: Collective, algo: str, sizes: Dict[str, int],
                      topology, calibration=None) -> Optional[float]:
    """Predicted seconds for `c` under `algo` on `topology` (duck-typed:
    needs ici_bandwidth_gbps() / dci_gbps / chips_per_host — a
    parallel/mesh.py Topology). Returns None when the algorithm has no
    implementation for this collective (tree rotation, hierarchical on a
    single-host group) — the chooser skips it. Pure host-side math.

    A Calibration adds its fitted per-dispatch overhead ONCE per
    collective — a constant addend across algorithms, so the chooser's
    argmin (and therefore every recorded plan's algorithm column) is
    identical calibrated or raw; only the priced total moves."""
    intra, inter = group_host_split(sizes, c.axes, topology.chips_per_host)
    crosses = inter > 1
    ici = float(topology.ici_bandwidth_gbps()) * 1e9
    dci = float(topology.dci_gbps) * 1e9
    n = max(1, c.group)
    payload = float(c.payload_bytes)
    overhead = per_dispatch_overhead_s(calibration)
    # a flat schedule on a spanning group is throttled by its slowest
    # link: every hop pays the DCI tier
    bw, lat = (dci, DCI_HOP_LATENCY_S) if crosses \
        else (ici, ICI_HOP_LATENCY_S)
    if algo == "ring":
        return c.wire_bytes / bw + _ring_steps(c.kind, n) * lat + overhead
    if algo == "tree":
        if c.kind not in _TREE_KINDS:
            return None
        depth = max(1, math.ceil(math.log2(n)))
        trips = 2 if c.kind == "all_reduce" else 1
        return trips * (payload / bw + depth * lat) + overhead
    if algo == "hierarchical":
        # ICI reduce-scatter -> DCI ring over the 1/intra shard -> ICI
        # all-gather; only meaningful for spanning reduction groups with
        # an intra-host part to scatter over
        if not crosses or intra <= 1 or c.kind not in _TREE_KINDS:
            return None
        shard = payload / intra
        t_ici = (intra - 1) * ((payload / intra) / ici + ICI_HOP_LATENCY_S)
        t_dci = _ring_steps(c.kind, inter) * (
            shard / inter / dci + DCI_HOP_LATENCY_S)
        if c.kind == "all_reduce":
            t_ici *= 2  # reduce-scatter in, all-gather out
        return t_ici + t_dci + overhead
    raise ValueError(f"unknown collective algorithm {algo!r} "
                     f"(know {list(ALGORITHMS)})")


def choose_algorithm(c: Collective, sizes: Dict[str, int], topology,
                     force: Optional[str] = None,
                     calibration=None) -> Tuple[str, float, bool]:
    """(algorithm, predicted seconds, crosses_hosts) for one collective:
    the cheapest applicable algorithm, or `force` where applicable
    (falling back to ring — ring implements everything). Ties break
    toward ring, the fabric's default convention."""
    _, inter = group_host_split(sizes, c.axes, topology.chips_per_host)
    crosses = inter > 1
    if force is not None:
        t = collective_time_s(c, force, sizes, topology,
                              calibration=calibration)
        if t is None:
            force = "ring"
            t = collective_time_s(c, "ring", sizes, topology,
                                  calibration=calibration)
        return force, float(t), crosses
    best = ("ring", collective_time_s(c, "ring", sizes, topology,
                                      calibration=calibration))
    for algo in ("tree", "hierarchical"):
        t = collective_time_s(c, algo, sizes, topology,
                              calibration=calibration)
        if t is not None and t < best[1]:
            best = (algo, t)
    return best[0], float(best[1]), crosses


def choose_algorithms(collectives: Sequence[Collective],
                      sizes: Dict[str, int], topology,
                      force: Optional[str] = None,
                      calibration=None
                      ) -> Tuple[float, List[dict]]:
    """Per-collective algorithm choice over a whole audit: returns
    (total predicted comm seconds, the algorithm table) — the planner's
    comm leg and the plan artifact's `collectives` record. Deterministic
    (rescore_plan must reproduce the search's choice exactly — and the
    calibrated overhead is a constant per collective, so the choice
    itself never depends on whether a calibration was applied)."""
    total = 0.0
    table: List[dict] = []
    for c in collectives:
        algo, t, crosses = choose_algorithm(c, sizes, topology, force,
                                            calibration=calibration)
        total += t
        table.append({
            "kind": c.kind, "op_type": c.op_type, "var": c.var,
            "axes": list(c.axes), "group": int(c.group),
            "payload_bytes": int(c.payload_bytes),
            "wire_bytes": int(c.wire_bytes),
            "algorithm": algo, "t_ms": t * 1e3,
            "crosses_hosts": bool(crosses),
        })
    return total, table


# ---------------------------------------------------------------------------
# the verifier pass
# ---------------------------------------------------------------------------

@verifier_pass("collective-audit")
def _check_collectives(program: Program, ctx) -> List[Diagnostic]:
    """Flag accidental resharding (an all-gather no user asked for) as
    warnings. Runs only when the caller supplied a concrete mesh — the
    ParallelExecutor pre-pass and the transpiler post-condition gate do;
    without axis sizes there is nothing to audit."""
    if not ctx.axis_sizes:
        return []
    try:
        report = audit_collectives(program, ctx.axis_sizes)
    except (KeyError, IndexError):
        # un-inferable shapes (hand-built op stream): the shape passes
        # report those; the audit has nothing sound to say
        return []
    diags: List[Diagnostic] = []
    for c in report.flagged:
        diags.append(Diagnostic(
            WARNING, "accidental-all-gather",
            f"{c.reason} ({c.wire_bytes / 1e6:.2f} MB on the wire per "
            f"device per step over axes {list(c.axes)})",
            0, c.op_idx, c.op_type, c.var))
    return diags
