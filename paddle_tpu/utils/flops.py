"""Analytic FLOP counting from the program IR — shim over analysis/cost.py.

≙ the role of the reference's benchmark flop accounting (hand-written
per-model constants in benchmark/fluid) — but derived from the compiled
program's op list + inferred shapes, so a model variant (e.g. the
SE-ResNeXt test net whose grouped stage is twice the standard width)
cannot silently run against the wrong denominator.

Since PR 7 the per-op formulas live in `analysis/cost.py` (one cost
surface for FLOPs, HBM bytes, liveness, and the roofline); this module
is the stable MFU-convention API over it:

* `program_forward_flops` / `program_train_flops` keep the MATMUL-CLASS
  (MXU) count — 2 flops/MAC, the standard MFU numerator. Elementwise /
  normalization / attention-softmax work is VECTOR (VPU) flops: real
  hardware work but never MFU numerator, so the historical "undercount"
  was a convention, not a bug — pass include_vector=True (or read
  `program_cost(...)` directly) to see it. The cost model also covers
  ops this module historically priced at zero (paged_attention, pool,
  lookup_table traffic, optimizer updates).
* Parity with the pre-PR-7 counter is pinned in
  tests/test_cost_model.py (and the closed-form checks in
  tests/test_flops_counter.py keep passing unchanged).

Ops inside control-flow sub-blocks are NOT counted (trip counts are
dynamic); the RNN benches use explicit per-config formulas instead.
"""

from __future__ import annotations

from typing import Optional

from ..analysis.cost import program_cost
from ..core.program import Program

__all__ = ["program_forward_flops", "program_train_flops"]


def program_forward_flops(program: Optional[Program] = None, batch: int = 1,
                          include_vector: bool = False) -> int:
    """Forward flops of block 0 for one step at `batch` (dynamic -1 dims
    substitute `batch`). Default: matmul-class (MXU) flops only — the
    MFU-numerator convention; include_vector=True adds elementwise /
    normalization / attention-softmax (VPU) work."""
    fwd = program_cost(program, batch=batch).forward
    return fwd.flops if include_vector else fwd.mxu_flops


def program_train_flops(program: Optional[Program] = None, batch: int = 1,
                        mult: float = 3.0) -> int:
    """Training-step flops: forward x `mult` (bwd ≈ 2x fwd; remat adds
    the policy's recompute on top — callers adjust mult)."""
    return int(program_forward_flops(program, batch) * mult)
