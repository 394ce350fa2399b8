"""In-graph training guardrails: step-health flag + guarded weight update.

PR 2 made crashes survivable and the async hot path (lazy fetches,
device-resident state) made the step loop free of host syncs — which also
means a single NaN/Inf batch poisons the weights *on device* and the
damage only surfaces (if ever) at a log step or checkpoint. The guard
closes that hole INSIDE the compiled step, so it composes with donation,
with `run_loop`'s device-side scan, and with whatever update sharding the
ParallelExecutor picks (cf. cross-replica weight-update sharding, arxiv
2004.13336: a host-side pre-check would see per-replica shards; an
in-graph scalar is global by construction):

1. **step-health flag** — ONE fused scalar per step::

       healthy = isfinite(loss) ∧ isfinite(‖grads‖₂) ∧ ‖grads‖₂ ≤ PT_GUARD_MAX_GNORM

   computed by a `step_health` op that `optimizer.minimize` appends when
   PT_GUARD is armed (or `instrument(program)` on demand). The executor
   appends it to the fetch list under ``lazy=True``, so detection
   piggybacks on the existing LazyFetch materialization — zero extra
   host syncs.

2. **guarded update** — the lowering rewrites the step's state output to
   ``new_state = where(healthy, updated_state, old_state)``
   (core/lowering.py). An anomalous batch is *skipped* for free: params,
   optimizer accumulators, bn statistics — every persistable — keep
   their pre-step value, and donation stays ON (unlike the
   FLAGS_check_nan_inf/checkify debug path, which must disable it).

3. **recovery policy** (PT_GUARD=skip|rollback|raise, consumed by the
   Trainer at log/checkpoint boundaries): `skip` relies on (2) and logs;
   `raise` raises StepAnomalyError after PT_GUARD_PATIENCE consecutive
   anomalies; `rollback` restores the newest *verified* checkpoint
   serial (PR 2 manifests) and resumes bit-exactly.

The norm is measured on the RAW backward gradients (the autodiff op's
`@GRAD` bindings, before clip/regularization rewrites) — a
clip_by_global_norm would otherwise mask the very explosions the guard
exists to catch — and is divided by the autodiff `loss_scale`, so AMP
loss scaling does not shift the PT_GUARD_MAX_GNORM threshold. Host-RAM
embedding tables apply their rows-grads host-side; the Trainer gates
those applies on the same health flag (trainer._apply_host_grads), which
costs nothing extra because that path already materializes per step.

Deterministic fault sites `nan_loss` / `nan_grad` (resilience/faults.py)
poison the step in-graph via a tiny int32 fault-code feed the executor
injects per dispatch, so every recovery path is provable under seeds.
"""

from __future__ import annotations

import os
import threading
import warnings
from typing import Optional

import jax.numpy as jnp

__all__ = [
    "HEALTH_VAR", "FAULT_FEED", "HEALTH_OP",
    "GuardConfigError", "StepAnomalyError", "RollbackSignal",
    "policy", "patience", "max_gnorm", "fault_code", "fault_feed",
    "instrument", "maybe_instrument", "is_instrumented",
    "assert_instrumented",
]

#: reserved name of the in-graph health scalar (the `step_health` output)
HEALTH_VAR = "__step_health__"
#: reserved feed name of the per-step fault-injection code (int32:
#: 0 = none, 1 = nan_loss, 2 = nan_grad)
FAULT_FEED = "__guard_fault__"
HEALTH_OP = "step_health"

POLICY_ENV = "PT_GUARD"
PATIENCE_ENV = "PT_GUARD_PATIENCE"
MAX_GNORM_ENV = "PT_GUARD_MAX_GNORM"

POLICIES = ("skip", "rollback", "raise")
_OFF = ("", "0", "off", "none", "false")


class GuardConfigError(RuntimeError):
    """Malformed or inconsistent PT_GUARD* configuration."""


class StepAnomalyError(RuntimeError):
    """PT_GUARD_PATIENCE consecutive anomalous steps under PT_GUARD=raise
    (or an exhausted/unavailable rollback under PT_GUARD=rollback)."""


class RollbackSignal(Exception):
    """Internal control flow: the Trainer's health drain requests a
    rollback to the newest verified checkpoint. Never escapes
    Trainer.train — deliberately NOT a RuntimeError so generic error
    handlers don't swallow it."""

    def __init__(self, epoch: int, step: int, streak: int):
        self.epoch, self.step, self.streak = epoch, step, streak
        super().__init__(
            f"{streak} consecutive anomalous steps "
            f"(last: epoch {epoch} step {step})")


def policy() -> Optional[str]:
    """The armed recovery policy, or None when the guard is off."""
    raw = os.environ.get(POLICY_ENV, "").strip().lower()
    if raw in _OFF:
        return None
    if raw not in POLICIES:
        raise GuardConfigError(
            f"{POLICY_ENV}={raw!r}: unknown policy "
            f"(want {' | '.join(POLICIES)}, or unset/0 to disable)")
    return raw


def patience() -> int:
    """Consecutive anomalous steps before raise/rollback act (default 3)."""
    raw = os.environ.get(PATIENCE_ENV, "").strip()
    if not raw:
        return 3
    try:
        k = int(raw)
    except ValueError as e:
        raise GuardConfigError(f"{PATIENCE_ENV}={raw!r}: not an int") from e
    if k < 1:
        raise GuardConfigError(f"{PATIENCE_ENV} must be >= 1, got {k}")
    return k


def max_gnorm() -> float:
    """Global-grad-norm ceiling baked into the compiled health flag
    (default inf: only non-finiteness trips the guard). Read at trace
    time; the executor keys its compile cache on the value, so changing
    it mid-process recompiles rather than silently keeping the old
    threshold."""
    raw = os.environ.get(MAX_GNORM_ENV, "").strip()
    if not raw:
        return float("inf")
    try:
        g = float(raw)
    except ValueError as e:
        raise GuardConfigError(f"{MAX_GNORM_ENV}={raw!r}: not a float") from e
    if not g > 0:
        raise GuardConfigError(f"{MAX_GNORM_ENV} must be > 0, got {g}")
    return g


# -- fault-code feed (deterministic in-graph injection) ----------------------

def fault_code() -> int:
    """One draw of the in-graph fault sites for one step. BOTH sites are
    hit on every guarded dispatch (their hit counters advance in step
    lockstep, so `nan_loss@N` means "step N of this process"); nan_loss
    wins when both fire on the same step."""
    from . import faults
    code = 1 if faults.fire("nan_loss") is not None else 0
    if faults.fire("nan_grad") is not None and code == 0:
        code = 2
    return code


def fault_feed(n_steps: Optional[int] = None):
    """The int32 fault-code array fed as FAULT_FEED: a scalar for
    Executor.run (and fake-feed run_loop windows — one draw per window),
    or [n_steps] for per_step_feeds windows (one draw per step)."""
    if n_steps is None:
        return jnp.int32(fault_code())
    return jnp.asarray([fault_code() for _ in range(n_steps)], jnp.int32)


# -- program instrumentation -------------------------------------------------

def is_instrumented(program) -> bool:
    return any(op.type == HEALTH_OP for op in program.global_block.ops)


def assert_instrumented(program) -> None:
    if not is_instrumented(program):
        raise GuardConfigError(
            "guarded execution requested but the program has no "
            f"{HEALTH_OP!r} op — set {POLICY_ENV} before building it "
            "(optimizer.minimize instruments the program) or call "
            "resilience.guard.instrument(program) explicitly")


def instrument(program=None):
    """Append the `step_health` op (idempotent): Health <- Loss + the raw
    `@GRAD` bindings named by the program's autodiff boundary. Called by
    `optimizer.minimize` when PT_GUARD is armed; callable directly on
    any program that has been through append_backward. Host-table rows-grads merged into the autodiff op
    AFTER instrumentation are excluded from the norm (they are gated
    host-side by the Trainer instead)."""
    from ..core.program import default_main_program
    from ..core.lowering import AUTODIFF_OP
    program = program if program is not None else default_main_program()
    block = program.global_block
    bop = next((op for op in block.ops if op.type == AUTODIFF_OP), None)
    if bop is None:
        raise GuardConfigError(
            "cannot instrument a program without an autodiff boundary — "
            "run optimizer.minimize / append_backward first")
    existing = next((op for op in block.ops if op.type == HEALTH_OP), None)
    if existing is not None:
        existing.inputs["Loss"] = [bop.attrs["loss"]]
        existing.inputs["Grads"] = list(bop.attrs["grad_names"])
        program.invalidate_cache()
        return program
    hv = block.create_var(HEALTH_VAR, shape=(), dtype="bool")
    hv.stop_gradient = True
    op = block.append_op(HEALTH_OP,
                         {"Loss": [bop.attrs["loss"]],
                          "Grads": list(bop.attrs["grad_names"])},
                         {"Health": [hv]}, {})
    # position matters, not just dataflow: the optimizer suffix REBINDS
    # the @GRAD names in place (clip.py writes {'X': grad} -> {'Out':
    # grad}), so an end-of-block health op would measure post-clip
    # values — the ceiling masked by exactly the clipping it exists to
    # see through. Move it directly after the autodiff boundary, where
    # the names still hold the raw backward gradients.
    block.ops.remove(op)
    block.ops.insert(block.ops.index(bop) + 1, op)
    program.invalidate_cache()
    return program


def maybe_instrument(program=None):
    """Instrument iff PT_GUARD is armed (the optimizer.minimize hook)."""
    if policy() is None:
        return program
    return instrument(program)


# -- the step_health op ------------------------------------------------------

_checkify_warned = threading.Event()


def warn_checkify_conflict() -> None:
    """Exactly-one-instrumentation rule: FLAGS_check_nan_inf (checkify —
    names the generating primitive, disables donation) and the guard
    must not both rewrite the step. The guard wins: it is the production
    path; checkify is the debug tool. Warn once per process."""
    if not _checkify_warned.is_set():
        _checkify_warned.set()
        warnings.warn(
            "both FLAGS_check_nan_inf and the step guard are enabled; the "
            "guard takes precedence and checkify instrumentation is "
            "skipped for guarded runs (use FLAGS_check_nan_inf alone to "
            "debug WHICH primitive produced the NaN; see "
            "docs/resilience.md)", stacklevel=3)


def _register_op() -> None:
    from ..core.registry import register_op
    from ..core.selected_rows import RowSparseGrad
    from ..core.lowering import AUTODIFF_OP

    def _health_shape(op, block):
        out = block.var(op.output("Health")[0])
        out.shape, out.dtype = (), "bool"

    @register_op(HEALTH_OP, infer_shape=_health_shape, supports_sparse=True)
    def step_health(ctx, ins, attrs):
        loss = ins["Loss"][0]
        ssq = jnp.float32(0.0)
        for g in ins.get("Grads", ()):
            v = g.values if isinstance(g, RowSparseGrad) else g
            v = v.astype(jnp.float32)
            ssq = ssq + jnp.sum(v * v)
        # grads carry the autodiff loss_scale (AMP); unscale so the
        # PT_GUARD_MAX_GNORM threshold is in true-gradient units
        scale = 1.0
        prog = getattr(ctx, "program", None)
        if prog is not None:
            bop = next((op for op in prog.global_block.ops
                        if op.type == AUTODIFF_OP), None)
            if bop is not None:
                scale = float(bop.attrs.get("loss_scale", 1.0))
        gnorm = jnp.sqrt(ssq) / jnp.float32(scale)
        healthy = (jnp.all(jnp.isfinite(loss))
                   & jnp.isfinite(gnorm)
                   & (gnorm <= jnp.float32(max_gnorm())))
        return {"Health": [healthy]}


_register_op()
