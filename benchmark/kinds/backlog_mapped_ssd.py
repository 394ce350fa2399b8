"""Traffic kind `backlog_mapped_ssd`: `backlog_mapped_state` as it is (its
check: the checked sequence admitted into a USED slot at a length that is
not its bucket's end, then teacher-forced steps, against the stateless
reference on the program's routes) for a model whose state layers are
updated by a kernel of their own and that holds a share of its experts.
Made the way `backlog_mapped_state.py` was: it sets names of
`backlog_mapped` (and one of `_serve`) for its own run, a process running
one cell, and edits no kind that exists. ROADMAP D12 folds the seven into
one.

Observations: those of `backlog_mapped_state`, plus `moe_held_pairs`
over the window (and over the traced seconds) and
`kernel.live_slot_steps`: live slots summed over the traced steps, times
the model's state layers: the calls the state update's kernel made.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from kinds import (_serve, backlog_mapped, backlog_mapped_limits,
                   backlog_mapped_state)

HELD_COUNTERS = ("moe_held_pairs",)


def counters(dec) -> Dict:
    """`backlog_mapped_state.counters`' keys, and the pairs that fell on
    held experts, from the same one snapshot."""
    snap = dec.metrics_snapshot()
    keys = (_serve.COUNTERS + ("slots_used_sum", "slots_capacity_sum")
            + backlog_mapped.MOE_COUNTERS
            + backlog_mapped_limits.PAGED_COUNTERS
            + backlog_mapped_state.STATE_COUNTERS + HELD_COUNTERS)
    return {k: snap[k] for k in keys if k in snap}


class LiveSpans(_serve.ProgramSpans):
    """`ProgramSpans`, counting also the live slots of the traced
    steps."""

    last = None    # the run's one instance, for `run` below

    def __init__(self, model):
        import jax
        step = model.decode_step            # before it is wrapped
        super().__init__(model)
        self.live_slots = 0

        def traced_step(token_ids, context_lens, *tables):
            if self.counting:
                self.context_tokens += int(np.sum(context_lens))
                self.decode_calls += 1
                self.live_slots += int(np.count_nonzero(context_lens))
            with jax.profiler.TraceAnnotation("program/decode_step"):
                return step(token_ids, context_lens, *tables)

        model.decode_step = traced_step
        LiveSpans.last = self


def run(cell, args, device, t_start):
    _serve.ProgramSpans = LiveSpans
    # `backlog_mapped_state.run` hands `backlog_mapped` the `counters`
    # of its module at the time of the call: this kind's
    backlog_mapped_state.counters = counters
    out = backlog_mapped_state.run(cell, args, device, t_start)
    obs = out["obs"]
    if LiveSpans.last is not None and obs.get("kernel"):
        obs["kernel"]["live_slot_steps"] = LiveSpans.last.live_slots \
            * int(obs["model"]["state_layers"])
    return out
