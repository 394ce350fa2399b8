"""Traffic kind `backlog_mapped_sel_held`: `backlog_mapped_sel` as it is
(its check on the program's routes AND selections, its four limits, its
row counters and traced op families) for a model that also holds a SHARE
of its experts. Made the way `backlog_mapped_ssd.py` was: it sets one name
of `backlog_mapped_sel` for its own run, a process running one cell, and
edits no kind that exists. ROADMAP D12 folds them into one.

Observations: those of `backlog_mapped_sel`, plus `moe_held_pairs` over
the window and over the traced seconds (the pairs that fell on held
experts: what `readers/held_experts.py`, `moe_held_pair_share.*` and
`serve_step_mfu` price a share's products by).
"""

from __future__ import annotations

from typing import Dict

from kinds import (_serve, backlog_mapped, backlog_mapped_limits,
                   backlog_mapped_sel)

HELD_COUNTERS = ("moe_held_pairs",)


def counters(dec) -> Dict:
    """`backlog_mapped_sel.counters`' keys, and the pairs that fell on
    held experts, from the same one snapshot."""
    snap = dec.metrics_snapshot()
    keys = (_serve.COUNTERS + ("slots_used_sum", "slots_capacity_sum")
            + backlog_mapped.MOE_COUNTERS
            + backlog_mapped_limits.PAGED_COUNTERS
            + backlog_mapped_sel.SPARSE_COUNTERS + HELD_COUNTERS)
    return {k: snap[k] for k in keys if k in snap}


def run(cell, args, device, t_start):
    # `backlog_mapped_sel.run` hands `backlog_mapped` the `counters` of
    # its module at the time of the call: this kind's
    backlog_mapped_sel.counters = counters
    return backlog_mapped_sel.run(cell, args, device, t_start)
