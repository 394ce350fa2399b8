"""Traffic kind `backlog_mapped_limits`: `backlog_mapped` as it is, with
the three limits of its check taken from the configuration's `harness`
section (`limits`: `row_max`, `rms_max`, `tie_max`, each set from
readings of THAT configuration on the chip and written there with its
reason) where `backlog_mapped.py` holds OLMoE's as module constants, and
with the paged kernel's page counters among the window's counts. A
process runs one cell, so setting that module's names is setting them
for this run alone. ROADMAP D12 folds this file and its two siblings
into one kind.

Observations: those of `backlog_mapped`, plus `paged_live_pages`, the
pages the paged kernel had to read over the window, a layer
(`DecodeMetrics.on_paged_pages`).
"""

from __future__ import annotations

from typing import Dict

from kinds import _serve, backlog_mapped

PAGED_COUNTERS = ("paged_live_pages",)


def counters(dec) -> Dict:
    """`backlog_mapped.counters`, and the page counters, from the same
    one snapshot."""
    snap = dec.metrics_snapshot()
    keys = (_serve.COUNTERS + ("slots_used_sum", "slots_capacity_sum")
            + backlog_mapped.MOE_COUNTERS + PAGED_COUNTERS)
    return {k: snap[k] for k in keys if k in snap}


def run(cell, args, device, t_start):
    limits = cell.config["harness"]["limits"]
    backlog_mapped.ROW_MAX = float(limits["row_max"])
    backlog_mapped.RMS_MAX = float(limits["rms_max"])
    backlog_mapped.TIE_MAX = float(limits["tie_max"])
    backlog_mapped.counters = counters
    return backlog_mapped.run(cell, args, device, t_start)
