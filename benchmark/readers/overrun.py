"""Share of the measured window, in percent, that records of one kind
in the program's trace ring cover: the stall sentinel's `stall` records
(a `PhaseTimer` span found open far beyond its phase's usual length,
with what its thread was doing meanwhile) or the collector's `host` /
`gc` records (a collection of a millisecond or more). params: cat, name
(`fnmatch` patterns of the record's category and name; name defaults to
every name).

A record is the interval `[t_end - seconds, t_end]` on
`time.perf_counter()`, from `paddle_tpu.obs.trace.attr_records()`
(`(cat, name, t_end, seconds, attrs)`). Counted are the records that
ENDED inside the window, whole, as `phase_ms` counts its phases, and the seconds are
those their union covers (a stalled admission holds a stalled wait:
two records, one stretch of the window); the window is `[t_open,
t_open + obs["window_s"]]` with `t_open = T_START + obs["setup_s"]`
(see `phase_ms`).

No record is 0.0 only where the sentinel says it watched:
`trace.sentinel_since()` is when it started, and a time before the
window's opening is the proof. Without it the answer is `None`: a
program that has no sentinel (the parent of the PR that brought it), or
one whose sentinel is stopped or was started inside the window. `None`
too without
`T_START` or the window's clocks, or where the ring has dropped part of
the window (its oldest phase record is younger than the window's
opening).

Each record counted is printed as one JSON line of its own, and so is
each that ended AFTER the window's close (the traced seconds: the
profiler's start and stop are in them), marked `"counted": false` and
left out of the share: what the thread was doing is the point of the
record, and the result line has no room for it.
"""

import json
import sys
from fnmatch import fnmatchcase

def read(ctx, cat, name="*"):
    obs = ctx["obs"]
    t_start = getattr(sys.modules.get("__main__"), "T_START", None)
    if t_start is None or "setup_s" not in obs or not obs.get("window_s"):
        return None
    try:
        from paddle_tpu.obs import trace
        since = trace.sentinel_since()
        oldest = trace.phase_records()[:1]
        records = trace.attr_records()
    except (ImportError, AttributeError):
        return None
    t_open = t_start + obs["setup_s"]
    t_close = t_open + obs["window_s"]
    if since is None or since > t_open \
            or not oldest or oldest[0][2] > t_open:
        return None
    counted = []
    for c, n, t_end, seconds, attrs in records:
        if t_end < t_open \
                or not (fnmatchcase(c, cat) and fnmatchcase(n, name)):
            continue
        inside = t_end <= t_close
        print(json.dumps({"record": [c, n], "counted": inside,
                          "ended_s_into_window": t_end - t_open,
                          "seconds": seconds, "attrs": attrs}),
              flush=True)
        if inside:
            counted.append((t_end - seconds, t_end))
    covered, reach = 0.0, float("-inf")
    for start, end in sorted(counted):
        covered += max(0.0, end - max(start, reach))
        reach = max(reach, end)
    return covered / obs["window_s"] * 100.0
