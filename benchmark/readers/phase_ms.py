"""Milliseconds of the program's own phase clocks per step, prefill or
call: the seconds of the named `PhaseTimer` phases that ENDED inside the
measured window, summed, over an observation counted over the same
window. params: phases (names), per (observation name), cat (default
"decode").

The phases are read from the program's trace ring
(`paddle_tpu.obs.trace.phase_records()`), which records every finished
phase whether or not tracing was asked for, with its end on
`time.perf_counter()`. The window on that clock is `[t_open, t_open +
obs["window_s"]]` with `t_open = T_START + obs["setup_s"]`: `run.py`
sets `T_START` and is the process's `__main__`, and the kinds define
`setup_s` as `t_open - t_start`. The warm-up (whose admissions build
executables) and the traced seconds after the window are never in the
sum.

Nothing to read is `None`, never a partial sum: a program without phase
records (the parent of the PR that brought them), no count to divide
by, or a ring that has dropped part of the window (its oldest phase
record is younger than the window's opening).
"""

import sys


def read(ctx, phases, per, cat="decode"):
    obs = ctx["obs"]
    t_start = getattr(sys.modules.get("__main__"), "T_START", None)
    if (t_start is None or not obs.get(per) or "setup_s" not in obs
            or "window_s" not in obs):
        return None
    try:
        from paddle_tpu.obs import trace
        records = trace.phase_records()
    except (ImportError, AttributeError):
        return None
    t_open = t_start + obs["setup_s"]
    t_close = t_open + obs["window_s"]
    if not records or records[0][2] > t_open:
        return None
    wanted = set(phases)
    seconds = sum(s for c, name, t_end, s in records
                  if c == cat and name in wanted
                  and t_open <= t_end <= t_close)
    return seconds / obs[per] * 1000.0
