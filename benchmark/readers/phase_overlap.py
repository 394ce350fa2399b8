"""Seconds of one kind of phase record, whole or only where they lie
inside records of other kinds, over an observation of the same window.
params: of (names: the records summed), under (names; left out: the
whole of every `of` record; []: the part inside NO other phase of the
category), per (observation name), scale (default 1), cat (default
"decode").

A record is the interval `[t_end - seconds, t_end]` on
`time.perf_counter()`, from the program's trace ring
(`paddle_tpu.obs.trace.phase_records()`). It is made for the one phase
that is not the host's own: the decode engine's `device_idle` (from the
return of a wait on the newest dispatch to the return of the next call
that dispatches), which overlaps whatever phases the host went through
meanwhile. `under` puts its seconds down to those: the part under
`step_dispatch`, under `step_emit` + `step_prep`, under `admit`, and
with `[]` the part the host spent in no phase at all. A record of the
category that is not named in `of` is one of "the other phases", so a
phase a later change adds is never silently counted as none. Parts
under disjoint phases, with the part under none, add to the whole.

The `of` records counted are those that ENDED inside the measured
window, whole, as `phase_ms` counts its phases; the window is
`[t_open, t_open + obs["window_s"]]` with `t_open = T_START +
obs["setup_s"]` (see `phase_ms`). The records they are intersected
with are all the ring has: the phase an interval ends in closes after
it.

Nothing to read is `None`, never a partial sum: no `T_START`, no count
to divide by, a program that leaves no record named in `of` (the parent
of the PR that brought the phase), or a ring that has dropped part of
the window (its oldest phase record is younger than the window's
opening).
"""

import sys

import numpy as np


def _covered(intervals, starts, ends):
    """Seconds of each `[starts[i], ends[i]]` that the union of
    `intervals` covers."""
    if not len(intervals):
        return np.zeros(len(starts))
    a, b = np.asarray(sorted(intervals)).T
    # the union as disjoint pieces: a piece starts where an interval
    # starts past everything before it
    reach = np.maximum.accumulate(b)
    first = np.concatenate(([True], a[1:] > reach[:-1]))
    lo, hi = a[first], np.maximum.reduceat(b, np.flatnonzero(first))
    cum = np.concatenate(([0.0], np.cumsum(hi - lo)))

    def upto(t):
        # seconds of the union at or before each t
        k = np.searchsorted(lo, t, side="right")
        j = np.maximum(k - 1, 0)       # the last piece that starts by t
        inside = np.clip(t - lo[j], 0.0, (hi - lo)[j])
        return np.where(k > 0, cum[j] + inside, 0.0)

    return upto(np.asarray(ends)) - upto(np.asarray(starts))


def read(ctx, of, per, under=None, scale=1.0, cat="decode"):
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
    of = set(of)
    mine = [(t_end - s, t_end) for c, name, t_end, s in records
            if c == cat and name in of]
    if not mine:
        return None
    inside = np.asarray([iv for iv in mine
                         if t_open <= iv[1] <= t_close]).reshape(-1, 2)
    starts, ends = inside[:, 0], inside[:, 1]
    whole = ends - starts
    if under is None:
        seconds = whole.sum()
    else:
        others = [(t_end - s, t_end) for c, name, t_end, s in records
                  if c == cat and name not in of
                  and (not under or name in under)]
        covered = _covered(others, starts, ends)
        seconds = (covered if under else whole - covered).sum()
    return float(seconds) / obs[per] * scale
