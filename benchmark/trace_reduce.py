"""From a profiler trace to numbers: device busy and idle time, kernel
time by name, and the idle gaps attributed to the host span that covers
them.

Two steps, kept apart so the second can be checked on a small recorded
trace (`fixtures/`, `check_trace_reduce.py`) without a chip:

  load_xplane(path)  -> the neutral form below, read with nothing but
                        JAX (`jax.profiler.ProfileData`)
  reduce(trace, ...) -> the numbers

Neutral form: {"planes": [{"name": str, "lines": [{"name": str,
"events": [[name, start_ns, duration_ns], ...]}]}]}. Device planes are
the ones named `/device:TPU:<n>`; on each, the line `XLA Ops` holds one
event per operation the chip ran. Host spans are the benchmark's own
`jax.profiler.TraceAnnotation`s, found on the host planes by name
(prefix `bench/` or `program/`).
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIXES = ("bench/", "program/")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
MIN_GAP_NS = 20_000    # shorter gaps are the chip's own sequencing


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str) -> Dict:
    """Read an .xplane.pb into the neutral form. Keeps the device
    planes' op lines and, of the host planes, only the benchmark's
    spans: a whole host plane is hundreds of thousands of events."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        is_device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if is_device and line.name != OPS_LINE:
                continue
            events = []
            for ev in line.events:
                if not is_device and not ev.name.startswith(SPAN_PREFIXES):
                    continue
                events.append([ev.name, int(ev.start_ns),
                               int(ev.duration_ns)])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def outline(path: str, samples: int = 6) -> List[Dict]:
    """Planes, lines, event counts and a few event names with their
    stats: what to look at by hand before trusting the reduction."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events = list(line.events)
            out.append({
                "plane": plane.name, "line": line.name,
                "events": len(events),
                "samples": [{"name": e.name, "start_ns": int(e.start_ns),
                             "duration_ns": int(e.duration_ns),
                             "stats": {str(k): str(v)[:80]
                                       for k, v in list(e.stats)[:8]}}
                            for e in events[:samples]]})
    return out


def op_family(name: str) -> str:
    """`%fusion.123 = f32[..] fusion(...)` -> `fusion`;
    `%jvp_scaled_dot_product_attention.12_.8 = ...` ->
    `jvp_scaled_dot_product_attention`; `%fusion.5098.remat` ->
    `fusion.remat`: the operation's name without XLA's numbering, so
    that the same operation sums across layers and keeps its name
    across compiles."""
    head = name.split(" = ")[0].strip().lstrip("%")
    m = re.match(r"^(.*?)((?:\.remat\d*|\.clone)*)$", head)
    base = re.sub(r"[._\d]+$", "", m.group(1)) or m.group(1)
    return base + (".remat" if "remat" in m.group(2) else "")


CONTAINERS = ("while", "conditional", "call")


def leaf_ops(events: List[List]) -> List[List]:
    """Drop control-flow containers: a `while` spans every operation of
    its body, which the line lists too, so counting it would hide the
    gaps inside the loop and double the time by name."""
    return [e for e in events if op_family(e[0]) not in CONTAINERS]


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def device_ops(trace: Dict) -> Dict[int, List[List]]:
    """{device index: [[name, start_ns, duration_ns], ...]}"""
    out = {}
    for plane in trace["planes"]:
        m = DEVICE_PLANE.match(plane["name"])
        if not m:
            continue
        for line in plane["lines"]:
            if line["name"] == OPS_LINE:
                out[int(m.group(1))] = leaf_ops(line["events"])
    return out


def host_spans(trace: Dict) -> List[List]:
    spans = []
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            spans.extend(e for e in line["events"]
                         if e[0].startswith(SPAN_PREFIXES))
    return sorted(spans, key=lambda e: e[1])


def window_of(trace: Dict, span_name: Optional[str]) -> Tuple[int, int]:
    """The traced window: the extent of the span `span_name` if the run
    wrote one, else from the first device op to the last."""
    if span_name:
        hits = [e for e in host_spans(trace) if e[0] == span_name]
        if hits:
            return (min(e[1] for e in hits),
                    max(e[1] + e[2] for e in hits))
    ops = [e for evs in device_ops(trace).values() for e in evs]
    if not ops:
        raise ValueError("no device operation in the trace")
    return min(e[1] for e in ops), max(e[1] + e[2] for e in ops)


def reduce(trace: Dict, window_span: Optional[str] = None,
           top: int = 10) -> Dict:
    """The numbers. Times in seconds.

    busy_s / window_s: union of the op intervals inside the window,
    averaged over the devices that ran anything; the window's length.
    op_seconds / op_calls: device time and event count by op family, on
    the lowest-numbered device.
    collective_exposed_s: time on that device in which a collective ran
    and no other operation did.
    idle_gaps: seconds of device idleness (gaps over MIN_GAP_NS) summed
    by the innermost host span covering each gap's middle.
    """
    lo, hi = window_of(trace, window_span)
    per_device = device_ops(trace)
    if not per_device:
        raise ValueError("no device plane in the trace")
    busy = []
    for evs in per_device.values():
        iv = _clip(_union([(s, s + d) for _, s, d in evs]), lo, hi)
        busy.append(_total(iv))
    first = min(per_device)
    evs = [e for e in per_device[first] if e[1] + e[2] > lo and e[1] < hi]

    op_seconds: Dict[str, float] = {}
    op_calls: Dict[str, int] = {}
    for name, _, dur in evs:
        fam = op_family(name)
        op_seconds[fam] = op_seconds.get(fam, 0.0) + dur / 1e9
        op_calls[fam] = op_calls.get(fam, 0) + 1

    def is_coll(name):
        return any(c in name for c in COLLECTIVES)

    coll = _clip(_union([(s, s + d) for n, s, d in evs if is_coll(n)]),
                 lo, hi)
    rest = _union([(s, s + d) for n, s, d in evs if not is_coll(n)])
    covered = 0
    for s, e in coll:
        covered += _total(_clip(rest, s, e))
    exposed = _total(coll) - covered

    spans = [e for e in host_spans(trace) if e[0] != window_span]
    busy_iv = _clip(_union([(s, s + d) for _, s, d in evs]), lo, hi)
    gaps, cursor = [], lo
    for s, e in busy_iv + [(hi, hi)]:
        if s - cursor >= MIN_GAP_NS:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    idle: Dict[str, float] = {}
    for s, e in gaps:
        mid = (s + e) // 2
        cover = [sp for sp in spans if sp[1] <= mid < sp[1] + sp[2]]
        name = min(cover, key=lambda sp: sp[2])[0] if cover \
            else "(no span)"
        idle[name] = idle.get(name, 0.0) + (e - s) / 1e9

    def ranked(d):
        return sorted(([k, v] for k, v in d.items()),
                      key=lambda kv: -kv[1])[:top]

    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "devices": len(per_device),
        "op_seconds": op_seconds,
        "op_calls": op_calls,
        "collective_s": _total(coll) / 1e9,
        "collective_exposed_s": exposed / 1e9,
        "device_ops": ranked(op_seconds),
        "idle_gaps": ranked(idle),
    }
