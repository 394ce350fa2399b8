"""A kernel's share (%) of its roofline over the traced window: the
least time the chip could take for the calls it made (the larger of
operations over peak FLOP/s and bytes over peak bytes/s, from
`flops.py` and `peaks.json`) over the kernel's device time in the
trace. Which peak bounds it goes on an earlier output line.

params:
  match, exclude  substrings the op family (trace_reduce.op_family) must
                  and must not contain
  cost            the function of flops.py that gives (flops, bytes)
  shape           the observation that holds its arguments
  per             "call": the shape describes one call, multiplied by
                  `calls_per_event` events found (a backward split into
                  two kernels has calls_per_event 0.5);
                  "window": the shape describes all calls together
"""

import json

import flops


def read(ctx, match, cost, shape, per="call", exclude=(),
         calls_per_event=1.0):
    red = ctx.get("reduced")
    if not red or ctx["device"]["platform"] != "tpu":
        return None
    names = [n for n in red["op_seconds"]
             if all(m in n for m in match)
             and not any(x in n for x in exclude)]
    seconds = sum(red["op_seconds"][n] for n in names)
    events = sum(red["op_calls"][n] for n in names)
    args = dict(ctx["obs"].get(shape) or {})
    if not seconds or not args:
        return None
    if per == "window":
        n_flops, n_bytes = getattr(flops, cost)(**args)
    else:
        one_flops, one_bytes = getattr(flops, cost)(**args)
        n_flops = one_flops * events * calls_per_event
        n_bytes = one_bytes * events * calls_per_event
    least, bound = flops.least_seconds(
        n_flops, n_bytes, flops.peaks(ctx["device"]["kind"]))
    print(json.dumps({"roofline": names, "events": events,
                      "kernel_s": seconds, "least_s": least,
                      "bound": bound}), flush=True)
    return 100.0 * least / seconds
