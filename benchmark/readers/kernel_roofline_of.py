"""`kernel_roofline` for a kernel whose operations and bytes live in
another module than `flops.py` (which the add-only rule keeps as it is):
a kernel's share (%) of its roofline over the traced window, the least
time the chip could take for the calls it made over the kernel's device
time in the trace. Which peak bounds it goes on an earlier output line.

params:
  module          the module beside `flops.py` that holds `cost`
  match, exclude  substrings the op family (trace_reduce.op_family) must
                  and must not contain
  cost            the function of `module` that gives (flops, bytes)
  shape           the observation that holds its arguments, all calls of
                  the traced window together

`None` where the trace holds no such kernel (the parent of the PR that
brought it), the observation is empty, or off the chip.
"""

import importlib
import json

import flops


def read(ctx, module, match, cost, shape, exclude=()):
    red = ctx.get("reduced")
    if not red or ctx["device"]["platform"] != "tpu":
        return None
    names = [n for n in red["op_seconds"]
             if all(m in n for m in match)
             and not any(x in n for x in exclude)]
    seconds = sum(red["op_seconds"][n] for n in names)
    args = dict(ctx["obs"].get(shape) or {})
    if not seconds or not args:
        return None
    n_flops, n_bytes = getattr(importlib.import_module(module), cost)(**args)
    least, bound = flops.least_seconds(
        n_flops, n_bytes, flops.peaks(ctx["device"]["kind"]))
    print(json.dumps({"roofline": names,
                      "events": sum(red["op_calls"][n] for n in names),
                      "kernel_s": seconds, "least_s": least,
                      "bound": bound}), flush=True)
    return 100.0 * least / seconds
