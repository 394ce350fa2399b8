"""`kernel_roofline_of` for work that several kernels share by name: an op
family (trace_reduce.op_family) counts if it contains ANY one of `match`
and none of `exclude` (the experts' products run in XLA's grouped matmul,
`ragged-dot`, or in the repo's own, `expert_grouped_matmul`, by a static
plan a shape: `benchmark/README.md`, "Per-layer names"), and the seconds
of all of them stand under the one least time of `cost(**obs[shape])`,
all calls of the traced window together.

params: module, match (alternatives), exclude, cost, shape: as
`kernel_roofline_of`'s. `None` where the trace holds no such kernel, the
observation is empty, or off the chip.
"""

import importlib
import json

import flops
from readers.expert_roofline import families


def read(ctx, module, match, cost, shape, exclude=()):
    red = ctx.get("reduced")
    if not red or ctx["device"]["platform"] != "tpu":
        return None
    names = families(red["op_seconds"], match, exclude)
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
