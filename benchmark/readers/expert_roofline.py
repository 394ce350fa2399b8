"""The expert matmuls' share (%) of their roofline over the traced
seconds: the least time the chip could take for the decode steps'
(token, expert) pairs and touched experts (`flops_moe.decode_experts`
against `peaks.json`; with 16 rows a step it is the weights' bytes that
bound it) over the device time of the kernels that computed them, by
name in the trace. The routing counters cover the measured window, the
trace the seconds after it on the same backlog, so the traced steps
(`kernel.calls`, counted by the kind while the profiler ran) are priced
at the window's mean pairs and touched experts a step. The admissions
that fall into the traced seconds run the same kernels, and their time
is in the denominator while their work, which no counter holds, is not
in the numerator: the share under-reads by their part (some 5% where an
admission comes every 20 steps) and cannot over-read for it.

params:
  match, exclude  substrings the op family (trace_reduce.op_family) must
                  and must not contain

`None` where the program counts no routing, the trace holds no such
kernel (another form of the expert layer, the parent of the PR that
brought it), or off the chip.
"""

import json

import flops
import flops_moe


def read(ctx, match, exclude=()):
    red, obs = ctx.get("reduced"), ctx["obs"]
    model, kernel = obs.get("model"), obs.get("kernel") or {}
    if (not red or not model or not kernel.get("calls")
            or not obs.get("moe_layer_steps")
            or ctx["device"]["platform"] != "tpu"):
        return None
    names = [n for n in red["op_seconds"]
             if all(m in n for m in match)
             and not any(x in n for x in exclude)]
    seconds = sum(red["op_seconds"][n] for n in names)
    if not seconds:
        return None
    steps = obs["moe_layer_steps"] / model["n_layers"]
    share = kernel["calls"] / steps      # traced steps over the window's
    n_flops, n_bytes = flops_moe.decode_experts(
        assignments=obs["moe_assignments"] * share,
        experts_touched=obs["moe_experts_touched"] * share, **model)
    least, bound = flops.least_seconds(
        n_flops, n_bytes, flops.peaks(ctx["device"]["kind"]))
    print(json.dumps({"roofline": names, "steps": kernel["calls"],
                      "kernel_s": seconds, "least_s": least,
                      "bound": bound}), flush=True)
    return 100.0 * least / seconds
