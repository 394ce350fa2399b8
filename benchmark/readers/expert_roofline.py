"""The expert matmuls' share (%) of their roofline over the traced
seconds: the least time the chip could take for the decode steps'
(token, expert) pairs and touched experts (`flops_moe.decode_experts`
against `peaks.json`; with 16 rows a step it is the weights' bytes that
bound it) over the device time of the kernels that computed them, by
name in the trace: XLA's grouped matmul (`ragged-dot`) AND
`expert_grouped_matmul`, the name `benchmark/README.md` ("Per-layer
names") reserves for a kernel or scope of the repo's own that computes
the experts' rows, in a decode step or an admission. The seconds of
both stand under the same least seconds, so a tree that moves some or
all of the products to its own kernel is priced on the same work as its
parent (PR 52; until then a kernel under another name left the traced
admissions' `ragged-dot` alone under the steps' whole work: far over
100%, or nothing to read). The traced steps (`kernel.calls`, counted by the
kind while the profiler ran) are priced at the pairs and touched experts
a step of THE TRACED SECONDS' OWN routing counters (`obs["traced"]`,
read by the kind where `kernel.calls` starts and stops counting). Until
PR 47 they were priced at the measured window's mean, which is another
51 seconds' routing: where few rows reach an expert (0.75 a step on the
chip that holds 8 of 128) a handful of sequences' draws moved the share
by a tenth and once over 100. An observation without `traced` (a kind
that does not read them) is priced at the window's mean as before; the
note this prints gives both.

Still under-read: the admissions that fall into the traced seconds run
the same kernels, and their time is in the denominator while their work,
which no counter holds, is not in the numerator: the share under-reads
by their part (some 5% where an admission comes every 20 steps; more
where one long admission falls into three seconds) and cannot over-read
for it. The note's `traced_prefills` says how many fell in.

params:
  match    ALTERNATIVES: an op family (trace_reduce.op_family) counts if
           it contains ANY one of them (one string is a list of one)
  exclude  and none of these (`metadata`: the grouped matmul's index
           bookkeeping, which computes no product)

`None` where the program counts no routing, the trace holds no such
kernel (another form of the expert layer, the parent of the PR that
brought it), or off the chip.
"""

import json

import flops
import flops_moe


def families(op_seconds, match, exclude=()):
    """The op families that computed the experts' products: those that
    contain any one of `match` and none of `exclude`."""
    if isinstance(match, str):
        match = [match]
    return [n for n in op_seconds
            if any(m in n for m in match)
            and not any(x in n for x in exclude)]


def read(ctx, match, exclude=()):
    red, obs = ctx.get("reduced"), ctx["obs"]
    model, kernel = obs.get("model"), obs.get("kernel") or {}
    if (not red or not model or not kernel.get("calls")
            or not obs.get("moe_layer_steps")
            or ctx["device"]["platform"] != "tpu"):
        return None
    names = families(red["op_seconds"], match, exclude)
    seconds = sum(red["op_seconds"][n] for n in names)
    if not seconds:
        return None
    peaks = flops.peaks(ctx["device"]["kind"])

    def least_of(counts):
        """Least seconds of `kernel.calls` steps at `counts`' pairs and
        touched experts a step."""
        steps = counts["moe_layer_steps"] / model["n_layers"]
        share = kernel["calls"] / steps
        n_flops, n_bytes = flops_moe.decode_experts(
            assignments=counts["moe_assignments"] * share,
            experts_touched=counts["moe_experts_touched"] * share, **model)
        return flops.least_seconds(n_flops, n_bytes, peaks)

    at_window, bound = least_of(obs)
    traced = obs.get("traced") or {}
    least = at_window
    if traced.get("moe_layer_steps"):
        least, bound = least_of(traced)
    print(json.dumps({"roofline": names, "steps": kernel["calls"],
                      "kernel_s": seconds, "least_s": least,
                      "least_s_at_window_mean": at_window,
                      "traced_prefills": traced.get("prefills"),
                      "traced_prefill_tokens": traced.get("prefill_tokens"),
                      "bound": bound}), flush=True)
    return 100.0 * least / seconds
