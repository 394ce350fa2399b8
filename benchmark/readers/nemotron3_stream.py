"""What a decode step of a Nemotron-H cut moves (`flops_nemotron3.py`),
from the window's counters, and its routed experts' roofline:

  which = "state"    the Mamba-2 states (a matrix a head and three rows of
                     x, B, C a slot and layer), read and written once a
                     live slot, layer and step, over the step's least bytes
                     (weights + states + the attention layers' live rows)
  which = "kv"       the attention layers' live pages, the same way
  which = "weights"  `weight_stream`'s number on this model's weights:
                     the least seconds the chip needs to read, once a
                     step, the weights the window's steps had to read, at
                     `peaks.json`'s bytes/s, over the seconds the host
                     waited for the steps (`step_wait`); an earlier output
                     line gives the weights' share of the least bytes
  which = "state_slots"  that every live slot's state moved once a state
                     layer in every step: `state_slot_steps` over slots x
                     the model's state layers x the steps DISPATCHED; 100
                     with every slot live. The counter ticks when a step
                     is dispatched and `decode_steps` when it is emitted,
                     so a window's two ends can differ by the steps in
                     flight: the steps are the larger of `decode_steps`
                     and the whole steps the counter itself holds
  which = "experts"  `expert_roofline` with an expert priced at its TWO
                     matrices and the pairs that fell on HELD experts
                     (`moe_held_pairs`): the least time for the traced
                     steps' pairs and touched experts, at the traced
                     seconds' own counters, over the device time of the
                     kernels whose family contains ANY one of `match`
                     (`ragged-dot`, or `expert_grouped_matmul`, the name
                     reserved for a kernel of the repo's own:
                     `expert_roofline.families`); the admissions that
                     fall into the traced seconds run the same kernels
                     uncounted, so it under-reads by their part and
                     cannot over-read

`None` where the program counts no state or no held pairs (the parent of
the PR that brought the configuration), has no phase records ("weights")
or no such kernel in its trace ("experts"), or off the chip.
"""

import json

import flops
import flops_nemotron3
from readers import expert_roofline, phase_ms


def _experts(ctx, match, exclude):
    red, obs = ctx.get("reduced"), ctx["obs"]
    model, kernel = obs["model"], obs.get("kernel") or {}
    traced = obs.get("traced") or {}
    counts = traced if traced.get("moe_layer_steps") else obs
    if not red or not kernel.get("calls") or "moe_held_pairs" not in counts:
        return None
    names = expert_roofline.families(red["op_seconds"], match, exclude)
    seconds = sum(red["op_seconds"][n] for n in names)
    if not seconds:
        return None
    steps = counts["moe_layer_steps"] / model["expert_layers"]
    share = kernel["calls"] / steps
    n_flops, n_bytes = flops_nemotron3.decode_experts(
        assignments=counts["moe_held_pairs"] * share,
        experts_touched=counts["moe_experts_touched"] * share, **model)
    least, bound = flops.least_seconds(
        n_flops, n_bytes, flops.peaks(ctx["device"]["kind"]))
    print(json.dumps({"roofline": names, "steps": kernel["calls"],
                      "kernel_s": seconds, "least_s": least,
                      "traced_prefills": traced.get("prefills"),
                      "bound": bound}), flush=True)
    return 100.0 * least / seconds


def read(ctx, which, match=(), exclude=()):
    obs = ctx["obs"]
    model = obs.get("model")
    if (not model or "ssm_heads" not in model
            or not obs.get("moe_layer_steps")
            or "state_slot_steps" not in obs
            or not obs.get("decode_steps")
            or ctx["device"]["platform"] != "tpu"):
        return None
    if which == "experts":
        return _experts(ctx, match, exclude)
    if which == "state_slots":
        slots = obs.get("slots_capacity_sum", 0) // obs["decode_steps"]
        a_step = slots * model["state_layers"]
        if not a_step:
            return None
        steps = max(obs["decode_steps"],
                    -(-obs["state_slot_steps"] // a_step))
        return 100.0 * obs["state_slot_steps"] / (a_step * steps)
    parts = flops_nemotron3.decode_bytes(
        moe_experts_touched=obs["moe_experts_touched"],
        moe_layer_steps=obs["moe_layer_steps"],
        paged_live_pages=obs.get("paged_live_pages", 0),
        state_slot_steps=obs["state_slot_steps"],
        block_size=obs["block_size"], **model)
    least = sum(parts.values())
    if which != "weights":
        if which == "state":
            print(json.dumps({"least_bytes_a_step": {
                k: v / obs["decode_steps"] for k, v in parts.items()}}),
                flush=True)
        return 100.0 * parts[which] / least
    wait_ms = phase_ms.read(ctx, ["step_wait"], per="decode_steps")
    if not wait_ms:
        return None
    wait_s = wait_ms / 1000.0 * obs["decode_steps"]
    peak = flops.peaks(ctx["device"]["kind"])
    print(json.dumps({"weights_share_of_least_bytes":
                      100.0 * parts["weights"] / least}), flush=True)
    return 100.0 * parts["weights"] / peak["hbm_bytes_per_s"] / wait_s
