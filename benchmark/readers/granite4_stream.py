"""What a decode step of granite-4.0-h-micro moves (`flops_granite4.py`),
from the window's counters; a dense model, so nothing here reads a
routing counter:

  which = "state"    the 36 Mamba-2 layers' states (a [64, 128] matrix a
                     head and three rows of x, B, C a slot and layer),
                     read and written once a live slot, layer and step,
                     over the step's least bytes (weights as served +
                     states + the attention layers' live rows)
  which = "kv"       the attention layers' live pages, the same way
  which = "weights"  `weight_stream`'s number on this model's weights AS
                     SERVED (bfloat16 matrices, float32 small
                     parameters): the least seconds the chip needs to
                     read them once a step at `peaks.json`'s bytes/s,
                     over the seconds the host waited for the steps
                     (`step_wait`); an earlier output line gives the
                     weights' share of the least bytes
  which = "state_slots"  that every live slot's state moved once a state
                     layer in every step: `state_slot_steps` over slots x
                     the model's state layers x the steps DISPATCHED; 100
                     with every slot live (`nemotron3_stream` has why the
                     steps are the larger of two counts)

`None` where the program counts no state (the parent of the PR that
brought the configuration), has no phase records ("weights"), or off the
chip.
"""

import json

import flops
import flops_granite4
from readers import phase_ms


def read(ctx, which):
    obs = ctx["obs"]
    model = obs.get("model")
    if (not model or "ssm_heads" not in model
            or "state_slot_steps" not in obs
            or not obs.get("decode_steps")
            or ctx["device"]["platform"] != "tpu"):
        return None
    if which == "state_slots":
        slots = obs.get("slots_capacity_sum", 0) // obs["decode_steps"]
        a_step = slots * model["state_layers"]
        if not a_step:
            return None
        steps = max(obs["decode_steps"],
                    -(-obs["state_slot_steps"] // a_step))
        return 100.0 * obs["state_slot_steps"] / (a_step * steps)
    parts = flops_granite4.decode_bytes(
        decode_steps=obs["decode_steps"],
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
