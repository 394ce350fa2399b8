"""The shares of a decode step's least bytes of a decoder-hybrid-decoder
(`flops_phi4flash.py`), from the window's counters (context for the
phases, none a roofline):

  which = "shared"   the full pool's rows, as the layer that writes it and
                     the cross layers that do not own it read them, over
                     the step's least bytes (weights + those rows + the
                     windows' rows + the scans' states); an earlier output
                     line gives the readers' part of it alone
  which = "window"   the rows inside the slots' windows, the same way
  which = "state"    the scans' states, read and written once a live slot,
                     layer and step, the same way
  which = "weights"  `weight_stream`'s number on this model's weights:
                     the least seconds the chip needs to read, once a
                     step, the f32 weights, at `peaks.json`'s bytes/s,
                     over the seconds the host waited for the steps
                     (`step_wait`); an earlier output line gives the
                     weights' share of the least bytes

  which = "state_slots"  that every live slot's state moved once a state
                     layer in every step: `state_slot_steps` over slots x
                     the model's state layers x steps; 100 with every slot
                     live

`None` where the program counts no rows read from a pool by layers that
do not own it (the parent of the PR that brought the configuration) or,
for "weights", has no phase records.
"""

import json

import flops
import flops_phi4flash
from readers import phase_ms


def read(ctx, which):
    obs = ctx["obs"]
    model = obs.get("model")
    if (not model or "pool_rows_read_readers" not in obs
            or "window_rows_read" not in obs
            or "state_slot_steps" not in obs or "ssm_inner" not in model
            or not obs.get("decode_steps")
            or ctx["device"]["platform"] != "tpu"):
        return None
    parts = flops_phi4flash.decode_bytes(
        pool_rows_read_writer=obs["pool_rows_read_writer"],
        pool_rows_read_readers=obs["pool_rows_read_readers"],
        window_rows_read=obs["window_rows_read"],
        state_slot_steps=obs["state_slot_steps"],
        decode_steps=obs["decode_steps"], **model)
    if which == "state_slots":
        capacity = obs.get("slots_capacity_sum", 0) * model["state_layers"]
        return 100.0 * obs["state_slot_steps"] / capacity \
            if capacity else None
    readers = parts.pop("readers")
    least = sum(parts.values())
    if which != "weights":
        if which == "shared":
            print(json.dumps({"least_bytes_a_step": {
                k: v / obs["decode_steps"] for k, v in parts.items()},
                "readers_share": 100.0 * readers / least}), flush=True)
        return 100.0 * parts[which] / least
    wait_ms = phase_ms.read(ctx, ["step_wait"], per="decode_steps")
    if not wait_ms:
        return None
    wait_s = wait_ms / 1000.0 * obs["decode_steps"]
    peak = flops.peaks(ctx["device"]["kind"])
    print(json.dumps({"weights_share_of_least_bytes":
                      100.0 * parts["weights"] / least}), flush=True)
    return 100.0 * parts["weights"] / peak["hbm_bytes_per_s"] / wait_s
