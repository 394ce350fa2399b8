"""What a decode step of a MiniCPM-SALA cut moves (`flops_minicpm_sala
.py`), from the window's counters:

  which = "state"    the linear layers' states (a [128, 128] matrix a head
                     and slot), read and written once a live slot, layer
                     and step, over the step's least bytes (weights +
                     states + the sparse layers' chosen rows and pooled
                     keys)
  which = "kv"       the sparse layers' chosen blocks' rows and the pooled
                     keys their choice was scored on, the same way
  which = "weights"  `weight_stream`'s number on this model's weights:
                     the least seconds the chip needs to read, once a
                     step, the weights the window's steps had to read, at
                     `peaks.json`'s bytes/s, over the seconds the host
                     waited for the steps (`step_wait`); an earlier output
                     line gives the weights' share of the least bytes

`None` where the program counts no state or no chosen rows (the parent
of the PR that brought the configuration), has no phase records
("weights"), or off the chip.
"""

import json

import flops
import flops_minicpm_sala
from readers import phase_ms


def read(ctx, which):
    obs = ctx["obs"]
    model = obs.get("model")
    if (not model or "sparse_selected_rows" not in obs
            or "state_slot_steps" not in obs
            or not obs.get("decode_steps")
            or ctx["device"]["platform"] != "tpu"):
        return None
    parts = flops_minicpm_sala.decode_bytes(obs, **model)
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
