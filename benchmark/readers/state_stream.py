"""Two shares of a decode step of a model with state layers (gated short
convolutions) beside attention layers, from the window's counters and
`flops_lfm2.py` (context for the phases, neither a roofline):

  which = "weights"  `weight_stream`'s number on this model's weights:
                     the least seconds the chip needs to read, once a
                     step, the weights the window's steps had to read, at
                     `peaks.json`'s bytes/s, over the seconds the host
                     waited for the steps (`step_wait`); the device also
                     works while `step_dispatch` runs, so nothing holds it
                     under 100% but the size of what it leaves out
  which = "kv"       the K/V rows' bytes (every live page of the
                     attention layers; a conv layer has none) over those
                     plus the weights' bytes: how much of a step's least
                     bytes the cache is

`None` where the program counts no routing or no state (the parent of
the PR that brought the configuration) or has no phase records.
"""

import flops
import flops_lfm2
from readers import phase_ms


def read(ctx, which):
    obs = ctx["obs"]
    model = obs.get("model")
    if (not model or not obs.get("moe_layer_steps")
            or "moe_experts_touched" not in obs
            or "state_slot_steps" not in obs or "conv_taps" not in model
            or ctx["device"]["platform"] != "tpu"):
        return None
    weights = flops_lfm2.decode_weight_bytes(
        experts_touched=obs["moe_experts_touched"],
        layer_steps=obs["moe_layer_steps"], **model)
    if which == "kv":
        kv = flops_lfm2.decode_kv_bytes(
            paged_live_pages=obs.get("paged_live_pages", 0),
            block_size=obs["block_size"], **model)
        return 100.0 * kv / (kv + weights)
    wait_ms = phase_ms.read(ctx, ["step_wait"], per="decode_steps")
    if not wait_ms:
        return None
    wait_s = wait_ms / 1000.0 * obs["decode_steps"]
    peak = flops.peaks(ctx["device"]["kind"])
    return 100.0 * weights / peak["hbm_bytes_per_s"] / wait_s
