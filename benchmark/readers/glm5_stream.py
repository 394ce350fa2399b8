"""Shares of a GLM-5 decode step and of its admissions, from the window's
counters and `flops_glm5.py`, or from the reduced trace (context for the
phases and the rooflines, none a roofline):

  which = "cache"    the caches' bytes (every live row's index key and
                     the selected rows' latent rows, a layer) over those
                     plus the weights' bytes: how much of a step's least
                     bytes both pools are. Counters alone.
  which = "weights"  `weight_stream`'s number on this model's weights:
                     the least seconds the chip needs to read, once a
                     step, the weights the window's steps had to read, at
                     `peaks.json`'s bytes/s, over the seconds the host
                     waited for the steps (`step_wait`); the device also
                     works while `step_dispatch` runs, so nothing holds it
                     under 100% but the size of what it leaves out
  which = "scope"    the device seconds, in the traced window, of the op
                     families that contain `match` (a `jax.named_scope`
                     of an admission: the prefill's index scores, its
                     selected attention) over the traced window's BUSY
                     seconds, %: a part of the whole, so under 100; 0
                     where the traced seconds hold no such family (no
                     admission fell into them)

`None` where the program counts no routing or no selected rows (the
parent of the PR that brought the configuration), has no phase records,
or there is no trace.
"""

import flops
import flops_glm5
from readers import phase_ms


def read(ctx, which, match=()):
    obs = ctx["obs"]
    if ctx["device"]["platform"] != "tpu":
        return None
    if which == "scope":
        red = ctx.get("reduced")
        if not red or not red.get("busy_s"):
            return None
        seconds = sum(s for name, s in red["op_seconds"].items()
                      if any(m in name for m in match))
        return 100.0 * seconds / red["busy_s"]
    model = obs.get("model")
    if (not model or not obs.get("moe_layer_steps")
            or "moe_experts_touched" not in obs
            or "sparse_selected_rows" not in obs
            or "q_lora_rank" not in model):
        return None
    weights = flops_glm5.decode_weight_bytes(
        experts_touched=obs["moe_experts_touched"],
        layer_steps=obs["moe_layer_steps"], **model)
    if which == "cache":
        cache = flops_glm5.cache_bytes(
            sparse_live_rows=obs["sparse_live_rows"],
            sparse_selected_rows=obs["sparse_selected_rows"], **model)
        return 100.0 * cache / (cache + weights)
    if which != "weights":
        raise ValueError(f"unknown share {which!r}")
    wait_ms = phase_ms.read(ctx, ["step_wait"], per="decode_steps")
    if not wait_ms:
        return None
    wait_s = wait_ms / 1000.0 * obs["decode_steps"]
    peak = flops.peaks(ctx["device"]["kind"])
    return 100.0 * weights / peak["hbm_bytes_per_s"] / wait_s
