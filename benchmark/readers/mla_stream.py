"""What a decode step of a latent-attention model streams, over the
measured window, from the program's counters (`flops_mla.py`).

params:
  what  "latent_share": the latent rows' bytes as a share (%) of the
        least bytes of the window's steps (those rows and the weights
        of `flops_mla.decode_weight_bytes`): how much of a step the
        cache is. From counters alone.
        "weight_wait_share": `weight_stream`'s number on this model's
        weight bytes: the least seconds the chip needs to read them, at
        `peaks.json`'s bytes/s, over the seconds the host waited for
        the steps (`step_wait`). The same caveat: the device also works
        while `step_dispatch` runs, so nothing holds it under 100% but
        the size of what it leaves out.

`None` where the program counts no routing or no pages (the parent of
the PR that brought the counters), or has no phase records.
"""

import flops
import flops_mla
from readers import phase_ms


def read(ctx, what):
    obs = ctx["obs"]
    model = obs.get("model")
    if (not model or not obs.get("moe_layer_steps")
            or "moe_experts_touched" not in obs
            or ctx["device"]["platform"] != "tpu"):
        return None
    weights = flops_mla.decode_weight_bytes(
        experts_touched=obs["moe_experts_touched"],
        layer_steps=obs["moe_layer_steps"], **model)
    if what == "weight_wait_share":
        wait_ms = phase_ms.read(ctx, ["step_wait"], per="decode_steps")
        if not wait_ms:
            return None
        wait_s = wait_ms / 1000.0 * obs["decode_steps"]
        peak = flops.peaks(ctx["device"]["kind"])
        return 100.0 * weights / peak["hbm_bytes_per_s"] / wait_s
    if what != "latent_share":
        raise ValueError(f"unknown share {what!r}")
    if not obs.get("paged_live_pages"):
        return None
    latent = flops_mla.latent_cache_bytes(
        live_pages=obs["paged_live_pages"],
        block_size=ctx["cell"].config["serving"]["block_size"], **model)
    return 100.0 * latent / (latent + weights)
