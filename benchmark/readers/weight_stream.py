"""Weight-streaming share (%) of a decode step's wait, over the measured
window, on the HOST's clock: the least seconds the chip needs to read,
once a step, the weights those steps had to read
(`flops_moe.decode_weight_bytes`, at `peaks.json`'s bytes/s), over the
seconds the host waited for the steps (`step_wait`, the program's own
phase clock). Context for the decode engine's phases, not a kernel's
roofline: the step is dispatched asynchronously, so the device also
works while `step_dispatch` runs, `step_wait` is less than the device's
whole step, and nothing holds this under 100% but the size of what it
leaves out. The kernels' own share, on the device's clock, is
`expert_roofline`.

params: none. Reads the window's `moe_experts_touched` and
`moe_layer_steps` and the configuration's sizes (`model`) from the
observations; `None` where the program counts no routing (a dense
model, the parent of the PR that brought the counters) or has no phase
records.
"""

import flops
import flops_moe
from readers import phase_ms


def read(ctx):
    obs = ctx["obs"]
    model = obs.get("model")
    if (not model or not obs.get("moe_layer_steps")
            or "moe_experts_touched" not in obs
            or ctx["device"]["platform"] != "tpu"):
        return None
    wait_ms = phase_ms.read(ctx, ["step_wait"], per="decode_steps")
    if not wait_ms:
        return None
    wait_s = wait_ms / 1000.0 * obs["decode_steps"]
    nbytes = flops_moe.decode_weight_bytes(
        experts_touched=obs["moe_experts_touched"],
        layer_steps=obs["moe_layer_steps"], **model)
    peak = flops.peaks(ctx["device"]["kind"])
    return 100.0 * nbytes / peak["hbm_bytes_per_s"] / wait_s
