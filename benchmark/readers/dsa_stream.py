"""`weight_stream`'s number for a model with a sparse-attention indexer
(`flops_dsa.decode_weight_bytes`): the least seconds the chip needs to
read, once a step, the weights the window's decode steps had to read, at
`peaks.json`'s bytes/s, over the seconds the host waited for the steps
(`step_wait`). The same caveat as there: the device also works while
`step_dispatch` runs, so nothing holds it under 100% but the size of what
it leaves out.

params: none. `None` where the program counts no routing (the parent of
the PR that brought the configuration) or has no phase records.
"""

import flops
import flops_dsa
from readers import phase_ms


def read(ctx):
    obs = ctx["obs"]
    model = obs.get("model")
    if (not model or not obs.get("moe_layer_steps")
            or "moe_experts_touched" not in obs
            or "index_heads" not in model
            or ctx["device"]["platform"] != "tpu"):
        return None
    wait_ms = phase_ms.read(ctx, ["step_wait"], per="decode_steps")
    if not wait_ms:
        return None
    wait_s = wait_ms / 1000.0 * obs["decode_steps"]
    nbytes = flops_dsa.decode_weight_bytes(
        experts_touched=obs["moe_experts_touched"],
        layer_steps=obs["moe_layer_steps"], **model)
    peak = flops.peaks(ctx["device"]["kind"])
    return 100.0 * nbytes / peak["hbm_bytes_per_s"] / wait_s
