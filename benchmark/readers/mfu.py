"""Model FLOP/s utilization (%): the operations the forward and
backward passes need per token (`flops.py`; recomputation not counted)
times tokens per second, over chips times the published bf16 peak. An
end-to-end utilization: not a kernel's roofline share, and blind to
where the time goes.
params: tokens, seconds (observation names)."""

import flops


def read(ctx, tokens, seconds):
    obs = ctx["obs"]
    if not obs.get(seconds) or ctx["device"]["platform"] != "tpu":
        return None
    peak = flops.peaks(ctx["device"]["kind"])["bf16_flops_per_s"]
    rate = obs[tokens] / obs[seconds]
    return 100.0 * obs["flops_per_token"] * rate / (obs["chips"] * peak)
