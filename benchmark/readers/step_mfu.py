"""The whole step's share (%) of the chip's peak in a serve cell, over
the measured window: the serve cells' `train_mfu`. 100 x (the least
seconds the chip could take for the work the window did) / `window_s`,
the window `serve_tokens_per_s` is taken over, from the program's
counters and the host's clock: no trace, no kernel's name, so no change
of implementation silences it. A kernel's roofline says how near ONE
kernel runs to its bytes' rate while it runs; this says how much of the
window the chip NEEDED, whatever ran, and is what bounds a claim over a
kernel that a PR took off the path.

The work, each part through `flops.least_seconds` against `peaks.json`
as every roofline is:

  the decode steps  the least bytes they had to move (the architecture's
      `decode_least_bytes`: the weights by the touched-expert counter,
      the cache rows the contexts held, the states of the live slots)
      and their matmuls' operations (2 a weight and live slot, 2 a
      weight of an expert and routed pair);
  the admissions    each a pass of its own over the weights
      (`pass_weight_bytes`: everything outside the routed experts once,
      the routed experts one token must touch) and its prompt tokens'
      operations (2 a weight and token; the head for ONE row).

A true lower bound, so it cannot pass 100: widths are the PUBLISHED ones
of the configuration (`obs["model"]`, never what the program stores: a
program that pads its storage or reads an untouched expert reads LOWER);
experts are the touched ones, rows the live ones. What is left out makes
it smaller, never larger: the embedding rows gathered, an admission's
cache and state writes and its attention over its own rows, a step's
attention operations (a few a byte where the ridge is 240), the routed
experts of a prompt past one token's. `live_rows`: the page counter
counts a slot's last page whole, so a live slot's last page is priced at
the ONE row it must hold.

One caveat, for the PR that changes it: an admission is priced as a
pass of its own because this engine runs it as one (one program at a
time on the device). A program that folds a prompt's rows into the
steps' passes (chunked prefill beside decode) reads the weights once for
both; the `benchmark` issue that goes with it takes the admissions'
weight bytes out of this numerator.

params:
  modules  the module beside `flops.py` that prices an architecture, by
           the configuration's `harness.mapping` ("" where it has none:
           the GPT-2 stack of `flops.py`). A configuration added later
           names its own under `harness.flops` and edits nothing here.

Prints the numerator's parts (seconds of the window) as the stream
readers print theirs. `None` with no decode step in the window, without
the page counter (a kind that does not read it), or off the chip.
"""

import importlib
import json

import flops


def _module(ctx, modules):
    harness = ctx["cell"].config.get("harness") or {}
    name = harness.get("flops") or modules.get(harness.get("mapping", ""))
    return importlib.import_module(name) if name else None


def live_rows(obs):
    """Cache rows the steps' contexts held, a layer, at the least: the
    pages a layer's call had to read (`paged_live_pages`, each slot's
    last one counted whole) with every live slot's last page at one
    row."""
    pages, slots = obs["paged_live_pages"], obs.get("slots_used_sum", 0)
    return (pages - slots) * obs["block_size"] + slots


def read(ctx, modules):
    obs = ctx["obs"]
    model = obs.get("model")
    if (not model or not obs.get("decode_steps") or not obs.get("window_s")
            or "paged_live_pages" not in obs or "block_size" not in obs
            or ctx["device"]["platform"] != "tpu"):
        return None
    module = _module(ctx, modules)
    if module is None:
        return None
    peak = flops.peaks(ctx["device"]["kind"])
    width = float(model.get("dtype_bytes", 4))
    counts = dict(obs, live_rows=live_rows(obs))
    parts = module.decode_least_bytes(counts, **model)
    a_pass = module.pass_weight_bytes(**model)
    # the experts whose rows the steps computed: the pairs that fell on
    # the experts held here where a share is held, every routed pair else
    pairs = obs.get("moe_held_pairs", obs.get("moe_assignments", 0))
    step_flops = 2.0 / width * (
        obs.get("slots_used_sum", 0) * a_pass["always"]
        + pairs * a_pass["expert"])
    steps_s, bound = flops.least_seconds(
        step_flops, sum(parts.values()), peak)
    prefills, tokens = obs.get("prefills", 0), obs.get("prefill_tokens", 0)
    routed = a_pass["routed"] * a_pass["expert"]
    admit_s, admit_bound = flops.least_seconds(
        2.0 / width * (tokens * (a_pass["always"] - a_pass["head"] + routed)
                       + prefills * a_pass["head"]),
        prefills * (a_pass["always"] + routed), peak)
    rate = peak["hbm_bytes_per_s"]
    print(json.dumps({"serve_step_mfu": {
        "window_s": obs["window_s"], "decode_steps": obs["decode_steps"],
        "least_s": {"weights": parts["weights"] / rate,
                    "cache_rows": parts["cache"] / rate,
                    "states": parts["states"] / rate,
                    "admissions": admit_s},
        "least_bytes_a_step": {k: v / obs["decode_steps"]
                               for k, v in parts.items()},
        "steps_bound": bound, "steps_least_s": steps_s,
        "admissions": prefills, "admission_tokens": tokens,
        "admissions_bound": admit_bound}}), flush=True)
    return 100.0 * (steps_s + admit_s) / obs["window_s"]
