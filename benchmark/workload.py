"""The one general traffic generator.

A traffic mix is a data file under `benchmark/traffic/`; this module
turns it and `--seed` into the inputs of a run. The rule every kind
keeps: the seed never changes how much work a run offers, in what order,
nor when it is due. It fills in the token ids (and the weights).

A group is `len(prompt_lens)` requests: each prompt length once, each
output length once, paired by a Latin square (group g pairs prompt i
with output (i + g) mod n), so every group carries exactly the same
prompt tokens and output tokens, and n consecutive groups hold every
pairing once. The order inside each group is shuffled once, from the
traffic file's `order_seed`, and is the same in every run: `--seed`
draws the token ids. (A first design let `--seed` shuffle inside the
groups; the work per group was then fixed but which prefill fell inside
the window was not, and one prefill more or less is over 1% of a window:
PERF.md, PR 24.)
"""

from __future__ import annotations

import math
from typing import Dict, List

import numpy as np


def evenly_spaced(lo: int, hi: int, n: int, multiple: int = 1) -> List[int]:
    """n evenly spaced whole numbers from lo to hi inclusive: the
    quantiles of the uniform range, rounded to a multiple."""
    if n == 1:
        return [int(hi)]
    out = []
    for i in range(n):
        v = lo + (hi - lo) * i / (n - 1)
        out.append(int(round(v / multiple)) * multiple)
    return out


def exponential_quantiles(n: int) -> List[float]:
    """n stratified quantiles of the unit exponential (mid-points of n
    equal-probability strata), rescaled to mean exactly 1: the gaps of a
    Poisson process with its sampling noise taken out."""
    q = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    mean = sum(q) / n
    return [v / mean for v in q]


def lengths_of(spec: Dict) -> List[int]:
    """{"lo", "hi", "count", "multiple"} or {"values": [...]}"""
    if "values" in spec:
        return [int(v) for v in spec["values"]]
    return evenly_spaced(int(spec["lo"]), int(spec["hi"]),
                         int(spec["count"]), int(spec.get("multiple", 1)))


def request_groups(traffic: Dict, seed: int, n_requests: int,
                   vocab: int) -> List[Dict]:
    """n_requests requests in seeded order: {"prompt": [ids],
    "max_new": n, "gap_s": seconds to wait BEFORE this request is due
    (0 for a backlog)}. Work per group is fixed; see the module text."""
    prompts = lengths_of(traffic["prompt_lens"])
    outputs = lengths_of(traffic["output_lens"])
    n = len(prompts)
    if len(outputs) != n:
        raise ValueError("prompt_lens and output_lens must have the same "
                         f"count, got {n} and {len(outputs)}")
    rate = traffic.get("rate_per_s")
    gaps = ([g / float(rate) for g in exponential_quantiles(n)]
            if rate else [0.0] * n)
    # the order inside each group comes from the traffic file's own
    # `order_seed`, the same for every run: --seed draws the token ids
    # and nothing else, so every run of a cell is offered the same
    # sequence of sizes at the same times and only its speed varies
    order_rng = np.random.RandomState(int(traffic["order_seed"]))
    rng = np.random.RandomState(seed % (2 ** 32))
    out: List[Dict] = []
    g = 0
    while len(out) < n_requests:
        order = order_rng.permutation(n)
        gap_order = order_rng.permutation(n)
        for j, i in enumerate(order):
            out.append({
                "prompt": rng.randint(0, vocab, prompts[i]).tolist(),
                "max_new": outputs[(i + g) % n],
                "gap_s": gaps[gap_order[j]]})
        g += 1
    return out[:n_requests]


def stagger_first(requests: List[Dict], slots: int) -> None:
    """Give the first `slots` requests outputs of k/slots of their
    length, k = 1..slots, so the slots of a backlog run are out of phase
    from the first step and the lead-in is seconds, not a request's
    lifetime. The same cut in every run."""
    for k, req in enumerate(requests[:slots], start=1):
        req["max_new"] = max(2, req["max_new"] * k // slots)


def token_windows(seed: int, vocab: int, n_steps: int, batch: int,
                  seq_len: int):
    """An endless stream of training windows from the seed: each is
    (src [n_steps, batch, seq_len], tgt [n_steps, batch, seq_len, 1]),
    the target the next token of a [seq_len + 1] draw."""
    rng = np.random.RandomState(seed % (2 ** 32))
    while True:
        draw = rng.randint(0, vocab, (n_steps, batch, seq_len + 1))
        yield (draw[..., :-1].astype(np.int64),
               draw[..., 1:, None].astype(np.int64))
