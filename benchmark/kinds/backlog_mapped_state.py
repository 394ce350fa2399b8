"""Traffic kind `backlog_mapped_state`: `backlog_mapped_limits` for a model
some of whose layers keep a STATE a sequence and no cache (gated short
convolutions beside attention layers). Made the way `backlog_mapped_win.py`
was: it sets names of `backlog_mapped` for its own run, a process running
one cell, and edits no kind that exists. ROADMAP D12 folds the six into one.

The check there runs the checked sequence in slot 0 of zeroed pools, its
prompt as long as its bucket. This one admits it as the scheduler admits a
sequence: into slot `check.slot`, which another, SHORTER sequence
(`check.former_len` tokens through the smallest bucket) was admitted into
and decoded a step in before, so that the slot's state and blocks hold
that sequence's rows when the admission comes; through the largest bucket
at a length that is NOT the bucket's end (`check.prompt_len`: the state
is what row n - 1 leaves, not what the padding leaves); then
`check.decode_steps` teacher-forced steps through the jitted step, so
that the taps read rows the admission seeded and rows the steps wrote.
The reference (`reference_on`: the compared positions' rows alone, on
the program's routes) has no state at all: it convolves the whole
sequence. Limits, from the configuration's `harness.limits` with their
readings in `limits_why`: `row_max`, `rms_max`, `tie_max`, as in
`backlog_mapped`.

Observations: those of `backlog_mapped_limits`, plus `state_slot_steps`,
`state_seeds`, `state_seed_bytes` (`DecodeMetrics.on_state_rows`) over
the window and `block_size`.
"""

from __future__ import annotations

import math
from typing import Dict

import numpy as np

from kinds import _serve, backlog_mapped, backlog_mapped_limits
from kinds.backlog_mapped_win import readings, within

STATE_COUNTERS = ("state_slot_steps", "state_seeds", "state_seed_bytes")


def counters(dec) -> Dict:
    """`backlog_mapped_limits.counters`, and the state's counters, from
    the same one snapshot."""
    snap = dec.metrics_snapshot()
    keys = (_serve.COUNTERS + ("slots_used_sum", "slots_capacity_sum")
            + backlog_mapped.MOE_COUNTERS
            + backlog_mapped_limits.PAGED_COUNTERS + STATE_COUNTERS)
    return {k: snap[k] for k in keys if k in snap}


def former_ids(ids, former_len):
    """The sequence that owns the slot before the checked one: other ids
    than the checked prompt's first rows."""
    return np.asarray(ids)[::-1][:former_len]


def _cached(model, ids, p_len, m, slot, former_len):
    """The module's text: (logits rows [m + 1, V], the experts the
    program chose [expert layers, p_len + m, k])."""
    bs = model.block_size
    blocks = list(range(1, 1 + math.ceil((p_len + m) / bs)))
    tokens = np.zeros(model.slots, np.int64)
    lens = np.zeros(model.slots, np.int32)
    tables = np.zeros((model.slots, model.max_blocks_per_seq), np.int32)
    tables[slot, :len(blocks)] = blocks
    if former_len:
        # the slot's former owner: admitted, one step, gone
        former = [int(t) for t in former_ids(ids, former_len)]
        _, kv = model.prefill(former)
        model.seed_sequence(blocks[:math.ceil(former_len / bs)], kv,
                            slot=slot)
        tokens[slot], lens[slot] = former[0], former_len + 1
        model.decode_step(tokens, lens, tables).tokens
    last, kv = model.prefill([int(t) for t in ids[:p_len]])
    routes = [np.asarray(model.last_routes)[:, :p_len]]
    model.seed_sequence(blocks[:math.ceil(p_len / bs)], kv, slot=slot)
    rows = [np.asarray(last)]
    for j in range(m):
        tokens[slot] = ids[p_len + j]
        lens[slot] = p_len + j + 1
        rows.append(np.asarray(model.decode_step(tokens, lens,
                                                 tables))[slot])
        routes.append(np.asarray(model.last_routes)[:, slot:slot + 1])
    model.reset_pools()
    return np.stack(rows), np.concatenate(routes, 1)


def check_with(limits: Dict, slot: int, former_len: int):
    def check(mapping, reference, model, sz, cfg, ids, p_len, m, prints):
        """The comparison that decides `correct` (the module's text).
        Returns (correct, what it read)."""
        got, routes = _cached(model, ids, p_len, m, slot, former_len)
        weights = mapping.reference_weights(model.weights.__getitem__,
                                            sz["n_layers"])
        same = bool(np.array_equal(backlog_mapped._fingerprint(weights),
                                   prints))
        want, tie = mapping.reference_on(
            reference, weights, cfg, ids, routes,
            list(range(p_len - 1, p_len + m)))
        read = readings(got, want, tie, p_len)
        read.update(limits, weights_came_back_bit_for_bit=same, slot=slot,
                    former_len=former_len)
        return bool(same and np.all(np.isfinite(got))
                    and within(read, limits)), read

    return check


def run(cell, args, device, t_start):
    limits = {k: float(v)
              for k, v in cell.config["harness"]["limits"].items()}
    chk = cell.traffic["check"]
    backlog_mapped.check = check_with(limits, int(chk["slot"]),
                                      int(chk["former_len"]))
    backlog_mapped.counters = counters
    out = backlog_mapped.run(cell, args, device, t_start)
    out["obs"]["block_size"] = int(cell.config["serving"]["block_size"])
    return out
