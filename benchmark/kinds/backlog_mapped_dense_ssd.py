"""Traffic kind `backlog_mapped_dense_ssd`: `backlog_mapped_ssd` for a
model WITHOUT experts (its check has no routes to force and no shortfall
to hold: `backlog_mapped_state`'s reads `last_routes`, which a dense
model has none of) whose bundle serves its matrices in the dtype the
configuration states (`serving.weight_dtype`). Made the way
`backlog_mapped_ssd.py` was: it sets names of `backlog_mapped` (and one
of `_serve`) for its own run, a process running one cell, imports what it
needs of its siblings (the slot's former owner, the state's and the
pages' counters, the spans that count live slots) and edits no kind that
exists. ROADMAP D12 folds the eight into one.

The check admits the checked sequence as the scheduler admits one: into
slot `check.slot`, which another, SHORTER sequence (`check.former_len`
tokens through the smallest bucket) was admitted into and decoded a step
in before, so that the slot's states and blocks hold that sequence's
rows when the admission comes; through the largest bucket at a length
that is NOT the bucket's end (`check.prompt_len`); then
`check.decode_steps` teacher-forced steps through the jitted step. The
reference (`reference_on`: the compared positions' rows alone) has no
cache and no state, and reads the SAME matrices the server holds, cast
up where it uses them: what the limits hold is the program's arithmetic,
not the rounding of the weights, which both sides share. The weights'
fingerprints are taken of the scope's ROUNDED values (the mapping's
start-up program rounds each matrix where it draws it) and must come
back bit for bit, and the bundle must say the dtype the configuration
states. Limits, from the configuration's `harness.limits` with their
readings in `limits_why`: `row_max` and `rms_max`, as in
`backlog_mapped` (no experts: no `tie_max`).

Observations: those of `backlog_mapped_ssd` (`state_slot_steps`,
`state_seeds`, `state_seed_bytes`, `paged_live_pages`,
`kernel.live_slot_steps`; no routing counter). The check's line carries
`weight_dtype` and `weight_bytes` as the loaded bundle says them.
"""

from __future__ import annotations

import math
from typing import Dict

import numpy as np

import common
from kinds import _serve, backlog_mapped, backlog_mapped_ssd
from kinds.backlog_mapped_state import former_ids


def _cached(model, ids, p_len, m, slot, former_len):
    """The module's text: the logits rows [m + 1, V]."""
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
    model.seed_sequence(blocks[:math.ceil(p_len / bs)], kv, slot=slot)
    rows = [np.asarray(last)]
    for j in range(m):
        tokens[slot] = ids[p_len + j]
        lens[slot] = p_len + j + 1
        rows.append(np.asarray(model.decode_step(tokens, lens,
                                                 tables))[slot])
    model.reset_pools()
    return np.stack(rows)


def readings(got, want) -> Dict:
    """What a check reads of the program's rows `got` against the
    reference's `want` (both [m + 1, V]), as shares of the reference
    logits' standard deviation."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    by_row = np.max(np.abs(got - want), axis=-1) / np.std(want)
    return dict(
        max_abs_err_over_std=float(by_row.max()),
        max_by_position=[round(float(v), 5) for v in by_row],
        rms_err_over_std=float(
            np.sqrt(np.mean(np.square(got - want))) / np.std(want)),
        reference_std=float(np.std(want)))


def within(read: Dict, limits: Dict) -> bool:
    return bool(read["max_abs_err_over_std"] <= limits["row_max"]
                and read["rms_err_over_std"] <= limits["rms_max"])


def check_with(limits: Dict, slot: int, former_len: int, weight_dtype: str):
    def check(mapping, reference, model, sz, cfg, ids, p_len, m, prints):
        """The comparison that decides `correct` (the module's text).
        Returns (correct, what it read)."""
        got = _cached(model, ids, p_len, m, slot, former_len)
        weights = mapping.reference_weights(model.weights.__getitem__,
                                            sz["n_layers"])
        same = bool(np.array_equal(backlog_mapped._fingerprint(weights),
                                   prints))
        want, _ = mapping.reference_on(
            reference, weights, cfg, ids, None,
            list(range(p_len - 1, p_len + m)))
        read = readings(got, want)
        stated = model.weight_dtype == (weight_dtype or "float32")
        read.update(limits, weights_came_back_bit_for_bit=same, slot=slot,
                    former_len=former_len, weight_dtype=model.weight_dtype,
                    weight_bytes=model.weight_bytes)
        return bool(same and stated and np.all(np.isfinite(got))
                    and within(read, limits)), read

    return check


def run(cell, args, device, t_start):
    cfg = cell.config
    limits = {k: float(v) for k, v in cfg["harness"]["limits"].items()}
    chk = cell.traffic["check"]
    backlog_mapped.check = check_with(
        limits, int(chk["slot"]), int(chk["former_len"]),
        str(cfg["serving"].get("weight_dtype", "")))
    backlog_mapped.counters = backlog_mapped_ssd.counters
    _serve.ProgramSpans = backlog_mapped_ssd.LiveSpans
    out = backlog_mapped.run(cell, args, device, t_start)
    obs = out["obs"]
    spans = backlog_mapped_ssd.LiveSpans.last
    if spans is not None and obs.get("kernel"):
        obs["kernel"]["live_slot_steps"] = spans.live_slots \
            * int(obs["model"]["state_layers"])
    common.note(served=dict(weight_dtype=cfg["serving"].get("weight_dtype"),
                            state_layers=obs["model"]["state_layers"],
                            full_layers=obs["model"]["full_layers"]))
    return out
