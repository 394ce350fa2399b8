"""Traffic kind `backlog_mapped_hybrid`: `backlog_mapped_limits` for a
model with THREE kinds of sequence memory in one bundle (a full pool that
later layers read without owning it, window pools, scan states a slot).
It IMPORTS what it needs of `backlog_mapped_win` (the window layers'
blocks held as the scheduler holds them, the spans that count the
windows' rows, the readings) and of `backlog_mapped_state` (the slot's
former owner, the state's counters) and copies neither; made the way they
were: it sets names of `backlog_mapped` (and one of `_serve`) for its own
run, a process running one cell, and edits no kind that exists. ROADMAP
D12 folds the seven into one.

The check admits the checked sequence as the scheduler admits one: into
slot `check.slot`, which another, SHORTER sequence (`check.former_len`
tokens through the smallest bucket) was admitted into and decoded a step
in before, so that the slot's states, its full blocks AND its window
blocks hold that sequence's rows when the admission comes; through the
largest bucket at a length that is NOT the bucket's end
(`check.prompt_len`: a state is what row n - 1 leaves, not what the
padding leaves); the window pools get the prompt's last window alone;
then `check.decode_steps` teacher-forced steps through the jitted step,
each releasing the window block that fell behind before it takes the new
row's, one table a kind. The reference (`reference_on`: the compared
positions' rows alone) has no cache and no state. Limits, from the
configuration's `harness.limits` with their readings in `limits_why`:
`row_max` and `rms_max`, as in `backlog_mapped` (no experts: no
`tie_max`), and `position_rms_max`, the largest root mean square of one
compared position.

Observations: those of `backlog_mapped_limits`, plus the window's
(`window_rows_read`, `window_rows_live`, `window_blocks_released`), the
state's (`state_slot_steps`, `state_seeds`, `state_seed_bytes`) and the
shared pool's (`pool_rows_read_writer`, `pool_rows_read_readers`:
`DecodeMetrics.on_pool_rows`) counters over the window, `block_size`, and
`kernel.window_rows` (rows inside the slots' windows over the traced
steps, a window layer).
"""

from __future__ import annotations

import math
from typing import Dict

import numpy as np

from kinds import _serve, backlog_mapped, backlog_mapped_limits
from kinds.backlog_mapped_state import STATE_COUNTERS, former_ids
from kinds.backlog_mapped_win import WINDOW_COUNTERS, WindowSpans
from kinds.backlog_mapped_win import readings as _logit_readings

POOL_COUNTERS = ("pool_rows_read_writer", "pool_rows_read_readers")


def counters(dec) -> Dict:
    """`backlog_mapped_limits.counters`, and the three kinds' counters,
    from the same one snapshot."""
    snap = dec.metrics_snapshot()
    keys = (_serve.COUNTERS + ("slots_used_sum", "slots_capacity_sum")
            + backlog_mapped_limits.PAGED_COUNTERS + WINDOW_COUNTERS
            + STATE_COUNTERS + POOL_COUNTERS)
    return {k: snap[k] for k in keys if k in snap}


class _WindowBlocks:
    """A slot's window blocks as the scheduler holds them: the prompt's
    last window at an admission, and before each step the block that
    fell behind released and the new row's taken."""

    def __init__(self, model):
        self.model = model
        self.free = list(range(model.window_blocks_per_seq + 1, 0, -1))
        self.first, self.held = 0, []

    def admit(self, length):
        self.free += self.held[::-1]
        self.first, count = self.model.window_span(length)
        self.held = [self.free.pop() for _ in range(count)]
        return self.held

    def table(self, length, row):
        first, count = self.model.window_span(length)
        while self.first < first:       # released before the new block
            self.free.append(self.held.pop(0))
            self.first += 1
        while self.first + len(self.held) < first + count:
            self.held.append(self.free.pop())
        row[:] = 0
        row[self.first:self.first + len(self.held)] = self.held


def _cached(model, ids, p_len, m, slot, former_len):
    """The module's text: the logits rows [m + 1, V]."""
    bs = model.block_size
    blocks = list(range(1, 1 + math.ceil((p_len + m) / bs)))
    window = _WindowBlocks(model)
    tokens = np.zeros(model.slots, np.int64)
    lens = np.zeros(model.slots, np.int32)
    tables = np.zeros((model.slots, model.max_blocks_per_seq), np.int32)
    wtables = np.zeros_like(tables)
    tables[slot, :len(blocks)] = blocks
    if former_len:
        # the slot's former owner: admitted, one step, gone
        former = [int(t) for t in former_ids(ids, former_len)]
        _, kv = model.prefill(former)
        model.seed_sequence(blocks[:math.ceil(former_len / bs)], kv,
                            window_ids=window.admit(former_len), slot=slot)
        tokens[slot], lens[slot] = former[0], former_len + 1
        window.table(former_len + 1, wtables[slot])
        model.decode_step(tokens, lens, tables, wtables).tokens
    last, kv = model.prefill([int(t) for t in ids[:p_len]])
    model.seed_sequence(blocks[:math.ceil(p_len / bs)], kv,
                        window_ids=window.admit(p_len), slot=slot)
    rows = [np.asarray(last)]
    for j in range(m):
        tokens[slot] = ids[p_len + j]
        lens[slot] = p_len + j + 1
        window.table(p_len + j + 1, wtables[slot])
        rows.append(np.asarray(model.decode_step(tokens, lens, tables,
                                                 wtables))[slot])
    model.reset_pools()
    return np.stack(rows)


def readings(got, want, tie, p_len) -> Dict:
    """`backlog_mapped_win.readings`, and the root mean square of each
    compared position by itself: a fault of ONE step (a context a row
    short at the last) is a ninth of the whole's mean square and all of
    its position's."""
    read = _logit_readings(got, want, tie, p_len)
    err = np.asarray(got, np.float32) - np.asarray(want)
    by_row = np.sqrt(np.mean(np.square(err), axis=-1)) / np.std(want)
    read["rms_by_position"] = [round(float(v), 5) for v in by_row]
    return read


def within(read: Dict, limits: Dict) -> bool:
    return bool(read["max_abs_err_over_std"] <= limits["row_max"]
                and read["rms_err_over_std"] <= limits["rms_max"]
                and max(read["rms_by_position"])
                <= limits["position_rms_max"])


def check_with(limits: Dict, slot: int, former_len: int):
    def check(mapping, reference, model, sz, cfg, ids, p_len, m, prints):
        """The comparison that decides `correct` (the module's text).
        Returns (correct, what it read)."""
        got = _cached(model, ids, p_len, m, slot, former_len)
        weights = mapping.reference_weights(model.weights.__getitem__,
                                            sz["n_layers"])
        same = bool(np.array_equal(backlog_mapped._fingerprint(weights),
                                   prints))
        want, tie = mapping.reference_on(
            reference, weights, cfg, ids, None,
            list(range(p_len - 1, p_len + m)))
        read = readings(got, want, tie, p_len)
        read.update(limits, weights_came_back_bit_for_bit=same, slot=slot,
                    former_len=former_len,
                    window_span=list(model.window_span(p_len + m)))
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
    _serve.ProgramSpans = WindowSpans
    out = backlog_mapped.run(cell, args, device, t_start)
    obs = out["obs"]
    obs["block_size"] = int(cell.config["serving"]["block_size"])
    if WindowSpans.last is not None and obs.get("kernel"):
        obs["kernel"]["window_rows"] = WindowSpans.last.window_rows
    return out
