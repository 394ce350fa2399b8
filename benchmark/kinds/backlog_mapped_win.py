"""Traffic kind `backlog_mapped_win`: `backlog_mapped_limits` for a model
with WINDOW layers beside full ones (two kinds of cache, one table a
kind) that holds a share of its experts. Made the way
`backlog_mapped_sel.py` was: it sets names of `backlog_mapped` (and one
of `_serve`) for its own run, a process running one cell, and edits no
kind that exists. ROADMAP D12 folds the five into one.

The check there seeds every layer's pool with the whole prompt through
one table and asks the reference for every position's logits. This one
admits the checked sequence as the scheduler does: the window layers'
pool gets the prompt's last window alone (`DecodeModel.window_span`),
each teacher-forced step releases the block that fell behind the window
before it takes the new row's, and the step reads one table a kind; and
it asks the mapping (`reference_on`) for the compared positions' rows
alone, the reference over the same share of the experts with the
program's routes forced. Limits, from the configuration's
`harness.limits` with their readings in `limits_why`: `row_max`,
`rms_max`, `tie_max`, as in `backlog_mapped`.

Observations: those of `backlog_mapped_limits`, plus `window_rows_read`,
`window_rows_live`, `window_blocks_released` (`DecodeMetrics.
on_window_rows`, `on_window_blocks`) and `moe_held_pairs` over the
window, `block_size`, and `kernel.window_rows` (rows inside the slots'
windows over the traced steps, a window layer).
"""

from __future__ import annotations

import math
from typing import Dict

import numpy as np

from kinds import _serve, backlog_mapped, backlog_mapped_limits

WINDOW_COUNTERS = ("window_rows_read", "window_rows_live",
                   "window_blocks_released", "moe_held_pairs")


def counters(dec) -> Dict:
    """`backlog_mapped_limits.counters`, and the window's and the held
    pairs' counters, from the same one snapshot."""
    snap = dec.metrics_snapshot()
    keys = (_serve.COUNTERS + ("slots_used_sum", "slots_capacity_sum")
            + backlog_mapped.MOE_COUNTERS
            + backlog_mapped_limits.PAGED_COUNTERS + WINDOW_COUNTERS)
    return {k: snap[k] for k in keys if k in snap}


def _cached(model, ids, p_len, m):
    """`backlog_mapped._cached` with the window layers' blocks held as
    the scheduler holds them: (logits rows [m + 1, V], the experts the
    program chose [layers, p_len + m, k])."""
    bs = model.block_size
    blocks = list(range(1, 1 + math.ceil((p_len + m) / bs)))
    free = list(range(model.window_blocks_per_seq + 1, 0, -1))
    last, kv = model.prefill([int(t) for t in ids[:p_len]])
    routes = [np.asarray(model.last_routes)[:, :p_len]]
    first, count = model.window_span(p_len)
    held = [free.pop() for _ in range(count)]
    model.seed_sequence(blocks[:math.ceil(p_len / bs)], kv,
                        window_ids=held)
    rows = [np.asarray(last)]
    tokens = np.zeros(model.slots, np.int64)
    lens = np.zeros(model.slots, np.int32)
    tables = np.zeros((model.slots, model.max_blocks_per_seq), np.int32)
    wtables = np.zeros_like(tables)
    tables[0, :len(blocks)] = blocks
    for j in range(m):
        tokens[0] = ids[p_len + j]
        lens[0] = p_len + j + 1
        new_first, count = model.window_span(p_len + j + 1)
        while first < new_first:        # released before the new block
            free.append(held.pop(0))
            first += 1
        while first + len(held) < new_first + count:
            held.append(free.pop())
        wtables[0] = 0
        wtables[0, first:first + len(held)] = held
        rows.append(np.asarray(model.decode_step(tokens, lens, tables,
                                                 wtables))[0])
        routes.append(np.asarray(model.last_routes)[:, :1])
    model.reset_pools()
    return np.stack(rows), np.concatenate(routes, 1)


def readings(got, want, tie, p_len) -> Dict:
    """What a check reads of the program's rows `got` against the
    reference's `want` (both [m + 1, V]) and the experts' shortfall."""
    got, want = np.asarray(got, np.float32), np.asarray(want)
    tie = np.asarray(tie)
    by_row = np.max(np.abs(got - want), axis=-1) / np.std(want)
    return dict(
        max_abs_err_over_std=float(by_row.max()),
        max_by_position=[round(float(v), 5) for v in by_row],
        rms_err_over_std=float(
            np.sqrt(np.mean(np.square(got - want))) / np.std(want)),
        reference_std=float(np.std(want)),
        max_shortfall=float(tie.max()),
        tokens_on_another_expert=int(np.sum(np.any(tie > 0, axis=0))),
        compared_on_another_expert=int(
            np.sum(np.any(tie[:, p_len - 1:] > 0, axis=0))))


def within(read: Dict, limits: Dict) -> bool:
    return bool(read["max_abs_err_over_std"] <= limits["row_max"]
                and read["rms_err_over_std"] <= limits["rms_max"]
                and read["max_shortfall"] <= limits["tie_max"])


def check_with(limits: Dict):
    def check(mapping, reference, model, sz, cfg, ids, p_len, m, prints):
        """The comparison that decides `correct` (the module's text).
        Returns (correct, what it read)."""
        got, routes = _cached(model, ids, p_len, m)
        weights = mapping.reference_weights(model.weights.__getitem__,
                                            sz["n_layers"])
        same = bool(np.array_equal(backlog_mapped._fingerprint(weights),
                                   prints))
        want, tie = mapping.reference_on(
            reference, weights, cfg, ids, routes,
            list(range(p_len - 1, p_len + m)))
        read = readings(got, want, tie, p_len)
        read.update(limits, weights_came_back_bit_for_bit=same,
                    window_span=list(model.window_span(p_len + m)))
        return bool(same and np.all(np.isfinite(got))
                    and within(read, limits)), read

    return check


class WindowSpans(_serve.ProgramSpans):
    """`ProgramSpans` for a step that takes one table a kind of cache,
    counting also the rows inside the slots' windows over the traced
    steps, a window layer: min(context, window) a slot."""

    last = None    # the run's one instance, for `run` below

    def __init__(self, model):
        import jax
        step = model.decode_step            # before it is wrapped
        super().__init__(model)
        self.window_rows = 0
        window = int(getattr(model, "window", 0))

        def traced_step(token_ids, context_lens, *tables):
            if self.counting:
                self.context_tokens += int(np.sum(context_lens))
                self.decode_calls += 1
                self.window_rows += int(
                    np.minimum(context_lens, window).sum())
            with jax.profiler.TraceAnnotation("program/decode_step"):
                return step(token_ids, context_lens, *tables)

        model.decode_step = traced_step
        WindowSpans.last = self


def run(cell, args, device, t_start):
    limits = {k: float(v)
              for k, v in cell.config["harness"]["limits"].items()}
    backlog_mapped.check = check_with(limits)
    backlog_mapped.counters = counters
    _serve.ProgramSpans = WindowSpans
    out = backlog_mapped.run(cell, args, device, t_start)
    obs = out["obs"]
    obs["block_size"] = int(cell.config["serving"]["block_size"])
    if WindowSpans.last is not None and obs.get("kernel"):
        obs["kernel"]["window_rows"] = WindowSpans.last.window_rows
    return out
