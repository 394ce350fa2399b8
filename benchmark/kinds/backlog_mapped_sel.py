"""Traffic kind `backlog_mapped_sel`: `backlog_mapped_limits` for a model
whose attention reads a SELECTED set of cache rows (a sparse-attention
indexer). Made the way that file was: it sets names of `backlog_mapped`
(and one of `_serve`) for its own run, a process running one cell, and
edits no kind that exists. ROADMAP D12 folds the four into one.

The check there reads `DecodeModel.last_routes` only. This one also
collects `DecodeModel.last_selections`, the positions every row's
attention read in every layer (a prefill returns them one bit a
position, a step as positions), and hands both to the mapping
(`reference_on`): the reference then computes the same equations on the
program's experts AND on the program's rows, and reports how far each
choice lies from its own. Every row, not the compared ones alone: a
row that is read or not can carry a tenth of a head's weight, so a near
tie in the indexer at ANY prompt row moves that row's output, and
through the next layers' K and V the compared rows' logits, by more than
the precision does (the first readings on the chip: PERF.md section 6,
PR 33). Limits, all from the configuration's
`harness.limits` with their readings in `limits_why`: `row_max`,
`rms_max`, `tie_max` (the experts, as in `backlog_mapped`) and
`sel_tie_max` (the selection: the shortfall of the program's weakest
selected index score under the reference's own topk-th, in standard
deviations of the row's scores).

Observations: those of `backlog_mapped_limits`, plus `sparse_live_rows`
and `sparse_selected_rows` over the window (`DecodeMetrics.
on_sparse_rows`: cache rows live in the steps' slots, and the rows of
them the attention read, a layer), `kernel.selected_rows` (the same
second count over the traced steps) and, of a traced run, `op_seconds`
and `op_calls` by op family once more under `traced_ops`, with the
traced decode steps, for `readers/op_ms.py`.
"""

from __future__ import annotations

import math
from typing import Dict

import numpy as np

import common
from kinds import _serve, backlog_mapped, backlog_mapped_limits

SPARSE_COUNTERS = ("sparse_live_rows", "sparse_selected_rows")


def counters(dec) -> Dict:
    """`backlog_mapped_limits.counters`, and the two row counters, from
    the same one snapshot."""
    snap = dec.metrics_snapshot()
    keys = (_serve.COUNTERS + ("slots_used_sum", "slots_capacity_sum")
            + backlog_mapped.MOE_COUNTERS
            + backlog_mapped_limits.PAGED_COUNTERS + SPARSE_COUNTERS)
    return {k: snap[k] for k in keys if k in snap}


def _cached(model, ids, p_len, m):
    """`backlog_mapped._cached`, and beside the logits rows and the
    routes [layers, p_len + m, k] what every row's attention read, bool
    [layers, p_len + m, p_len + m]; None where the program reports none
    (the parent of the PR that brought them)."""
    bs = model.block_size
    n = p_len + m
    blocks = list(range(1, 1 + math.ceil(n / bs)))
    last, kv = model.prefill([int(t) for t in ids[:p_len]])
    routes = [np.asarray(model.last_routes)[:, :p_len]]
    reports = getattr(model, "last_selections", None) is not None
    masks = None
    if reports:
        from paddle_tpu.ops.attention_ops import unpack_mask
        packed = np.stack([np.asarray(layer)         # [L, bound, bound/32]
                           for layer in model.last_selections])
        masks = np.zeros((packed.shape[0], n, n), bool)
        masks[:, :p_len, :p_len] = unpack_mask(
            packed, packed.shape[1])[:, :p_len, :p_len]
    model.seed_sequence(blocks[:math.ceil(p_len / bs)], kv)
    rows = [np.asarray(last)]
    tokens = np.zeros(model.slots, np.int64)
    lens = np.zeros(model.slots, np.int32)
    tables = np.zeros((model.slots, model.max_blocks_per_seq), np.int32)
    tables[0, :len(blocks)] = blocks
    for j in range(m):
        tokens[0] = ids[p_len + j]
        lens[0] = p_len + j + 1
        rows.append(np.asarray(model.decode_step(tokens, lens,
                                                 tables))[0])
        routes.append(np.asarray(model.last_routes)[:, :1])
        if reports:
            picked = np.asarray(model.last_selections)[:, 0]   # [L, topk]
            for layer, pos in enumerate(picked):
                masks[layer, p_len + j, pos[pos >= 0]] = True
    model.reset_pools()
    return np.stack(rows), np.concatenate(routes, 1), masks


def readings(got, want, tie, sel_tie, p_len) -> Dict:
    """What a check reads of the program's rows `got` against the
    reference's `want` (both [m + 1, V]) and the two shortfalls."""
    got, want = np.asarray(got, np.float32), np.asarray(want)
    tie, sel_tie = np.asarray(tie), np.asarray(sel_tie)
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
            np.sum(np.any(tie[:, p_len - 1:] > 0, axis=0))),
        max_selection_shortfall=float(sel_tie.max()),
        rows_on_another_selection=int(np.sum(np.any(sel_tie > 0, axis=0))),
        compared_on_another_selection=int(
            np.sum(np.any(sel_tie[:, p_len - 1:] > 0, axis=0))))


def within(read: Dict, limits: Dict) -> bool:
    return bool(read["max_abs_err_over_std"] <= limits["row_max"]
                and read["rms_err_over_std"] <= limits["rms_max"]
                and read["max_shortfall"] <= limits["tie_max"]
                and read["max_selection_shortfall"]
                <= limits["sel_tie_max"])


def check_with(limits: Dict):
    def check(mapping, reference, model, sz, cfg, ids, p_len, m, prints):
        """The comparison that decides `correct` (the module's text).
        Returns (correct, what it read)."""
        got, routes, masks = _cached(model, ids, p_len, m)
        if masks is None:
            raise SystemExit("benchmark: the program reports no "
                             "selections (DecodeModel.last_selections)")
        weights = mapping.reference_weights(model.weights.__getitem__,
                                            sz["n_layers"])
        same = bool(np.array_equal(backlog_mapped._fingerprint(weights),
                                   prints))
        want, tie, sel_tie = mapping.reference_on(
            reference, weights, cfg, ids, routes, masks,
            list(range(p_len - 1, p_len + m)))
        read = readings(got, want, tie, sel_tie, p_len)
        selected = masks[:, p_len - 1:].sum(-1)
        read.update(limits, weights_came_back_bit_for_bit=same,
                    selected_of_context=[int(selected.min()),
                                         int(selected.max()), p_len + m])
        return bool(same and np.all(np.isfinite(got))
                    and within(read, limits)), read

    return check


class SelectingSpans(_serve.ProgramSpans):
    """`ProgramSpans`, counting also the rows the traced steps' attention
    read, a layer: min(context, topk) a slot."""

    last = None    # the run's one instance, for `run` below

    def __init__(self, model):
        super().__init__(model)
        self.selected_rows = 0
        topk = int(getattr(model, "index_topk", 0))
        step = model.decode_step

        def counted_step(token_ids, context_lens, block_tables):
            if self.counting and topk:
                self.selected_rows += int(
                    np.minimum(context_lens, topk).sum())
            return step(token_ids, context_lens, block_tables)

        model.decode_step = counted_step
        SelectingSpans.last = self


def run(cell, args, device, t_start):
    limits = {k: float(v)
              for k, v in cell.config["harness"]["limits"].items()}
    backlog_mapped.check = check_with(limits)
    backlog_mapped.counters = counters
    _serve.ProgramSpans = SelectingSpans
    out = backlog_mapped.run(cell, args, device, t_start)
    spans, obs = SelectingSpans.last, out["obs"]
    if spans is not None and obs.get("kernel"):
        obs["kernel"]["selected_rows"] = spans.selected_rows
        red = out["reduced"]
        if red:
            obs["traced_ops"] = dict(seconds=red["op_seconds"],
                                     calls=red["op_calls"],
                                     decode_steps=spans.decode_calls)
            common.note(traced_ops=obs["traced_ops"])
    return out
