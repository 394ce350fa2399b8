"""Traffic kind `backlog_mapped_blk`: `backlog_mapped_ssd`'s run (a model
whose state layers are updated by a kernel of their own: the live slots
of the traced steps counted) for a model whose attention layers read
whole BLOCKS chosen on pooled keys, with `backlog_mapped_state`'s check
(the checked sequence admitted into a USED slot at a length that is not
its bucket's end, then teacher-forced steps) held to the reference on the
program's CHOICES, as `backlog_mapped_sel` holds an indexer's. Made the
way `backlog_mapped_ssd.py` was: it sets names of `backlog_mapped` (and
one of `_serve`) for its own run, a process running one cell, and edits
no kind that exists. ROADMAP D12 folds them into one.

The check collects `DecodeModel.last_selections`: after a prefill every
row's chosen blocks a K/V head, one bit a block; after a step every
slot's, as block numbers. The reference (`reference_on`) computes the
same equations on THOSE blocks (with random weights near ties flip on
rounding, and a block read or not moves the logits by more than the
precision does) and reports how far each choice lies from its own.
Limits, from the configuration's `harness.limits` with their readings in
`limits_why`: `row_max`, `rms_max` (as in `backlog_mapped`) and `tie_max`
(the largest shortfall of the program's weakest freely chosen block
score under the reference's own, a layer, row and K/V head).

Observations: those of `backlog_mapped_ssd`, plus `sparse_live_rows`,
`sparse_selected_rows` (`DecodeMetrics.on_sparse_rows`: cache rows live
in the steps' slots, and the rows of them the attention read, a layer
and K/V head), `block_chosen_blocks`, `block_pooled_rows`,
`block_dense_slot_steps` (`on_block_choices`) over the window, the same
rows over the traced steps (`kernel.selected_rows`, `kernel.pooled_rows`)
and, of a traced run, `traced_ops` for `readers/op_ms.py`.
"""

from __future__ import annotations

import math
from typing import Dict

import numpy as np

import common
from kinds import (_serve, backlog_mapped, backlog_mapped_limits,
                   backlog_mapped_sel, backlog_mapped_ssd,
                   backlog_mapped_state)

BLOCK_COUNTERS = ("block_chosen_blocks", "block_pooled_rows",
                  "block_dense_slot_steps")


def counters(dec) -> Dict:
    """`backlog_mapped_ssd.counters`' keys without the experts', and the
    selection's, from the same one snapshot."""
    snap = dec.metrics_snapshot()
    keys = (_serve.COUNTERS + ("slots_used_sum", "slots_capacity_sum")
            + backlog_mapped_limits.PAGED_COUNTERS
            + backlog_mapped_state.STATE_COUNTERS
            + backlog_mapped_sel.SPARSE_COUNTERS + BLOCK_COUNTERS)
    return {k: snap[k] for k in keys if k in snap}


def _unpack(packed, kv_heads, n_blocks):
    """A prefill's chosen blocks [bound, H_kv x words] int32 -> bool
    [bound, H_kv, n_blocks] (`pack_mask`'s bits, a K/V head's words
    side by side)."""
    from paddle_tpu.ops.attention_ops import unpack_mask
    packed = np.asarray(packed)
    return unpack_mask(packed.reshape(packed.shape[0], kv_heads, -1),
                       n_blocks)


def _cached(model, ids, p_len, m, slot, former_len):
    """`backlog_mapped_state._cached` for a model without experts, and
    beside the logits rows [m + 1, V] the blocks every row chose, bool
    [sparse layers, p_len + m, H_kv, NB]."""
    bs = model.block_size
    n = p_len + m
    n_blocks = math.ceil(n / bs)
    blocks = list(range(1, 1 + n_blocks))
    tokens = np.zeros(model.slots, np.int64)
    lens = np.zeros(model.slots, np.int32)
    tables = np.zeros((model.slots, model.max_blocks_per_seq), np.int32)
    tables[slot, :len(blocks)] = blocks
    if former_len:
        # the slot's former owner: admitted, one step, gone
        former = [int(t) for t in
                  backlog_mapped_state.former_ids(ids, former_len)]
        _, kv = model.prefill(former)
        model.seed_sequence(blocks[:math.ceil(former_len / bs)], kv,
                            slot=slot)
        tokens[slot], lens[slot] = former[0], former_len + 1
        model.decode_step(tokens, lens, tables).tokens
    last, kv = model.prefill([int(t) for t in ids[:p_len]])
    layers = [np.asarray(layer) for layer in model.last_selections]
    kv_heads = np.asarray(model.last_selections[0]).shape[-1] \
        // math.ceil(math.ceil(kv.bound / bs) / 32)
    chosen = np.zeros((len(layers), n, kv_heads, n_blocks), bool)
    for at, layer in enumerate(layers):
        got = _unpack(layer, kv_heads, math.ceil(kv.bound / bs))
        width = min(got.shape[-1], n_blocks)
        chosen[at, :p_len, :, :width] = got[:p_len, :, :width]
    model.seed_sequence(blocks[:math.ceil(p_len / bs)], kv, slot=slot)
    rows = [np.asarray(last)]
    for j in range(m):
        tokens[slot] = ids[p_len + j]
        lens[slot] = p_len + j + 1
        rows.append(np.asarray(model.decode_step(tokens, lens,
                                                 tables))[slot])
        picked = np.asarray(model.last_selections)[:, slot]   # [L, G, W]
        for at, heads in enumerate(picked):
            for g, own in enumerate(heads):
                chosen[at, p_len + j, g, own[own >= 0]] = True
    model.reset_pools()
    return np.stack(rows), chosen


def readings(got, want, tie, p_len) -> Dict:
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
        rows_on_another_choice=int(np.sum(np.any(tie > 0, axis=(0, 2)))),
        compared_on_another_choice=int(
            np.sum(np.any(tie[:, p_len - 1:] > 0, axis=(0, 2)))))


def within(read: Dict, limits: Dict) -> bool:
    return bool(read["max_abs_err_over_std"] <= limits["row_max"]
                and read["rms_err_over_std"] <= limits["rms_max"]
                and read["max_shortfall"] <= limits["tie_max"])


def check_with(limits: Dict, slot: int, former_len: int):
    def check(mapping, reference, model, sz, cfg, ids, p_len, m, prints):
        """The comparison that decides `correct` (the module's text).
        Returns (correct, what it read)."""
        if getattr(model, "last_selections", "absent") == "absent":
            raise SystemExit("benchmark: the program reports no "
                             "selections (DecodeModel.last_selections)")
        got, chosen = _cached(model, ids, p_len, m, slot, former_len)
        weights = mapping.reference_weights(model.weights.__getitem__,
                                            sz["n_layers"])
        same = bool(np.array_equal(backlog_mapped._fingerprint(weights),
                                   prints))
        want, tie = mapping.reference_on(
            reference, weights, cfg, ids, chosen,
            list(range(p_len - 1, p_len + m)), p_len)
        read = readings(got, want, tie, p_len)
        per_row = chosen[:, p_len - 1:].sum(-1)
        read.update(limits, weights_came_back_bit_for_bit=same, slot=slot,
                    former_len=former_len,
                    blocks_chosen_of=[int(per_row.min()), int(per_row.max()),
                                      chosen.shape[-1]])
        return bool(same and np.all(np.isfinite(got))
                    and within(read, limits)), read

    return check


class ChoosingSpans(backlog_mapped_ssd.LiveSpans):
    """`LiveSpans`, counting also what the traced steps' block-sparse
    layers read, a layer and K/V head: the chosen blocks' rows and the
    pooled keys scored (`ops.block_sparse_ops.chosen_counts`)."""

    def __init__(self, model):
        sizes = getattr(model, "block_sparse", None)
        super().__init__(model)
        wrapped = model.decode_step         # `LiveSpans`' own
        self.selected_rows = self.pooled_rows = 0

        def counted_step(token_ids, context_lens, *tables):
            if self.counting and sizes:
                from paddle_tpu.ops.block_sparse_ops import chosen_counts
                read, pooled, _, _ = chosen_counts(context_lens, sizes,
                                                   model.block_size)
                self.selected_rows += read
                self.pooled_rows += pooled
            return wrapped(token_ids, context_lens, *tables)

        model.decode_step = counted_step
        backlog_mapped_ssd.LiveSpans.last = self


def run(cell, args, device, t_start):
    limits = {k: float(v)
              for k, v in cell.config["harness"]["limits"].items()}
    chk = cell.traffic["check"]
    backlog_mapped.check = check_with(limits, int(chk["slot"]),
                                      int(chk["former_len"]))
    backlog_mapped.counters = counters
    _serve.ProgramSpans = ChoosingSpans
    out = backlog_mapped.run(cell, args, device, t_start)
    obs = out["obs"]
    obs["block_size"] = int(cell.config["serving"]["block_size"])
    spans = backlog_mapped_ssd.LiveSpans.last
    if spans is not None and obs.get("kernel"):
        model = obs["model"]
        obs["kernel"].update(
            live_slot_steps=spans.live_slots * int(model["state_layers"]),
            selected_rows=spans.selected_rows,
            pooled_rows=spans.pooled_rows)
        red = out["reduced"]
        if red:
            obs["traced_ops"] = dict(seconds=red["op_seconds"],
                                     calls=red["op_calls"],
                                     decode_steps=spans.decode_calls)
            common.note(traced_ops=obs["traced_ops"])
    return out
