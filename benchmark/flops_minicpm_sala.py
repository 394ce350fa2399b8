"""Operations and bytes of a MiniCPM-SALA cut (block-sparse attention
over pooled keys in one layer of four, linear attention whose state is a
matrix a head in the others, a dense gated FFN behind each), from its
shapes and the program's counters alone. Beside `flops.py` and its
siblings, which the add-only rule keeps as they are; same rule as there:
what the ALGORITHM needs, never what an implementation happens to do
(the pooled keys behind a slot's length, which the scoring's one product
reads and masks, are NOT counted, nor the other K/V head's lanes of a
chosen page: a kernel that reads them pays for them in its share).
"""

from __future__ import annotations


def paged_block_sparse(*, selected_rows, full_layers, calls, slots, heads,
                       kv_heads, head_dim, dtype_bytes=4, **_):
    """(flops, bytes) of the block-sparse layers' attention calls of
    `calls` decode steps: every row of the chosen blocks (`selected_rows`:
    the rows a K/V head's choice holds, summed over slots and steps; the
    query's own block counted to the query) read once a layer and K/V
    head, its K and its V of `head_dim`; each of that head's `heads /
    kv_heads` query heads scores it and takes its value, 2 FLOPs a float
    each; the queries are read and the outputs written."""
    rows = float(selected_rows) * full_layers
    flops = 4.0 * rows * heads * head_dim
    nbytes = dtype_bytes * (
        rows * kv_heads * 2.0 * head_dim
        + float(calls) * full_layers * slots * 2.0 * heads * head_dim)
    return flops, nbytes


def lightning_update(*, live_slot_steps, heads, head_dim, dtype_bytes=4,
                     **_):
    """(flops, bytes) of the linear layers' state update: each live
    slot's [heads, head_dim, head_dim] matrix read once and written once
    a linear layer and step (`live_slot_steps`: live slots summed over
    the traced steps AND the linear layers); a decay, a push and a read
    a float (5 FLOPs): the bytes bound it. The rows of q, k and v (a few
    KB a slot) are left out: a floor."""
    floats = float(live_slot_steps) * heads * head_dim * head_dim
    return 5.0 * floats, 2.0 * dtype_bytes * floats


def state_update_bytes(*, state_slot_steps, n_heads, head_dim,
                       dtype_bytes=4, **_):
    """Bytes the linear layers' states cost the steps of a window: every
    live slot's matrix of every linear layer (`state_slot_steps`,
    `pt_decode_state_slot_steps_total`) read once and written once."""
    return dtype_bytes * 2.0 * float(state_slot_steps) \
        * n_heads * head_dim * head_dim


def decode_kv_bytes(*, sparse_selected_rows, block_pooled_rows, full_layers,
                    n_kv_heads, head_dim, dtype_bytes=4, **_):
    """Cache bytes the decode steps of a window must read in the
    block-sparse layers: the chosen blocks' rows (`sparse_selected_rows`:
    a layer and K/V head; K and V) and the pooled keys the choice was
    scored on (`block_pooled_rows`: every kernel wholly inside a pruning
    slot's context, a layer and K/V head)."""
    head = dtype_bytes * n_kv_heads * head_dim
    return full_layers * head * (2.0 * float(sparse_selected_rows)
                                 + float(block_pooled_rows))


def _pass_weights(*, state_layers, full_layers, d_model, d_ff, n_heads,
                  n_kv_heads, head_dim, **_):
    """Floats one pass over the layers reads: a linear layer's five
    projections, its two q/k gains and its output norm; a sparse layer's
    q, gate and out, its k and v of the K/V heads and its gains; each
    layer's gated FFN and two norms."""
    wide, narrow = n_heads * head_dim, n_kv_heads * head_dim
    ffn = 3.0 * d_model * d_ff + 2.0 * d_model
    linear = 5.0 * d_model * wide + 2.0 * head_dim + wide
    sparse = 3.0 * d_model * wide + 2.0 * d_model * narrow + 2.0 * head_dim
    return state_layers * (linear + ffn) + full_layers * (sparse + ffn)


def decode_weight_bytes(*, decode_steps, d_model, vocab, dtype_bytes=4,
                        **model):
    """Weight bytes the decode steps of a window must read at least once
    a step: every layer's mixer and FFN, the head and its norm. The
    embedding rows a step gathers, the cache and the states are not
    weights: a floor."""
    return dtype_bytes * float(decode_steps) * (
        _pass_weights(d_model=d_model, **model) + d_model * vocab + d_model)


def decode_bytes(counts, **model):
    """The parts of the least bytes the decode steps of a window must
    move: {"weights", "state", "kv"}."""
    return {
        "weights": decode_weight_bytes(decode_steps=counts["decode_steps"],
                                       **model),
        "state": state_update_bytes(
            state_slot_steps=counts["state_slot_steps"], **model),
        "kv": decode_kv_bytes(
            sparse_selected_rows=counts["sparse_selected_rows"],
            block_pooled_rows=counts.get("block_pooled_rows", 0), **model)}


# -- the whole step's least (`flops.py` has the two functions' text) --------

def decode_least_bytes(counts, **model):
    parts = decode_bytes(counts, **model)
    return {"weights": parts["weights"], "cache": parts["kv"],
            "states": parts["state"]}


def pass_weight_bytes(*, d_model, vocab, dtype_bytes=4, **model):
    """A dense model: an admission reads every layer and the head once,
    and has no routed expert."""
    head = dtype_bytes * (d_model * vocab + d_model)
    return {"always": dtype_bytes * _pass_weights(d_model=d_model, **model)
            + head, "head": head, "expert": 0.0, "routed": 0}
