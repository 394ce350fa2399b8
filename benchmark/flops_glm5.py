"""Operations and bytes of a model with latent attention behind a query
low-rank, a sparse-attention indexer that selects rows of the LATENT
cache, a leading dense layer and one chip's share of an expert layer
beside a shared expert (GLM-5), from its shapes and the program's
counters alone. Beside `flops.py` and the other architectures' modules,
which the add-only rule keeps as they are; the configuration names this
one under `harness.flops`. What the ALGORITHM needs, never what an
implementation happens to do, with one exception the issue that brought
the kernel made: a selected latent row is priced AS STORED (640 floats,
2,560 B: one copy a row is the kernel's whole point, and the row cannot
be copied short of its tile), where the whole step's least
(`decode_least_bytes`) counts its 576 floats of content.
"""

from __future__ import annotations

#: floats a latent row is stored in (whole lane tiles of 128)
_STORED = 128


def _stored(floats):
    return -(-int(floats) // _STORED) * _STORED


def paged_sparse_latent(*, selected_rows, layers, calls, slots, heads,
                        row_floats, value_floats, dtype_bytes=4, **_):
    """(flops, bytes) of the sparse latent attention's calls of `calls`
    decode steps: every SELECTED row (`selected_rows`: min(context, topk)
    summed over slots and steps, the rows the selection returned) read
    once a layer for all `heads` heads, as stored; each head scores it
    on its `row_floats` and takes its first `value_floats` as the value,
    2 FLOPs a float and head each (one MXU pass each: a kernel that runs
    its scores in more passes pays for them in its share); the absorbed
    queries are read and the outputs written."""
    rows = float(selected_rows) * layers
    flops = 2.0 * rows * heads * (row_floats + value_floats)
    nbytes = dtype_bytes * (
        rows * _stored(row_floats)
        + float(calls) * layers * slots * heads
        * (row_floats + value_floats))
    return flops, nbytes


def paged_index(*, context_tokens, layers, calls, slots, index_heads,
                index_dim, dtype_bytes=4, **_):
    """(flops, bytes) of the indexer's paged scoring calls of `calls`
    decode steps (`flops_dsa.paged_index`'s text: every live token's
    index key read once for all index heads, 2 FLOPs a float and head,
    a relu and a weighted sum over the heads)."""
    rows = float(context_tokens) * layers
    flops = rows * index_heads * (2.0 * index_dim + 2.0)
    nbytes = dtype_bytes * (
        rows * (index_dim + 1)
        + float(calls) * layers * slots * index_heads * (index_dim + 1))
    return flops, nbytes


def decode_weight_bytes(*, experts_touched, layer_steps, n_layers,
                        dense_layers, dense_width, d_model, d_ff,
                        num_experts, shared_width, n_heads, q_lora_rank,
                        kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
                        v_head_dim, index_heads, index_head_dim, vocab,
                        dtype_bytes=4, **_):
    """Weight bytes the decode steps of a window must read at least once
    a step. `experts_touched` (of the experts held here) and
    `layer_steps` are the window's `pt_decode_moe_*` counters, summed
    over the layers that HAVE experts and over steps. A step reads: in
    every layer the five latent projections (q_a, q_b, kv_a, kv_b, o),
    the indexer's three, the gains (two norms, the two low-rank norms,
    the index key's LayerNorm); in a leading dense layer its three FFN
    matrices; in an expert layer the router over ALL `num_experts` with
    its bias, the shared expert's three matrices, and the three matrices
    of each held expert that received a token; once, the head and its
    norm. The embedding rows a step gathers and the cache it reads are
    not weights and are left out: a floor."""
    steps = layer_steps / (n_layers - dense_layers)
    attention = d_model * q_lora_rank \
        + q_lora_rank * n_heads * (qk_nope_head_dim + qk_rope_head_dim) \
        + d_model * (kv_lora_rank + qk_rope_head_dim) \
        + kv_lora_rank * n_heads * (qk_nope_head_dim + v_head_dim) \
        + n_heads * v_head_dim * d_model
    indexer = q_lora_rank * index_heads * index_head_dim \
        + d_model * (index_head_dim + index_heads)
    gains = 2.0 * d_model + q_lora_rank + kv_lora_rank \
        + 2.0 * index_head_dim
    dense = 3.0 * d_model * dense_width
    sparse = d_model * num_experts + num_experts \
        + 3.0 * d_model * shared_width
    head = d_model * vocab + d_model
    return dtype_bytes * (
        experts_touched * 3.0 * d_model * d_ff
        + steps * (n_layers * (attention + indexer + gains)
                   + dense_layers * dense
                   + (n_layers - dense_layers) * sparse + head))


def cache_bytes(*, sparse_live_rows, sparse_selected_rows, n_layers,
                kv_lora_rank, qk_rope_head_dim, index_head_dim,
                dtype_bytes=4, **_):
    """Cache bytes the decode steps of a window must read: every layer
    reads every live row's index key and the latent rows of the
    SELECTED rows alone (`sparse_live_rows`, `sparse_selected_rows`: a
    layer), each at the floats that carry the token."""
    return dtype_bytes * n_layers * (
        float(sparse_live_rows) * index_head_dim
        + float(sparse_selected_rows) * (kv_lora_rank + qk_rope_head_dim))


# -- the whole step's least (`flops.py` has the two functions' text) --------

def decode_least_bytes(counts, **model):
    return {
        "weights": decode_weight_bytes(
            experts_touched=counts["moe_experts_touched"],
            layer_steps=counts["moe_layer_steps"], **model),
        "cache": cache_bytes(
            sparse_live_rows=counts["sparse_live_rows"],
            sparse_selected_rows=counts["sparse_selected_rows"], **model),
        "states": 0.0}


def pass_weight_bytes(*, n_layers, dense_layers, d_model, d_ff, vocab,
                      dtype_bytes=4, **model):
    """A share of the experts is held: a token's eight may all fall on
    other chips, so no routed expert is counted for an admission."""
    expert_layers = n_layers - dense_layers
    return {"always": decode_weight_bytes(
                experts_touched=0, layer_steps=expert_layers,
                n_layers=n_layers, dense_layers=dense_layers,
                d_model=d_model, d_ff=d_ff, vocab=vocab,
                dtype_bytes=dtype_bytes, **model),
            "head": dtype_bytes * (d_model * vocab + d_model),
            "expert": dtype_bytes * 3.0 * d_model * d_ff, "routed": 0}
