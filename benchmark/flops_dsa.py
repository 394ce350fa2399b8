"""Operations and bytes of a model with grouped-query attention and a
sparse-attention indexer (DeepSeek Sparse Attention: an index key a
token, a top-k of the context a query), from its shapes and the
program's counters alone. Beside `flops.py`, `flops_moe.py` and
`flops_mla.py`, which the add-only rule keeps as they are; same rule as
there: what the ALGORITHM needs, never what an implementation happens to
do (the 64 padding floats an index key is stored with are NOT counted,
nor the wrong-group columns the sparse kernel scores and masks: a kernel
that reads or computes them pays for them in its share).
"""

from __future__ import annotations


def paged_index(*, context_tokens, layers, calls, slots, index_heads,
                index_dim, dtype_bytes=4, **_):
    """(flops, bytes) of the indexer's paged scoring calls of `calls`
    decode steps, one call per layer and step: every live token's index
    key (`context_tokens`: the sum of the context lengths over slots and
    steps; `index_dim` floats, read ONCE for all index heads) against
    the slot's `index_heads` index queries, 2 FLOPs a float and head,
    then a relu and a weighted sum over the heads (2 more a head); one
    score a token is written, the queries and their weights are read."""
    rows = float(context_tokens) * layers
    flops = rows * index_heads * (2.0 * index_dim + 2.0)
    nbytes = dtype_bytes * (
        rows * (index_dim + 1)
        + float(calls) * layers * slots * index_heads * (index_dim + 1))
    return flops, nbytes


def paged_sparse(*, selected_rows, layers, calls, slots, heads, kv_heads,
                 head_dim, dtype_bytes=4, **_):
    """(flops, bytes) of the sparse attention's calls of `calls` decode
    steps: every SELECTED row (`selected_rows`: min(context, topk)
    summed over slots and steps) read once a layer, its K and its V of
    `kv_heads` heads; each of the `heads` query heads scores it and
    takes its value, 2 FLOPs a float each; the queries are read and the
    outputs written."""
    rows = float(selected_rows) * layers
    flops = 4.0 * rows * heads * head_dim
    nbytes = dtype_bytes * (
        rows * 2.0 * kv_heads * head_dim
        + float(calls) * layers * slots * 2.0 * heads * head_dim)
    return flops, nbytes


def decode_weight_bytes(*, experts_touched, layer_steps, n_layers, d_model,
                        d_ff, num_experts, n_heads, n_kv_heads, head_dim,
                        index_heads, index_head_dim, vocab, dtype_bytes=4,
                        **_):
    """Weight bytes the decode steps of a window must read at least once
    a step. `experts_touched` and `layer_steps` are the window's
    `pt_decode_moe_*` counters. A step reads: in every layer the four
    attention projections (q and o of all heads, k and v of the K/V
    heads), the indexer's three, the router, the gains (two norms, q and
    k norm, the index key's LayerNorm) and the three matrices of each
    expert that received a token; once, the head and its norm. The
    embedding rows a step gathers and the cache it reads are not weights
    and are left out: a floor."""
    steps = layer_steps / n_layers
    attention = 2.0 * d_model * n_heads * head_dim \
        + 2.0 * d_model * n_kv_heads * head_dim
    indexer = d_model * (index_heads * index_head_dim + index_head_dim
                         + index_heads)
    gains = 2.0 * d_model + 2.0 * head_dim + 2.0 * index_head_dim
    layer = attention + indexer + gains + d_model * num_experts
    head = d_model * vocab + d_model
    return dtype_bytes * (experts_touched * 3.0 * d_model * d_ff
                          + layer_steps * layer + steps * head)


# -- the whole step's least (`flops.py` has the two functions' text) --------

def decode_least_bytes(counts, *, n_layers, n_kv_heads, head_dim,
                       index_head_dim, dtype_bytes=4, **model):
    """Every layer reads every live row's index key (64 floats, not the
    128 it is stored in) and the K and V rows of the SELECTED rows alone
    (`sparse_live_rows`, `sparse_selected_rows`: a layer)."""
    return {
        "weights": decode_weight_bytes(
            experts_touched=counts["moe_experts_touched"],
            layer_steps=counts["moe_layer_steps"], n_layers=n_layers,
            n_kv_heads=n_kv_heads, head_dim=head_dim,
            index_head_dim=index_head_dim, dtype_bytes=dtype_bytes,
            **model),
        "cache": dtype_bytes * n_layers * (
            float(counts["sparse_live_rows"]) * index_head_dim
            + float(counts["sparse_selected_rows"]) * 2.0 * n_kv_heads
            * head_dim),
        "states": 0.0}


def pass_weight_bytes(*, n_layers, d_model, d_ff, vocab, experts_per_tok,
                      dtype_bytes=4, **model):
    return {"always": decode_weight_bytes(
                experts_touched=0, layer_steps=n_layers, n_layers=n_layers,
                d_model=d_model, d_ff=d_ff, vocab=vocab,
                dtype_bytes=dtype_bytes, **model),
            "head": dtype_bytes * (d_model * vocab + d_model),
            "expert": dtype_bytes * 3.0 * d_model * d_ff,
            "routed": experts_per_tok * n_layers}
