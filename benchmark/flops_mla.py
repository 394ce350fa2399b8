"""Operations and bytes of a model with latent attention (MLA), a
leading dense layer and shared experts, from its shapes and the
program's counters alone. Beside `flops.py` and `flops_moe.py`, which
the add-only rule keeps as they are; same rule as there: what the
ALGORITHM needs, never what an implementation happens to do (the 64
padding floats a latent row is stored with are NOT counted: a kernel
that reads them pays for them in its share).
"""

from __future__ import annotations


def paged_latent(*, context_tokens, layers, calls, heads, row_floats,
                 value_floats, slots, dtype_bytes=4, **_):
    """(flops, bytes) of the latent paged decode-attention calls of
    `calls` decode steps, one call per layer and step: one absorbed
    query per slot and head against the slot's cached latent rows
    (`context_tokens` is the sum of the context lengths over slots and
    steps; every layer reads its own row of each ONCE, for all heads);
    a row scores on all its `row_floats` (latent and rotary key) and
    gives its first `value_floats` as the value, 2 FLOPs a float and
    head each; the queries are read and the outputs written."""
    rows = float(context_tokens) * layers
    flops = 2.0 * rows * heads * (row_floats + value_floats)
    nbytes = dtype_bytes * (
        rows * row_floats
        + float(calls) * layers * slots * heads
        * (row_floats + value_floats))
    return flops, nbytes


def _expert_layers(n_layers, dense_layers, **_):
    return n_layers - dense_layers


def decode_weight_bytes(*, experts_touched, layer_steps, n_layers,
                        dense_layers, dense_width, d_model, d_ff,
                        num_experts, shared_width, n_heads, kv_lora_rank,
                        qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                        vocab, dtype_bytes=4, **_):
    """Weight bytes the decode steps of a window must read at least once
    a step. `experts_touched` and `layer_steps` are the window's
    `pt_decode_moe_*` counters, summed over the layers that HAVE experts
    and over steps. A step reads: in every layer the four latent
    projections (q, kv_a, kv_b, o) and three norms' gains; in a leading
    dense layer its three FFN matrices; in an expert layer the router
    with its bias, the shared expert's three matrices, and the three
    matrices of each routed expert that received a token; once, the head
    and its norm. The embedding rows a step gathers and the cache it
    reads are not weights and are left out: a floor."""
    steps = layer_steps / (n_layers - dense_layers)
    attention = d_model * n_heads * (qk_nope_head_dim + qk_rope_head_dim) \
        + d_model * (kv_lora_rank + qk_rope_head_dim) \
        + kv_lora_rank * n_heads * (qk_nope_head_dim + v_head_dim) \
        + n_heads * v_head_dim * d_model \
        + 2.0 * d_model + kv_lora_rank
    dense = 3.0 * d_model * dense_width
    sparse = d_model * num_experts + num_experts \
        + 3.0 * d_model * shared_width
    head = d_model * vocab + d_model
    return dtype_bytes * (
        experts_touched * 3.0 * d_model * d_ff
        + steps * (n_layers * attention + dense_layers * dense
                   + (n_layers - dense_layers) * sparse + head))


def latent_cache_bytes(*, live_pages, block_size, n_layers, kv_lora_rank,
                       qk_rope_head_dim, dtype_bytes=4, **_):
    """Bytes of latent rows the decode steps of a window must read:
    `live_pages` is `pt_decode_paged_live_pages_total` over the window
    (pages a layer's call has to read, summed over slots and steps; a
    sequence's last page counts whole, under a third of a per cent at
    these contexts), every layer reads its own."""
    return dtype_bytes * float(live_pages) * block_size * n_layers \
        * (kv_lora_rank + qk_rope_head_dim)


# -- the whole step's least (`flops.py` has the two functions' text) --------

def decode_least_bytes(counts, **model):
    """The latent rows at the floats that carry the token (576, not the
    640 they are stored in)."""
    return {
        "weights": decode_weight_bytes(
            experts_touched=counts["moe_experts_touched"],
            layer_steps=counts["moe_layer_steps"], **model),
        "cache": latent_cache_bytes(
            live_pages=float(counts["live_rows"]) / counts["block_size"],
            block_size=counts["block_size"], **model),
        "states": 0.0}


def pass_weight_bytes(*, n_layers, dense_layers, d_model, d_ff, vocab,
                      experts_per_tok, dtype_bytes=4, **model):
    expert_layers = n_layers - dense_layers
    return {"always": decode_weight_bytes(
                experts_touched=0, layer_steps=expert_layers,
                n_layers=n_layers, dense_layers=dense_layers,
                d_model=d_model, d_ff=d_ff, vocab=vocab,
                dtype_bytes=dtype_bytes, **model),
            "head": dtype_bytes * (d_model * vocab + d_model),
            "expert": dtype_bytes * 3.0 * d_model * d_ff,
            "routed": experts_per_tok * expert_layers}
