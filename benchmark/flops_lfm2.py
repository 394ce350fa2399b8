"""Operations and bytes of a model whose layers are gated short
convolutions with ONE grouped-query attention layer a period, leading
dense FFNs and experts behind them, from its shapes and the program's
counters alone. Beside `flops.py`, `flops_moe.py`, `flops_mla.py`,
`flops_dsa.py` and `flops_swa.py`, which the add-only rule keeps as they
are; same rule as there: what the ALGORITHM needs, never what an
implementation happens to do (the lanes of a tile that hold the other
K/V head of a pair are NOT counted twice, nor the wrong-group columns the
kernel scores and masks: a kernel that reads or computes them pays for
them in its share).
"""

from __future__ import annotations


# (flops, bytes) of the attention layers' paged attention calls: every live
# row read once a layer, its K and V of `kv_heads` heads of `head_dim`,
# each of the `heads` query heads scoring it and taking its value. The
# count `flops_swa.py` makes of Command A+'s full layers, at this model's
# widths (live rows x 4,096 B): one function, under this module's name
from flops_swa import paged_full  # noqa: E402,F401


def decode_weight_bytes(*, experts_touched, layer_steps, n_layers,
                        state_layers, dense_layers, dense_width, d_model,
                        d_ff, num_experts, n_heads, n_kv_heads, head_dim,
                        conv_taps, vocab, dtype_bytes=4, **_):
    """Weight bytes the decode steps of a window must read at least once
    a step. `experts_touched` and `layer_steps` are the window's
    `pt_decode_moe_*` counters (over the layers that HAVE experts). A
    step reads: in every conv layer the in-projection (d x 3 d), the
    taps and the out-projection; in every attention layer the four
    projections (q and o of all heads, k and v of the K/V heads) and the
    two head norms; in every layer its two norms' gains; the dense
    layers' three matrices; in every expert layer the router, its bias
    and the three matrices of each expert that received a token; once,
    the tied head (the embedding's table) and its norm. The embedding
    rows a step gathers, the cache and the states it reads are not
    weights and are left out: a floor."""
    expert_layers = n_layers - dense_layers
    steps = layer_steps / expert_layers
    conv = 4.0 * d_model * d_model + conv_taps * d_model
    attention = 2.0 * d_model * n_heads * head_dim \
        + 2.0 * d_model * n_kv_heads * head_dim + 2.0 * head_dim
    mixers = state_layers * conv + (n_layers - state_layers) * attention \
        + n_layers * 2.0 * d_model
    dense = dense_layers * 3.0 * d_model * dense_width
    routers = expert_layers * (d_model * num_experts + num_experts)
    head = d_model * vocab + d_model
    return dtype_bytes * (experts_touched * 3.0 * d_model * d_ff
                          + steps * (mixers + dense + routers + head))


def decode_kv_bytes(*, paged_live_pages, block_size, state_layers,
                    n_layers, n_kv_heads, head_dim, dtype_bytes=4, **_):
    """K/V bytes the decode steps of a window must read: every live page
    of the attention layers (`paged_live_pages`, a layer). The conv
    layers' states (two rows a slot and layer) are nothing beside it and
    are left out."""
    row = dtype_bytes * 2.0 * n_kv_heads * head_dim
    return row * float(paged_live_pages) * block_size \
        * (n_layers - state_layers)


# -- the whole step's least (`flops.py` has the two functions' text) --------

def decode_least_bytes(counts, *, d_model, conv_taps, dtype_bytes=4,
                       **model):
    """Every live row of the attention layers; a conv layer's state (taps
    - 1 rows of d_model a slot) read once and written once a live slot,
    state layer and step."""
    return {
        "weights": decode_weight_bytes(
            experts_touched=counts["moe_experts_touched"],
            layer_steps=counts["moe_layer_steps"], d_model=d_model,
            conv_taps=conv_taps, dtype_bytes=dtype_bytes, **model),
        "cache": decode_kv_bytes(
            paged_live_pages=float(counts["live_rows"])
            / counts["block_size"],
            block_size=counts["block_size"], dtype_bytes=dtype_bytes,
            **model),
        "states": dtype_bytes * 2.0 * float(counts["state_slot_steps"])
        * (conv_taps - 1) * d_model}


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
