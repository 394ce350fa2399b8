"""Operations and bytes of a model whose layers alternate window and full
attention over grouped K/V heads, with one chip's share of an expert
layer beside shared experts, from its shapes and the program's counters
alone. Beside `flops.py`, `flops_moe.py`, `flops_mla.py` and
`flops_dsa.py`, which the add-only rule keeps as they are; same rule as
there: what the ALGORITHM needs, never what an implementation happens to
do (the rows of a window's oldest page that lie behind the window are
NOT counted, nor the wrong-group columns the kernel scores and masks: a
kernel that reads or computes them pays for them in its share).
"""

from __future__ import annotations


def _paged(rows, calls, layers, slots, heads, kv_heads, head_dim,
           dtype_bytes):
    rows = float(rows) * layers
    flops = 4.0 * rows * heads * head_dim
    nbytes = dtype_bytes * (
        rows * 2.0 * kv_heads * head_dim
        + float(calls) * layers * slots * 2.0 * heads * head_dim)
    return flops, nbytes


def paged_window(*, window_rows, window_layers, calls, slots, heads,
                 kv_heads, head_dim, dtype_bytes=4, **_):
    """(flops, bytes) of the window layers' paged attention calls of
    `calls` decode steps: every row inside a slot's window
    (`window_rows`: min(context, window) summed over slots and steps)
    read once a window layer, its K and its V of `kv_heads` heads; each
    of the `heads` query heads scores it and takes its value, 2 FLOPs a
    float each; the queries are read and the outputs written."""
    return _paged(window_rows, calls, window_layers, slots, heads,
                  kv_heads, head_dim, dtype_bytes)


def paged_full(*, context_tokens, full_layers, calls, slots, heads,
               kv_heads, head_dim, dtype_bytes=4, **_):
    """The same for the full layers' calls: every live row
    (`context_tokens`: the contexts summed over slots and steps)."""
    return _paged(context_tokens, calls, full_layers, slots, heads,
                  kv_heads, head_dim, dtype_bytes)


def decode_weight_bytes(*, experts_touched, layer_steps, n_layers, d_model,
                        d_ff, num_experts, n_heads, n_kv_heads, head_dim,
                        shared_width, vocab, dtype_bytes=4, **_):
    """Weight bytes the decode steps of a window must read at least once
    a step. `experts_touched` (of the experts held here) and
    `layer_steps` are the window's `pt_decode_moe_*` counters. A step
    reads: in every layer the four attention projections (q and o of all
    heads, k and v of the K/V heads), the router over all `num_experts`,
    the shared experts' three matrices, the one norm's gain and the
    three matrices of each held expert that received a token; once, the
    tied head (the embedding's table) and its norm. The embedding rows a
    step gathers and the cache it reads are not weights and are left
    out: a floor."""
    steps = layer_steps / n_layers
    attention = 2.0 * d_model * n_heads * head_dim \
        + 2.0 * d_model * n_kv_heads * head_dim
    layer = attention + d_model * num_experts \
        + 3.0 * d_model * shared_width + d_model
    head = d_model * vocab + d_model
    return dtype_bytes * (experts_touched * 3.0 * d_model * d_ff
                          + layer_steps * layer + steps * head)


def decode_kv_bytes(*, window_rows_read, paged_live_pages, block_size,
                    window_layers, full_layers, n_kv_heads, head_dim,
                    dtype_bytes=4, **_):
    """K/V bytes the decode steps of a window must read: the rows inside
    the windows (`window_rows_read`, `pt_decode_window_rows_read_total`:
    summed over slots AND window layers already) and every live page of
    the full layers (`paged_live_pages`, a layer)."""
    row = dtype_bytes * 2.0 * n_kv_heads * head_dim
    return row * (float(window_rows_read)
                  + float(paged_live_pages) * block_size * full_layers)


# -- the whole step's least (`flops.py` has the two functions' text) --------

def decode_least_bytes(counts, **model):
    """The rows inside the windows of the window layers and every live
    row of the full ones."""
    return {
        "weights": decode_weight_bytes(
            experts_touched=counts["moe_experts_touched"],
            layer_steps=counts["moe_layer_steps"], **model),
        "cache": decode_kv_bytes(
            window_rows_read=counts["window_rows_read"],
            paged_live_pages=float(counts["live_rows"])
            / counts["block_size"],
            block_size=counts["block_size"], **model),
        "states": 0.0}


def pass_weight_bytes(*, n_layers, d_model, d_ff, vocab, dtype_bytes=4,
                      **model):
    """A share of the experts is held: a token's eight may all fall on
    other chips, so no routed expert is counted for an admission."""
    return {"always": decode_weight_bytes(
                experts_touched=0, layer_steps=n_layers, n_layers=n_layers,
                d_model=d_model, d_ff=d_ff, vocab=vocab,
                dtype_bytes=dtype_bytes, **model),
            "head": dtype_bytes * (d_model * vocab + d_model),
            "expert": dtype_bytes * 3.0 * d_model * d_ff, "routed": 0}
