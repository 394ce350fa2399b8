"""Operations and bytes of the Mellum 2 training step's kernels, from
shapes alone, beside `flops.py` (which the add-only rule keeps as it is).

As there, everything counts what the MATHEMATICS needs, never what an
implementation does: a windowed call is priced at the keys inside the
band (row t reads min(t + 1, window) keys), the experts at the (token,
expert) pairs that fell on held experts. A kernel that walks the whole
triangle, computes an edge block's hidden half, pads a group or puts a
wave's rows through their experts a second time in its backward reads
LOW for it, not busy; no share built on these can pass 100% unless the
time leaves work out.
"""

from __future__ import annotations


def visible_pairs(seq_len, window=None):
    """(row, key) pairs a causal call of `seq_len` rows reads: row t
    reads t + 1 keys, under a window at most `window` of them."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) / 2.0
    return window * (window + 1) / 2.0 + (seq_len - window) * float(window)


def flash_fwd(*, calls, batch, heads, kv_heads, seq_len, head_dim,
              window=None, dtype_bytes=2):
    """(flops, bytes) of `calls` causal flash forward calls: QK^T and PV,
    2 D each a visible pair and query head; Q read and O written at the
    query heads, K and V read once at the K/V heads groups share."""
    flops = 4.0 * calls * batch * heads * head_dim \
        * visible_pairs(seq_len, window)
    nbytes = 2.0 * calls * batch * (heads + kv_heads) * seq_len \
        * head_dim * dtype_bytes
    return flops, nbytes


def flash_bwd(**shape):
    """(flops, bytes) of the backward of those calls, however many
    kernels it is split into: five products a visible pair (QK^T again
    because P is not kept, dV, dP, dQ, dK) against the forward's two; Q,
    K, V, O, dO read, dQ, dK, dV written: twice the forward's bytes."""
    flops, nbytes = flash_fwd(**shape)
    return 2.5 * flops, 2.0 * nbytes


def expert_products(*, pairs, layer_steps, held, d_model, expert_width,
                    dtype_bytes=2):
    """(flops, bytes) of the nine grouped products a layer and step
    (gate, up and down forward, each one's dx and dW) over `pairs` (token,
    expert) pairs on `held` experts in `layer_steps` layer-steps: 2 d f
    operations a pair and product; every product reads or writes the
    held experts' matrix once and moves a pair's two rows."""
    per = float(d_model) * expert_width
    flops = 9.0 * 2.0 * per * pairs
    nbytes = 9.0 * dtype_bytes * (layer_steps * held * per
                                  + pairs * (d_model + expert_width))
    return flops, nbytes


def forward_flops_per_token(*, d_model, heads, kv_heads, head_dim, window,
                            window_layers, full_layers, expert_width,
                            experts, held, top_k, vocab, seq_len,
                            held_pairs_per_token=None):
    """Forward operations a token: 2 a weight the token meets (q, k, v
    and o; the router's E columns; three matrices of each held expert
    its pairs fell on, `held_pairs_per_token` a layer or, None, what an
    even routing sends here, k held / E; the held rows of the head) and
    4 D a visible (row, key) pair and query head."""
    layers = window_layers + full_layers
    proj = 2.0 * d_model * (2 * heads + 2 * kv_heads) * head_dim
    pairs = top_k * held / float(experts) if held_pairs_per_token is None \
        else held_pairs_per_token
    ffn = 2.0 * d_model * experts + pairs * 3 * 2.0 * d_model * expert_width
    scores = 4.0 * heads * head_dim / seq_len * (
        window_layers * visible_pairs(seq_len, window)
        + full_layers * visible_pairs(seq_len))
    return layers * (proj + ffn) + scores + 2.0 * d_model * vocab


def train_flops_per_token(**shape):
    """Forward + backward: 3x the forward (the backward computes the
    gradient of each product's two operands). Recomputation (remat, the
    flash backward's second QK^T, the held share's second gate and up
    products) is not counted, nor the optimizer's elementwise update."""
    return 3.0 * forward_flops_per_token(**shape)
