"""Operations and bytes of a decoder-hybrid-decoder (selective scans and
differential attention over windows in a first decoder, ONE full layer
whose pool the cross layers of a second decoder read, gated memory units
between them), from its shapes and the program's counters alone. Beside
`flops.py` and its siblings, which the add-only rule keeps as they are;
same rule as there: what the ALGORITHM needs, never what an
implementation happens to do (a K or V row is counted ONCE a call though
two softmaxes read it; the rows of a window's oldest page that lie behind
the window are not counted).
"""

from __future__ import annotations


def _paged_diff(rows, calls, layers, slots, heads, kv_heads, head_dim,
                dtype_bytes):
    """(flops, bytes) of differential paged attention calls: each live
    row's K and V (`kv_heads` heads of `head_dim` each) read once a
    layer; each of the `heads` query heads scores it against ONE K head
    (2 D FLOPs) and takes its value of BOTH V heads of its pair (2 x 2 D
    FLOPs); the queries are read and the differences (heads / 2 rows of
    2 D) written."""
    rows = float(rows) * layers
    flops = 6.0 * rows * heads * head_dim
    nbytes = dtype_bytes * (
        rows * 2.0 * kv_heads * head_dim
        + float(calls) * layers * slots * 2.0 * heads * head_dim)
    return flops, nbytes


def paged_diff(*, context_tokens, full_layers, reader_layers, calls, slots,
               heads, kv_heads, head_dim, dtype_bytes=4, **_):
    """The calls over the full pool, its writer's and its other
    readers': every live row (`context_tokens`: the contexts summed over
    slots and steps) once a call."""
    return _paged_diff(context_tokens, calls, full_layers + reader_layers,
                       slots, heads, kv_heads, head_dim, dtype_bytes)


def paged_diff_window(*, window_rows, window_layers, calls, slots, heads,
                      kv_heads, head_dim, dtype_bytes=4, **_):
    """The window layers' calls: every row inside a slot's window
    (`window_rows`: min(context, window) summed over slots and steps)
    once a window layer."""
    return _paged_diff(window_rows, calls, window_layers, slots, heads,
                       kv_heads, head_dim, dtype_bytes)


def state_update_bytes(*, state_slot_steps, ssm_inner, ssm_state, conv_taps,
                       dtype_bytes=4, **_):
    """Bytes the scans' states cost the steps of a window: every live
    slot's state of every state layer (`state_slot_steps`,
    `pt_decode_state_slot_steps_total`) read once and written once: the
    [d_state, d_inner] matrix and the convolution's taps - 1 rows. (Its
    operations, a handful a float, are nothing beside its bytes.)"""
    return dtype_bytes * 2.0 * float(state_slot_steps) * ssm_inner * (
        ssm_state + conv_taps - 1)


def decode_weight_bytes(*, decode_steps, d_model, d_ff, vocab, n_heads,
                        n_kv_heads, head_dim, state_layers, window_layers,
                        full_layers, reader_layers, gmu_layers, ssm_inner,
                        ssm_state, ssm_dt_rank, conv_taps, dtype_bytes=4,
                        **_):
    """Weight bytes the decode steps of a window must read at least once
    a step: every layer's gated FFN (3 d f) and its two norms; a scan
    layer's in-, x-, dt- and out-projections, taps, biases, A_log and
    D_skip; a self-attention layer's four projections with their biases,
    the four lambda vectors and the sub-norm; a cross layer's q and out
    alone; a gated memory unit's two; once, the tied head (the
    embedding's table) and its norm. The embedding rows a step gathers,
    the caches and the states it reads are not weights: a floor."""
    layers = state_layers + window_layers + full_layers + reader_layers \
        + gmu_layers
    q = d_model * n_heads * head_dim + n_heads * head_dim
    kv = d_model * n_kv_heads * head_dim + n_kv_heads * head_dim
    out = n_heads * head_dim * d_model + d_model
    small = 4.0 * head_dim + 2.0 * head_dim
    scan = d_model * 2.0 * ssm_inner + conv_taps * ssm_inner + ssm_inner \
        + ssm_inner * (ssm_dt_rank + 2.0 * ssm_state) \
        + ssm_dt_rank * ssm_inner + ssm_inner + ssm_inner * ssm_state \
        + ssm_inner + ssm_inner * d_model
    mixers = state_layers * scan \
        + (window_layers + full_layers) * (q + 2.0 * kv + out + small) \
        + reader_layers * (q + out + small) \
        + gmu_layers * 2.0 * d_model * ssm_inner
    every = layers * (3.0 * d_model * d_ff + 4.0 * d_model)
    head = d_model * vocab + 2.0 * d_model
    return dtype_bytes * float(decode_steps) * (mixers + every + head)


def decode_bytes(*, pool_rows_read_writer, pool_rows_read_readers,
                 window_rows_read, n_kv_heads, head_dim, dtype_bytes=4,
                 **sizes):
    """The parts of the least bytes the decode steps of a window must
    move: {"shared": the full pool's rows as its writer and its other
    readers read them, "window": the rows inside the windows (both
    counters summed over slots AND layers already), "state": the scans'
    states, "weights": `decode_weight_bytes`}."""
    row = dtype_bytes * 2.0 * n_kv_heads * head_dim
    return {
        "shared": row * (float(pool_rows_read_writer)
                         + float(pool_rows_read_readers)),
        "readers": row * float(pool_rows_read_readers),
        "window": row * float(window_rows_read),
        "state": state_update_bytes(dtype_bytes=dtype_bytes, **sizes),
        "weights": decode_weight_bytes(
            n_kv_heads=n_kv_heads, head_dim=head_dim,
            dtype_bytes=dtype_bytes, **sizes)}


# -- the whole step's least (`flops.py` has the two functions' text) --------

def decode_least_bytes(counts, **model):
    """`decode_bytes`' parts: the full pool's rows as its writer and its
    readers read them and the windows' rows (the program's own row
    counters), the scans' states, the weights."""
    parts = decode_bytes(
        pool_rows_read_writer=counts["pool_rows_read_writer"],
        pool_rows_read_readers=counts["pool_rows_read_readers"],
        window_rows_read=counts["window_rows_read"],
        state_slot_steps=counts["state_slot_steps"],
        decode_steps=counts["decode_steps"], **model)
    return {"weights": parts["weights"],
            "cache": parts["shared"] + parts["window"],
            "states": parts["state"]}


def pass_weight_bytes(*, d_model, vocab, dtype_bytes=4, **model):
    return {"always": decode_weight_bytes(
                decode_steps=1, d_model=d_model, vocab=vocab,
                dtype_bytes=dtype_bytes, **model),
            "head": dtype_bytes * (d_model * vocab + 2.0 * d_model),
            "expert": 0.0, "routed": 0}
