"""Operations and bytes of granite-4.0-h-micro (Mamba-2 layers at ONE
group whose state is a matrix a head, attention layers with 4 query heads
a K/V head of 64, a dense gated FFN in every layer, a tied head), from
its shapes and the program's counters alone. Beside `flops.py` and its
siblings, which the add-only rule keeps as they are; same rule as there:
what the ALGORITHM needs, never what an implementation happens to do.

Three widths of a float: a MATRIX as the configuration serves it
(`dtype_bytes`: 2 at `serving.weight_dtype` bfloat16), a state's
(`state_dtype_bytes`) and a pool's (`cache_dtype_bytes`), 4 each; the
small parameters (norm gains, the scans' vectors, the taps) are float32
whatever the matrices are.
"""

from __future__ import annotations

# (flops, bytes) of the attention layers' paged attention calls: every
# live row read once a layer, its K and V of `kv_heads` heads of
# `head_dim`, each of the `heads` query heads scoring it and taking its
# value: `flops_swa.py`'s count of a full layer, at this model's widths
# (live rows x 4,096 B a layer), under this module's name
from flops_swa import paged_full  # noqa: E402,F401

# (flops, bytes) of the state update's calls: each live slot's matrix
# read once and written once a Mamba-2 layer and step (`live_slot_steps`:
# live slots summed over the traced steps AND the 36 state layers), 5
# FLOPs a float: the bytes bound it. `flops_nemotron3.py`'s count (the
# same kernel, the same [64, 64, 128] float32 state a slot and layer),
# under this module's name
from flops_nemotron3 import ssd_update  # noqa: E402,F401


def state_update_bytes(*, state_slot_steps, ssm_inner, ssm_state, ssm_groups,
                       conv_taps, state_dtype_bytes=4, **_):
    """Bytes the states cost the steps of a window: every live slot's
    state of every state layer (`state_slot_steps`,
    `pt_decode_state_slot_steps_total`) read once and written once: the
    matrix and the convolution's taps - 1 rows of x, B and C."""
    conv = (conv_taps - 1) * (ssm_inner + 2 * ssm_groups * ssm_state)
    return state_dtype_bytes * 2.0 * float(state_slot_steps) * (
        ssm_inner * ssm_state + conv)


def _pass_weights(*, state_layers, full_layers, d_model, d_ff, n_heads,
                  n_kv_heads, head_dim, ssm_heads, ssm_inner, ssm_state,
                  ssm_groups, conv_taps, **_):
    """(floats of the matrices, floats of the small parameters) one pass
    over the layers reads: a Mamba-2 mixer's in-projection (d x (2 d_i +
    2 G N + H)) and out-projection, its taps, their bias, the three
    vectors a head and the gated norm's gain; an attention mixer's four
    projections; every layer's gated FFN (three matrices of d x d_ff) and
    two norms."""
    width = ssm_inner + 2.0 * ssm_groups * ssm_state
    mamba = d_model * (ssm_inner + width + ssm_heads) + ssm_inner * d_model
    mamba_small = (conv_taps + 1.0) * width + 3.0 * ssm_heads + ssm_inner
    attention = 2.0 * d_model * n_heads * head_dim \
        + 2.0 * d_model * n_kv_heads * head_dim
    ffn = 3.0 * d_model * d_ff
    layers = state_layers + full_layers
    return (state_layers * mamba + full_layers * attention + layers * ffn,
            state_layers * mamba_small + layers * 2.0 * d_model)


def _pass_bytes(*, d_model, vocab, dtype_bytes=4, **model):
    """(bytes of the layers, bytes of the head) a pass reads: the head is
    the embedding's table (tied), read once as a matrix, and its norm."""
    matrices, small = _pass_weights(d_model=d_model, **model)
    return (dtype_bytes * matrices + 4.0 * small,
            dtype_bytes * float(d_model) * vocab + 4.0 * d_model)


def decode_weight_bytes(*, decode_steps, **model):
    """Weight bytes the decode steps of a window must read at least once
    a step: every layer's mixer and FFN, the head and its norm. The
    embedding rows a step gathers, the cache and the states are not
    weights: a floor."""
    return float(decode_steps) * sum(_pass_bytes(**model))


def decode_kv_bytes(*, paged_live_pages, block_size, full_layers,
                    n_kv_heads, head_dim, cache_dtype_bytes=4, **_):
    """K/V bytes the decode steps of a window must read: every live page
    of the attention layers (`paged_live_pages`, a layer)."""
    row = cache_dtype_bytes * 2.0 * n_kv_heads * head_dim
    return row * float(paged_live_pages) * block_size * full_layers


def decode_bytes(*, decode_steps, paged_live_pages, state_slot_steps,
                 block_size, **model):
    """The parts of the least bytes the decode steps of a window must
    move: {"weights", "state", "kv"}."""
    return {
        "weights": decode_weight_bytes(decode_steps=decode_steps, **model),
        "state": state_update_bytes(state_slot_steps=state_slot_steps,
                                    **model),
        "kv": decode_kv_bytes(paged_live_pages=paged_live_pages,
                              block_size=block_size, **model)}


# -- the whole step's least (`flops.py` has the two functions' text) --------

def decode_least_bytes(counts, **model):
    parts = decode_bytes(
        decode_steps=counts["decode_steps"],
        paged_live_pages=float(counts["live_rows"]) / counts["block_size"],
        state_slot_steps=counts["state_slot_steps"],
        block_size=counts["block_size"], **model)
    return {"weights": parts["weights"], "cache": parts["kv"],
            "states": parts["state"]}


def pass_weight_bytes(**model):
    """A dense model: an admission reads every layer and the head once,
    and has no routed expert."""
    layers, head = _pass_bytes(**model)
    return {"always": layers + head, "head": head, "expert": 0.0,
            "routed": 0}
