"""The repo's own grouped matmul (`kernels/expert_matmul.py`): the kernel
interpreted against `jax.lax.ragged_dot`, the plan at every MoE cell's
shapes, and `moe_gated_ffn` through it at the Nemotron cell's widths."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import expert_matmul as em
from paddle_tpu.obs import trace
from paddle_tpu.ops import moe_ops


def _operands(m, k, n, groups, seed):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(m, k), jnp.float32),
            jnp.asarray(rng.randn(groups, k, n) / np.sqrt(k), jnp.float32))


def _as_multiplied(a):
    """What the kernel (and XLA's, on the chip, at the default precision)
    multiplies: float32 rounded to bfloat16."""
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def _against_ragged_dot(m, k, n, sizes, tk, tn, seed=0):
    x, w = _operands(m, k, n, len(sizes), seed)
    sz = jnp.asarray(sizes, jnp.int32)
    got = em._expert_matmul_pallas(x, w, sz, tk=tk, tn=tn, interpret=True)
    want = jax.lax.ragged_dot(_as_multiplied(x), _as_multiplied(w), sz,
                              precision=jax.lax.Precision.HIGHEST)
    live = int(sum(sizes))
    assert got.shape == (m, n) and got.dtype == x.dtype
    # the groups' rows to the accumulation's rounding, and nothing but
    # zeros behind them: rows in no group are left finite
    assert np.allclose(got[:live], want[:live], atol=3e-5, rtol=1e-5)
    assert not np.any(np.asarray(got[live:]))
    if live:
        assert float(jnp.std(got[:live])) > 0.3
    return got


#: sizes of groups: none, one row, seven (under a sublane tile), 24, 300
#: (ten row chunks, and no boundary of it on a chunk's), in several orders
@pytest.mark.parametrize("sizes", [
    [0, 1, 7, 24, 300], [300, 24, 7, 1, 0], [7, 0, 0, 300, 1, 24],
    [1, 1, 1, 1, 1, 1, 1, 1], [24, 24, 24], [0, 0, 5, 0]],
    ids=lambda s: "-".join(map(str, s)))
def test_groups_of_every_size_match_ragged_dot(sizes):
    _against_ragged_dot(344, 256, 512, sizes, 128, 512)


@pytest.mark.parametrize("sizes,m", [
    ([30, 5, 33], 72),      # the second group crosses a row chunk's end
    ([3, 61], 64),          # the last chunk is pulled back inside the rows
    ([6] * 10, 64),         # groups across every sublane boundary
    ([40, 24], 64)],        # the rows are full: no row in no group
    ids=["crosses_a_chunk", "last_chunk_pulled_back", "sublanes", "full"])
def test_a_group_that_crosses_a_row_chunk(sizes, m):
    _against_ragged_dot(m, 128, 512, sizes, 128, 512, seed=3)


def test_rows_behind_the_groups_and_rows_in_no_group():
    """Rows behind `sum(sizes)` (the pairs on experts this chip does not
    hold) are written zeros, with garbage in them or not; with every row
    in no group nothing is multiplied at all."""
    x, w = _operands(48, 128, 512, 4, 9)
    x = x.at[20:].set(jnp.nan)
    got = em._expert_matmul_pallas(x, w, jnp.asarray([8, 0, 12, 0]),
                                   tk=128, tn=512, interpret=True)
    assert np.all(np.isfinite(got)) and not np.any(np.asarray(got[20:]))
    none = em._expert_matmul_pallas(x, w, jnp.zeros((4,), jnp.int32),
                                    tk=128, tn=512, interpret=True)
    assert not np.any(np.asarray(none))


@pytest.mark.parametrize("k,tk,n,tn", [
    (2688, 2688, 512, 512),     # the cell's k, whole
    (2688, 896, 1024, 512),     # ... in three parts, two column tiles
    (1024, 512, 512, 512),      # a k that is a multiple of 512
    (512, 128, 1024, 1024)])
def test_the_contraction_in_parts_and_whole(k, tk, n, tn):
    _against_ragged_dot(40, k, n, [6, 0, 7, 13], tk, tn, seed=k + tk)


def test_an_empty_group_reads_no_weight():
    """A group of no rows is never walked: NaNs in its matrix reach no
    row (the steps behind the live groups name the last live tile)."""
    x, w = _operands(32, 128, 512, 4, 5)
    w = w.at[1].set(jnp.nan).at[3].set(jnp.nan)
    got = em._expert_matmul_pallas(x, w, jnp.asarray([9, 0, 20, 0]),
                                   tk=128, tn=512, interpret=True)
    assert np.all(np.isfinite(got)) and float(jnp.std(got[:29])) > 0.3


def _sparse_sizes(groups, m, seed):
    """128 groups of 0-2 rows as a decode step's pairs fall: live groups
    with empty ones between them, the first and the last empty, and rows
    left behind `sum(sizes)`."""
    rng = np.random.RandomState(seed)
    sizes = rng.randint(0, 3, size=groups)
    sizes[[0, 5, 6, groups - 1]] = 0
    sizes[[4, 7]] = 2
    assert sizes.sum() < m - 8
    return sizes


@pytest.mark.parametrize("m,tk,tn,of", [
    (128, 1024, 768, (2048, 768)),      # Kanana's and Keye's gate and up
    (128, 768, 1024, (768, 2048)),      # ... and down
    (256, 2048, 512, (2048, 1536))],    # LFM2's gate and up
    ids=["1024x768", "768x1024", "2048x512"])
def test_a_decode_steps_groups_at_the_new_tiles(m, tk, tn, of):
    """The three weight tiles the rule of PR 61 brings (ONE tile of each
    a group here, where the cells' `[k, n]` hold two or three), 128
    groups of 0-2 rows: every live group's rows against its own matrix,
    to the accumulation's rounding; zeros behind the groups."""
    assert em._kernel_tile(*of, 4) == (tk, tn)
    groups, k, n = 128, tk, tn
    sizes = _sparse_sizes(groups, m, seed=tk + tn)
    rng = np.random.default_rng(tk)
    x = rng.standard_normal((m, k), np.float32)
    w = rng.standard_normal((groups, k, n), np.float32) / np.float32(
        np.sqrt(k))
    got = np.asarray(em._expert_matmul_pallas(
        jnp.asarray(x), jnp.asarray(w), jnp.asarray(sizes, jnp.int32),
        tk=tk, tn=tn, interpret=True))
    ends = np.cumsum(sizes)
    rounded = np.asarray(_as_multiplied(jnp.asarray(x)), np.float64)
    for g in np.flatnonzero(sizes):
        lo, hi = ends[g] - sizes[g], ends[g]
        want = rounded[lo:hi] @ np.asarray(
            _as_multiplied(jnp.asarray(w[g])), np.float64)
        assert np.allclose(got[lo:hi], want, atol=3e-5, rtol=1e-5), g
    assert np.std(got[:ends[-1]]) > 0.3 and not np.any(got[ends[-1]:])


def test_the_kernel_under_a_derivative_is_ragged_dots():
    """A program that trains experts at rows the plan gives the kernel:
    the forward is the kernel's, the derivative `ragged_dot`'s own (the
    kernel has no transpose), for the rows and for the matrices; the
    sizes take none."""
    x, w = _operands(48, 128, 512, 4, 21)
    sz = jnp.asarray([8, 0, 25, 7], jnp.int32)

    def loss(dot):
        return lambda x, w: jnp.sum(jnp.tanh(dot(x, w)))

    own = jax.grad(loss(lambda x, w: em._expert_matmul_own(
        x, w, sz, 128, 512, True)), (0, 1))(x, w)
    # the same cotangent through XLA's transposes: tanh' at the kernel's
    # own forward, which rounds its operands to bfloat16
    out = em._expert_matmul_pallas(x, w, sz, tk=128, tn=512, interpret=True)
    _, transposes = jax.vjp(lambda x, w: jax.lax.ragged_dot(x, w, sz), x, w)
    want = transposes(1 - jnp.tanh(out) ** 2)
    for got, ref in zip(own, want):
        assert got.shape == ref.shape and float(jnp.std(ref)) > 0
        assert np.allclose(got, ref, atol=1e-5, rtol=1e-5)
    assert not np.any(np.asarray(own[0][40:]))
    assert not np.any(np.asarray(own[1][1]))


# ---------------------------------------------------------------------------
# the row-tiled form and its two transposes
# ---------------------------------------------------------------------------

def _poisoned():
    """The TPU interpreter with every buffer it allocates (a call's output
    among them) filled with NaN: "written zeros" is then what the kernel
    wrote, not what the buffer held (a kernel that writes nothing returns
    NaN under it: the control in `test_the_tiled_forms_output_is_its_own`)."""
    return em.pltpu.InterpretParams(uninitialized_memory="nan")


#: rows of a row tile and of a chunk here: tiles of 64 rows in chunks of
#: 16 (a bfloat16 tile's sublanes), where the cell's are 2,048 in 128
_TM, _CHUNK = 64, 16


def _tiled_against_ragged_dot(m, k, n, sizes, dtype=jnp.float32, seed=0):
    """The product, dx and dW of the row-tiled form, interpreted over
    NaN-filled buffers, against `ragged_dot` and its `jax.vjp` on the
    operands as the kernels multiply them (rounded to bfloat16), the
    cotangent's rows behind the groups zeroed for the reference (XLA's
    transposes read them; the module's never do: they are NaN here)."""
    rng = np.random.RandomState(seed)
    groups = len(sizes)
    x = jnp.asarray(rng.randn(m, k), dtype)
    w = jnp.asarray(rng.randn(groups, k, n) / np.sqrt(k), dtype)
    dy = jnp.asarray(rng.randn(m, n), dtype)
    sz = jnp.asarray(sizes, jnp.int32)
    live = int(sum(sizes))
    behind = (jnp.arange(m) >= live)[:, None]
    f32 = lambda a: a.astype(jnp.float32)                      # noqa: E731
    want, transposes = jax.vjp(lambda x, w: jax.lax.ragged_dot(
        x, w, sz, precision=jax.lax.Precision.HIGHEST),
        _as_multiplied(x), _as_multiplied(w))
    want_dx, want_dw = transposes(jnp.where(behind, 0, _as_multiplied(dy)))
    # what lies behind the groups may be anything
    x, dy = (jnp.where(behind, jnp.nan, a) for a in (x, dy))
    tiles = dict(tm=_TM, chunk=_CHUNK, interpret=_poisoned())
    got = em._expert_matmul_tiled(x, w, sz, **tiles)
    dx = em._expert_matmul_tiled(dy, w, sz, transposed=True, **tiles)
    dw = em._expert_matmul_dw(x, dy, sz, **tiles)
    assert (got.shape, got.dtype) == ((m, n), dtype)
    assert (dx.shape, dx.dtype) == ((m, k), dtype)
    assert (dw.shape, dw.dtype) == ((groups, k, n), jnp.float32)
    # float32: the accumulation's rounding; bfloat16: the result's
    close = dict(atol=3e-5, rtol=1e-5) if dtype == jnp.float32 \
        else dict(atol=2e-2, rtol=1e-2)
    assert np.allclose(f32(got[:live]), want[:live], **close)
    assert np.allclose(f32(dx[:live]), want_dx[:live], **close)
    assert np.allclose(dw, want_dw, atol=1e-4 * np.sqrt(max(live, 1)),
                       rtol=1e-5)
    # rows in no group, and groups of no rows: zeros, written
    assert not np.any(np.asarray(f32(got[live:])))
    assert not np.any(np.asarray(f32(dx[live:])))
    assert not np.any(np.asarray(dw)[np.asarray(sizes) == 0])
    if live:
        assert float(jnp.std(f32(got[:live]))) > 0.3
        assert float(jnp.std(dw[int(np.argmax(sizes))])) > 0.3


@pytest.mark.parametrize("sizes", [
    [200, 56],                      # a group larger than a row tile (three)
    [5, 9, 17, 3, 20, 1, 6, 3],     # several groups inside one tile
    [0, 70, 0, 0, 90, 30, 0],       # empty groups first, middle and last
    [0, 0, 0, 0],                   # all groups empty
    [30, 0, 41],                    # whole tiles behind `sum(sizes)`
    [64, 64, 64, 64],               # every group a tile: no shared tile
    [63, 1, 64, 127, 1]],           # ends one row beside a tile's
    ids=["over_a_tile", "inside_a_tile", "empty_groups", "all_empty",
         "dead_tiles", "tile_aligned", "one_row_off"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_tiled_form_and_its_transposes_match_ragged_dots(sizes, dtype):
    _tiled_against_ragged_dot(256, 128, 256, sizes, dtype)


@pytest.mark.parametrize("k,n,dtype", [
    (384, 128, jnp.bfloat16),   # Mellum's gate and up (2,304 x 896), cut
    (128, 384, jnp.bfloat16),   # ... and down
    (256, 384, jnp.float32)],   # a serve bucket's float32 matrices
    ids=["384x128", "128x384", "256x384_float32"])
def test_the_tiled_form_at_the_cells_width_pairs(k, n, dtype):
    _tiled_against_ragged_dot(192, k, n, [50, 0, 100, 9], dtype, seed=k)


def test_the_tiled_forms_output_is_its_own():
    """The control of the poisoned buffers: under the same interpreter a
    kernel that writes nothing returns NaN, so the zeros above are the
    kernels' own; and the visit table walks every row tile (or group)
    once for each group (tile) it shares rows with, `m / tm + groups - 1`
    visits whatever the sizes."""
    nothing = em.pl.pallas_call(
        lambda x_ref, o_ref: None, interpret=_poisoned(),
        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32))(
            jnp.ones((8, 128)))
    assert np.all(np.isnan(nothing))
    sizes = jnp.asarray([0, 70, 0, 0, 90, 30, 0], jnp.int32)
    tile, gid, lo, hi, first = (np.asarray(t) for t in em._visit_table(
        sizes, 256, 64, "tile"))
    assert len(tile) == 256 // 64 + 7 - 1
    walked = [(t, g, a, b) for t, g, a, b in zip(tile, gid, lo, hi) if b > a]
    assert walked == [(0, 1, 0, 64), (1, 1, 0, 6), (1, 4, 6, 64),
                      (2, 4, 0, 32), (2, 5, 32, 62)]
    # the tile behind the groups once, with no rows; then it again
    assert list(tile[5:]) == [3] * 5 and list(first[5:]) == [1, 0, 0, 0, 0]
    assert list(first[:5]) == [1, 1, 0, 1, 0]
    tile, gid, lo, hi, first = (np.asarray(t) for t in em._visit_table(
        sizes, 256, 64, "group"))
    assert list(gid) == [0, 1, 1, 2, 3, 4, 4, 5, 6, 6]
    assert list(first) == [1, 1, 0, 1, 1, 1, 0, 1, 1, 0]
    assert [(a, b) for a, b in zip(lo, hi) if b > a] == [
        (0, 64), (0, 6), (6, 64), (0, 32), (32, 62)]


def _rounded_ragged_dot():
    """`ragged_dot` as the module's kernels multiply, under a derivative
    too: operands and cotangent rounded to bfloat16, float32 sums."""
    def dot(x, w, sizes):
        return jax.lax.ragged_dot(x, w, sizes,
                                  precision=jax.lax.Precision.HIGHEST)

    @jax.custom_vjp
    def rounded(x, w, sizes):
        return dot(_as_multiplied(x), _as_multiplied(w), sizes)

    def bwd(saved, g):
        x, w, sizes = saved
        return (*jax.vjp(lambda x, w: dot(x, w, sizes), _as_multiplied(x),
                         _as_multiplied(w))[1](_as_multiplied(g)), None)

    rounded.defvjp(lambda x, w, s: (rounded(x, w, s), (x, w, s)), bwd)
    return rounded


def test_a_trained_shares_gradients_through_the_tiled_form(monkeypatch):
    """`jax.grad` through `_held_sum` at a wave of 4,096 rows (2,048
    tokens x top-2, experts 2-5 of 8 held): every product of the wave and
    of its backward is the tiled form's (twelve plans and transposes in
    the ring, none `ragged_dot`), and the sum and its five gradients are
    `ragged_dot`'s on the same rounded operands, within what
    `tests/test_mellum2.py` holds a share to (2e-5; there in absolute
    terms at 24 rows, here relative to each gradient's largest entry)."""
    rng = np.random.RandomState(63)
    n, d, h, e, top_k, first, count = 2048, 128, 128, 8, 2, 2, 4
    xt = jnp.asarray(rng.randn(n, d), jnp.float32)
    experts = jnp.asarray(np.stack(
        [rng.permutation(e)[:top_k] for _ in range(n)]), jnp.int32)
    gates = jnp.asarray(rng.rand(n, top_k), jnp.float32)
    wg, wu = (jnp.asarray(rng.randn(count, d, h) * 0.1, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.randn(count, h, d) * 0.1, jnp.float32)
    w = jnp.asarray(rng.randn(n, d), jnp.float32)
    assert moe_ops._held_grad_rows(n, top_k, count, e) == 4096

    def held(xt, gates, wg, wu, wd):
        return jnp.sum(w * moe_ops._experts_held_trained(
            xt, experts, gates, wg, wu, wd, first, e)[0])

    args = (xt, gates, wg, wu, wd)
    monkeypatch.setattr(moe_ops.expert_matmul, "expert_matmul",
                        _rounded_ragged_dot())
    want = jax.value_and_grad(held, (0, 1, 2, 3, 4))(*args)
    monkeypatch.undo()
    own = em.expert_matmul
    monkeypatch.setattr(moe_ops.expert_matmul, "expert_matmul",
                        lambda x, w, s: own(x, w, s, interpret=True))
    before = len(_plan_records())
    got = jax.value_and_grad(held, (0, 1, 2, 3, 4))(*args)
    records = [r["args"] for r in _plan_records()[before:]]
    assert [r["product"] for r in records] == ["product"] * 6 \
        + ["dx", "dw"] * 3
    assert {r["form"] for r in records} == {"tiled"}
    assert {(r["tm"], r["chunk"], r["visits"]) for r in records} == {
        (2048, em._TILED_CHUNK, 4096 // 2048 + 3)}
    assert abs(float(got[0] - want[0])) <= 1e-4 * abs(float(want[0]))
    for g, r in zip(got[1], want[1]):
        scale = max(1.0, float(jnp.max(jnp.abs(r))))
        off = np.abs(np.asarray(g - r)) / scale
        # an entry in a hundred (a matrix's: a sum over a thousand rows)
        # may hold a row where a sum of another order rounds an operand
        # to the next bfloat16 (the two forms add a row's terms in
        # different orders): under one such step
        assert float(jnp.std(r)) > 0 and np.median(off) < 1e-6
        assert np.mean(off > 2e-5) < 1e-2 and np.max(off) < 2.0 ** -8


@pytest.mark.parametrize("without", ["dx", "dw"])
def test_a_transpose_without_a_row_tile_is_ragged_dots(without, monkeypatch):
    """A matrix that fits VMEM beside the product's row tiles may not fit
    as dW's float32 block (12 bytes an entry for the product's 4-10: a
    float32 `[2,048, 2,048]`): that transpose alone is `ragged_dot`'s,
    says so in its record, and the gradients are the same."""
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(4096, 128), jnp.float32)
    w = jnp.asarray(rng.randn(3, 128, 128) / 11, jnp.float32)
    sz = jnp.asarray([1500, 0, 2000], jnp.int32)

    def loss(x, w):
        out = em._expert_matmul_tiled_own(x, w, sz, True)
        return jnp.sum(jnp.where((jnp.arange(4096) < 3500)[:, None],
                                 jnp.tanh(out), 0.0))

    want = jax.grad(loss, (0, 1))(x, w)
    rule = em._tiled_rows
    monkeypatch.setattr(em, "_tiled_rows", lambda product, *a: None
                        if product == without else rule(product, *a))
    before = len(_plan_records())
    got = jax.grad(loss, (0, 1))(x, w)
    assert {r["args"]["product"]: r["args"]["form"]
            for r in _plan_records()[before:]} == {
        "dx": "tiled", "dw": "tiled", without: "ragged_dot"}
    for g, r in zip(got, want):
        assert float(jnp.std(r)) > 0
        assert np.allclose(g, r, atol=2e-2 if without == "dx" else 2e-1,
                           rtol=2e-2)


def _plan_records():
    return [e for e in trace.events()
            if e.get("name") == "expert_matmul_plan"]


# ---------------------------------------------------------------------------
# the plan
# ---------------------------------------------------------------------------

#: (cell, product): rows of a decode step, k, n, groups of each grouped
#: product the six MoE configurations run (`tools/expert_matmul_sweep.py`
#: has the same shapes): the expert widths as stored
_PRODUCTS = {
    ("olmoe", "gate"): (128, 2048, 1024, 64),
    ("olmoe", "up"): (128, 2048, 1024, 64),
    ("olmoe", "down"): (128, 1024, 2048, 64),
    ("kanana", "gate"): (96, 2048, 768, 128),
    ("kanana", "up"): (96, 2048, 768, 128),
    ("kanana", "down"): (96, 768, 2048, 128),
    ("keye", "gate"): (128, 2048, 768, 128),
    ("keye", "up"): (128, 2048, 768, 128),
    ("keye", "down"): (128, 768, 2048, 128),
    ("cmda", "gate"): (96, 4096, 4096, 8),
    ("cmda", "up"): (96, 4096, 4096, 8),
    ("cmda", "down"): (96, 4096, 4096, 8),
    ("lfm2", "gate"): (256, 2048, 1536, 64),
    ("lfm2", "up"): (256, 2048, 1536, 64),
    ("lfm2", "down"): (256, 1536, 2048, 64),
    ("nemotron3", "up"): (768, 2688, 2048, 32),
    ("nemotron3", "down"): (768, 2048, 3072, 32),
}


#: the kernel's weight tile at each product's `[k, n]` (`_kernel_tile`: n
#: in the wider of 1,024 / 512 columns that divides it, 768 whole; k in
#: the fewest parts that keep a float32 tile under 4 MB): the best tile
#: of the chip's sweep at every one (PERF.md section 6, PR 55 and PR 61)
_TILES = {(2048, 1024): (1024, 1024), (1024, 2048): (1024, 1024),
          (2048, 768): (1024, 768), (768, 2048): (768, 1024),
          (4096, 4096): (1024, 1024),
          (2048, 1536): (2048, 512), (1536, 2048): (768, 1024),
          (2688, 2048): (896, 1024), (2048, 3072): (1024, 1024)}


def _form_at(cell, m):
    """What the rule answers for a cell's products at m rows: the repo's
    kernel up to `_ROWS_MAX` rows where XLA's weight tile is 512 KB or
    less (Kanana, Keye; Nemotron's up product at 256 KB) or the rows are
    256 or more, but for a call that would ask for more VMEM than the
    widest measured (Command A+'s held wave: 2,048 rows of 4,096); over
    `_ROWS_MAX` its row-tiled form, but where a matrix does not fit the
    same VMEM whole (Command A+'s 64 MB; Nemotron's two, 22 and 25 MB)."""
    if m > em._ROWS_MAX:
        return "ragged_dot" if cell in ("cmda", "nemotron3") else "tiled"
    if cell == "cmda" and m == em._ROWS_MAX:
        return "ragged_dot"
    small_tile = cell in ("kanana", "keye")
    return "pallas" if small_tile or m >= 256 else "ragged_dot"


@pytest.mark.parametrize("cell,product", sorted(_PRODUCTS),
                         ids=lambda v: v)
def test_the_plan_at_every_cells_shapes(cell, product):
    """XLA's weight tile by its own rule; the repo's kernel where that is
    512 KB or less or the rows are 256 or more, at a decode step's rows
    and at a prefill wave's 2,048, as the parent answered them: Kanana's
    and Keye's three products and LFM2's from a step's rows on, Nemotron's
    two, OLMoE's at 2,048 rows alone (its shortest bucket), Command A+'s
    nowhere (96 rows of 1 MB tiles; 102 MB of VMEM at its wave). At a
    bucket's 8,192 rows the row-tiled form for the four configurations
    that put a bucket's pairs through in one product (OLMoE, Kanana,
    Keye, LFM2: row tiles of 1,024 or 2,048, k and n whole), XLA's still
    for the two that hold a share by waves of 2,048 and never ask."""
    rows, k, n, groups = _PRODUCTS[(cell, product)]
    xla_bytes = em._xla_tile(k) * em._xla_tile(n) * 4
    assert xla_bytes == {"kanana": 512 << 10, "keye": 512 << 10}.get(
        cell, 256 << 10 if (cell, product) == ("nemotron3", "up")
        else 1 << 20)
    for m in (rows, moe_ops._HELD_WAVE_ROWS, 8192):
        plan = em.expert_matmul_plan(m, k, n, groups, jnp.float32)
        assert (plan.rows, plan.k, plan.n, plan.groups) == (m, k, n, groups)
        assert plan.xla_tile_bytes == xla_bytes
        want = "pallas" if (cell, product) == ("nemotron3", "up") \
            and m <= em._ROWS_MAX else _form_at(cell, m)
        assert plan.form == want, (m, plan)
        if plan.form == "tiled":
            # the widest row tile the VMEM bound admits: 2,048 beside
            # Kanana's and Keye's 6.3 MB matrices, 1,024 beside OLMoE's
            # 8.4 and LFM2's 12.6
            assert (plan.tm, plan.tk, plan.tn) == (
                1024 if cell in ("olmoe", "lfm2") else 2048, k, n)
            assert em._tiled_vmem_bytes(plan.tm, k, n, em._TILED_CHUNK, 4,
                                        4) <= em._VMEM_BYTES_MAX
        elif plan.form == "pallas":
            assert (plan.tm, plan.tk, plan.tn) == (m,) + _TILES[(k, n)]
            assert 1 << 20 <= plan.tk * plan.tn * 4 <= em._TILE_BYTES_MAX
            assert em._vmem_bytes(m, k, plan.tk, plan.tn, 4, 2) \
                <= em._VMEM_BYTES_MAX
        else:
            assert (plan.tk, plan.tn) == (em._xla_tile(k), em._xla_tile(n))


def test_the_plan_refuses_a_call_over_the_widest_measured():
    """The bound on a call's VMEM is what Nemotron's up wave asks
    (`_vmem_bytes` at 2,048 rows of 2,688, `[896, 1,024]` tiles: 78 MB,
    under the 80 MB `tests/test_chip_compile.py` holds it to), from the
    module's own function and no constant a user sets: Command A+'s held
    wave (2,048 rows of 4,096: 102 MB) stays XLA's, the same widths at
    1,024 rows (60 MB) do not."""
    assert em._VMEM_BYTES_MAX == em._vmem_bytes(2048, 2688, 896, 1024, 4, 2)
    assert 72 << 20 < em._VMEM_BYTES_MAX < 80 << 20
    assert em._vmem_bytes(2048, 4096, 1024, 1024, 4, 2) > 96 << 20
    plan = em.expert_matmul_plan
    assert plan(2048, 4096, 4096, 8, jnp.float32).form == "ragged_dot"
    assert plan(1024, 4096, 4096, 8, jnp.float32).form == "pallas"
    assert plan(2048, 2688, 2048, 32, jnp.float32).form == "pallas"


def test_the_plan_reads_shapes_alone():
    """Rows that are no whole sublane tiles, a k that is no whole lane
    tiles and a width no tile of the kernel divides stay XLA's, whatever
    XLA's tile; bfloat16 halves a tile's bytes with the item."""
    plan = em.expert_matmul_plan
    assert plan(768, 2688, 2048, 32, jnp.float32).form == "pallas"
    assert plan(766, 2688, 2048, 32, jnp.float32).form == "ragged_dot"
    assert plan(768, 2688, 1856, 32, jnp.float32).form == "ragged_dot"
    assert plan(768, 2000, 2048, 32, jnp.float32).form == "ragged_dot"
    assert plan(96, 2048, 768, 128, jnp.float32).xla_tile_bytes == 512 << 10
    assert plan(96, 2048, 1024, 128, jnp.bfloat16).xla_tile_bytes \
        == 512 << 10
    # 1 MB tiles: the rows decide, at 256 (and whole sublane tiles still)
    assert plan(248, 2048, 1024, 64, jnp.float32).form == "ragged_dot"
    assert plan(256, 2048, 1024, 64, jnp.float32).form == "pallas"
    assert plan(260, 2048, 1024, 64, jnp.float32).form == "ragged_dot"
    # 512 KB tiles: any rows of whole sublane tiles up to the row bound
    assert plan(8, 2048, 768, 128, jnp.float32).form == "pallas"
    assert plan(2056, 2048, 768, 128, jnp.float32).form == "ragged_dot"


# ---------------------------------------------------------------------------
# the op through the kernel
# ---------------------------------------------------------------------------

def _moe_ins(rng, d, h, d_out, e, held):
    ins = {"RouterW": [rng.randn(d, e)],
           "RouterBias": [0.1 * rng.randn(e)],
           "WUp": [rng.randn(held, d, h) / np.sqrt(d)],
           "WDown": [rng.randn(held, h, d_out) / np.sqrt(h)]}
    return {k: [jnp.asarray(v[0], jnp.float32)] for k, v in ins.items()}


def test_the_two_matrix_op_through_the_kernel_at_the_cells_widths(
        monkeypatch):
    """`moe_gated_ffn` with `expert_form="relu2"` and `first_expert` at
    the cell's widths (2,688 rows by 2,048 stored up, 2,048 by 3,072
    stored down; cut in depth: one layer, 4 held experts of a router of
    16, 64 rows, 256 pairs on the held experts at most): both products go
    through the kernel (interpreted here; the up product by XLA's 256 KB
    tile, the down product by its rows), and the layer equals XLA's form
    (`ragged_dot` twice) to the products' rounding."""
    d, h, d_out, e, held = 2688, 2048, 3072, 16, 4
    rng = np.random.RandomState(55)
    ins = _moe_ins(rng, d, h, d_out, e, held)
    ins["X"] = [jnp.asarray(rng.randn(64, d), jnp.float32)]
    attrs = {"top_k": 6, "router": "sigmoid_bias", "norm_topk": True,
             "routed_scale": 2.5, "expert_form": "relu2", "first_expert": 0}
    seen, entry, kernel = [], em.expert_matmul, em._expert_matmul_pallas

    def interpreted(rows, w, sizes, **kw):
        seen.append(tuple(w.shape))
        return kernel(rows, w, sizes, **kw)

    monkeypatch.setattr(em, "_expert_matmul_pallas", interpreted)
    monkeypatch.setattr(em, "expert_matmul", lambda rows, w, sizes: entry(
        rows, w, sizes, interpret=True))
    got = moe_ops.moe_gated_ffn(None, ins, attrs)
    monkeypatch.undo()
    want = moe_ops.moe_gated_ffn(None, ins, attrs)    # the CPU: ragged_dot
    assert seen == [(held, d, h), (held, h, d_out)]
    out, ref = np.asarray(got["Out"][0]), np.asarray(want["Out"][0])
    assert out.shape == (64, d) and np.std(ref) > 0.05
    # one bfloat16 pass against the CPU's float32 products
    assert np.max(np.abs(out - ref)) < 0.02 * np.max(np.abs(ref))
    assert np.array_equal(got["Stats"][0], want["Stats"][0])
    assert np.array_equal(got["Experts"][0], want["Experts"][0])


def _gated_jaxpr(rows, k, n, groups, first):
    shapes = {"X": (rows, k), "RouterW": (k, 128 if first is not None
                                          else groups),
              "WGate": (groups, k, n), "WUp": (groups, k, n),
              "WDown": (groups, n, k)}
    attrs = {"top_k": 4}
    if first is not None:
        attrs["first_expert"] = first
    names = sorted(shapes)

    def fn(*args):
        out = moe_ops.moe_gated_ffn(
            None, {key: [a] for key, a in zip(names, args)}, attrs)
        return out["Out"][0]

    return str(jax.make_jaxpr(fn)(*[
        jax.ShapeDtypeStruct(shapes[key], jnp.float32) for key in names]))


@pytest.mark.parametrize("cell", ["olmoe", "kanana", "keye", "cmda", "lfm2"])
def test_the_other_configurations_trace_their_plans(cell, monkeypatch):
    """The five gated configurations at a decode step's rows, on a TPU:
    OLMoE and Command A+ (1 MB tiles under 256 rows) trace three
    `ragged_dot`s and no `pallas_call`, the parent's jaxpr; Kanana, Keye
    (512 KB tiles) and LFM2 (256 rows) three `pallas_call`s of the repo's
    kernel and no `ragged_dot`; each product left its plan in the trace
    ring."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows, k, n, groups = _PRODUCTS[(cell, "up")]
    tokens = rows // 4
    before = len([e for e in trace.events()
                  if e.get("name") == "expert_matmul_plan"])
    text = _gated_jaxpr(tokens, k, n, groups, 0 if cell == "cmda" else None)
    plans = [e for e in trace.events()
             if e.get("name") == "expert_matmul_plan"][before:]
    form = _form_at(cell, rows)
    assert form == ("ragged_dot" if cell in ("olmoe", "cmda") else "pallas")
    # a call of the jitted kernel a product (gate and up, of one shape,
    # share one trace of it: two `pallas_call`s are printed for three)
    own = len(re.findall(r"\bname=_expert_matmul_pallas\b", text))
    xla = len(re.findall(r"\bragged_dot(_general)?\b", text))
    assert (own, xla) == ((3, 0) if form == "pallas" else (0, 3))
    assert ("pallas_call" in text) == ("expert_grouped_matmul" in text) \
        == (form == "pallas")
    assert [p["args"]["form"] for p in plans] == [form] * 3
    assert {(p["args"]["k"], p["args"]["n"]) for p in plans} \
        == {(k, n), (n, k)}
    if form == "pallas":
        assert {(p["args"]["tk"], p["args"]["tn"]) for p in plans} \
            == {_TILES[(k, n)], _TILES[(n, k)]}


def test_the_plan_is_left_in_the_trace_ring(monkeypatch):
    """`kernel/expert_matmul_plan`, once a product traced: a record with
    no duration that says what runs and at what tile."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def up(x, w, s):
        return em.expert_matmul(x, w, s)

    text = str(jax.make_jaxpr(up)(
        jax.ShapeDtypeStruct((768, 2688), jnp.float32),
        jax.ShapeDtypeStruct((32, 2688, 2048), jnp.float32),
        jax.ShapeDtypeStruct((32,), jnp.int32)))
    assert "pallas_call" in text and "expert_grouped_matmul" in text
    assert "ragged_dot" not in text
    record = [e for e in trace.events()
              if e.get("name") == "expert_matmul_plan"][-1]
    assert record["cat"] == "kernel" and not record.get("dur")
    plan = em.expert_matmul_plan(768, 2688, 2048, 32, jnp.float32)
    assert record["args"] == plan._asdict()
    assert set(record["args"]) == {"rows", "k", "n", "groups", "form", "tm",
                                   "tk", "tn", "xla_tile_bytes"}
