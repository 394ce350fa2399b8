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


@pytest.mark.parametrize("cell,product", sorted(_PRODUCTS),
                         ids=lambda v: v)
def test_the_plan_at_every_cells_shapes(cell, product):
    """XLA's weight tile by its own rule; the repo's kernel where that is
    256 KB or less: the Nemotron cell's up product alone, at a decode
    step's rows and at a prefill wave's."""
    rows, k, n, groups = _PRODUCTS[(cell, product)]
    for m in (rows, moe_ops._HELD_WAVE_ROWS, 8192):
        plan = em.expert_matmul_plan(m, k, n, groups, jnp.float32)
        assert (plan.rows, plan.k, plan.n, plan.groups) == (m, k, n, groups)
        if (cell, product) == ("nemotron3", "up") and m <= em._ROWS_MAX:
            assert plan.form == "pallas"
            assert plan.xla_tile_bytes == 128 * 512 * 4
            assert plan.tm == m and k % plan.tk == 0 and n % plan.tn == 0
            assert plan.tk % 128 == 0 and plan.tn >= 512
            assert 1 << 20 <= plan.tk * plan.tn * 4 <= em._TILE_BYTES_MAX
        else:
            assert plan.form == "ragged_dot"
            assert (plan.tk, plan.tn) == (em._xla_tile(k), em._xla_tile(n))
            assert plan.xla_tile_bytes >= 512 << 10 or m > em._ROWS_MAX


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
    16, 64 rows): its up product goes through the kernel (interpreted
    here), its down product through `ragged_dot`, and the layer equals
    the parent's form (`ragged_dot` twice) to the products' rounding."""
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
    assert seen == [(held, d, h)]
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
def test_the_other_configurations_trace_no_kernel(cell, monkeypatch):
    """At the five gated configurations' shapes the plan answers
    `ragged_dot` thrice, on a TPU too: the op's jaxpr holds three
    `ragged_dot`s and no `pallas_call`, and each product left its plan
    in the trace ring."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows, k, n, groups = _PRODUCTS[(cell, "up")]
    tokens = rows // 4
    before = len([e for e in trace.events()
                  if e.get("name") == "expert_matmul_plan"])
    text = _gated_jaxpr(tokens, k, n, groups, 0 if cell == "cmda" else None)
    plans = [e for e in trace.events()
             if e.get("name") == "expert_matmul_plan"][before:]
    assert "pallas_call" not in text
    assert len(re.findall(r"\bragged_dot(_general)?\b", text)) == 3
    assert [p["args"]["form"] for p in plans] == ["ragged_dot"] * 3
    assert {(p["args"]["k"], p["args"]["n"]) for p in plans} \
        == {(k, n), (n, k)}


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
