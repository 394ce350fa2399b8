"""`kernels/paged_attention.py`: the plan that chooses a decode step's
attention kernel and its block, held to the benchmark cells' shapes, and
the arrow between the two kernel modules.

The kernels' interpret-mode parity cases live with the architecture they
were written for (`test_attention.py`, `test_decode.py`, `test_kanana.py`,
`test_keye.py`, `test_cmda.py`, `test_lfm2.py`; ROADMAP D22 folds them
here)."""

import ast

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.kernels import paged_attention as pa

#: the kernel function each of the plan's names stands for
KERNELS = {"per_head": "_paged_kernel", "grouped": "_paged_group_kernel",
           "latent": "_paged_latent_kernel",
           "index_sparse": "_paged_index_kernel"}

#: a family's decode attention at its cell's shapes (PERF.md section 3):
#: the cache as the bundle declares it, query heads, the table's width
#: (max_context / 16), the window; then what the plan has to say
CELLS = {
    "cerebras": dict(kind="kv", rows=[[16, 128]] * 2, heads=16, table=128,
                     slots=16, kernel="per_head", pages=8),
    "olmoe": dict(kind="kv", rows=[[16, 128]] * 2, heads=16, table=256,
                  slots=16, kernel="per_head", pages=8),
    "kanana": dict(kind="latent", rows=[[640]], heads=32, table=640,
                   slots=16, kernel="latent", pages=24),
    "keye": dict(kind="kv_index", rows=[[4, 128], [4, 128], [128]],
                 heads=32, table=480, slots=16, kernel="index_sparse",
                 pages=128, index_heads=16, topk=2048,
                 sparse={"kappa": 1.6, "pages_per_block": 32,
                         "chunk_rows": 128, "heads_per_product": 8,
                         "score_columns_per_block": 512}),
    "cmda_full": dict(kind="kv", rows=[[8, 128]] * 2, heads=128, table=640,
                      slots=12, kernel="grouped", pages=16, group=(16, 256)),
    "cmda_window": dict(kind="kv", rows=[[8, 128]] * 2, heads=128,
                        table=640, slots=12, window=4096, kernel="grouped",
                        pages=16, group=(16, 256)),
    # 8 K/V heads of 64, stored two to a lane tile
    "lfm2": dict(kind="kv", rows=[[4, 128]] * 2, heads=32, table=640,
                 slots=64, head_dim=64, kernel="grouped", pages=32,
                 group=(8, 512)),
}
BLOCK = 16


def _kernel_calls(fn, *args):
    """(kernel function, scope, P) of every Pallas call `fn` traces: the
    function's own name, the named scope a device trace shows the call
    under, and the pages of its first VMEM tile [2, P, ...]."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append((
                    eqn.params["jaxpr"].debug_info.func_name,
                    str(eqn.source_info.name_stack).split("/")[-1],
                    eqn.params["grid_mapping"].scratch_avals[0].shape[1]))
            for value in eqn.params.values():
                inner = getattr(value, "jaxpr", value)
                if hasattr(inner, "eqns"):
                    walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def _traced(c):
    """The family's wrappers traced in interpret mode at the cell's
    shapes (shapes alone: nothing runs)."""
    f32, i32 = jnp.float32, jnp.int32
    sds = jax.ShapeDtypeStruct
    slots, heads = c["slots"], c["heads"]
    tables = sds((slots, c["table"]), i32)
    lens = sds((slots,), i32)
    pool = lambda row: sds((64, BLOCK) + tuple(row), f32)
    if c["kind"] == "latent":
        width = c["rows"][0][0]
        return _kernel_calls(
            lambda *a: pa._paged_latent_attention_pallas(
                *a, value_width=512, scale=0.1, interpret=True),
            sds((slots, heads, width), f32), pool(c["rows"][0]), tables,
            lens)
    d = c.get("head_dim", c["rows"][0][1])
    q = sds((slots, heads, d), f32)
    k_pool = pool(c["rows"][0])
    if c["kind"] == "kv":
        return _kernel_calls(
            lambda *a: pa._paged_attention_pallas(
                *a, scale=0.1, interpret=True, window=c.get("window")),
            q, k_pool, k_pool, tables, lens)
    width = c["rows"][-1][0]
    return _kernel_calls(
        lambda qi, w, ipool, t, n: pa._paged_index_scores_pallas(
            qi, w, ipool, t, n, interpret=True),
        sds((slots, c["index_heads"], width), f32),
        sds((slots, c["index_heads"]), f32), pool(c["rows"][-1]), tables,
        lens) + _kernel_calls(
        lambda *a: pa._paged_sparse_attention_pallas(
            *a, scale=0.1, interpret=True),
        q, k_pool, k_pool, tables, lens,
        sds((slots, c["table"] * BLOCK), jnp.bool_))


@pytest.mark.parametrize("family", sorted(CELLS))
def test_the_plan_names_the_kernel_and_block_of_a_cell(family):
    """`paged_decode_plan` at a cell's shapes names the kernel and the P,
    `heads_per_product` and `score_columns_per_block` PERF.md section 3
    states, and the wrapper traced in interpret mode ran that kernel at
    that block, under the scope the rooflines read by."""
    c = CELLS[family]
    plan = pa.paged_decode_plan(c["kind"], c["rows"], c["heads"], BLOCK,
                                jnp.float32, c["table"], c.get("window"))
    assert plan.kernel == c["kernel"]
    assert plan.pages_per_block == c["pages"]
    assert (plan.heads_per_product, plan.score_columns_per_block) \
        == c.get("group", (None, None))
    assert plan.sparse == c.get("sparse")
    scope = {"latent": "paged_latent_attention",
             "index_sparse": "paged_index_scores"}.get(
        plan.kernel, "paged_window_attention" if c.get("window")
        else "paged_attention")
    ran = [(KERNELS[plan.kernel], scope, plan.pages_per_block)]
    if plan.sparse:     # the attention over the selection: its page walk
        ran.append(("_paged_sparse_kernel", "paged_sparse_attention",
                    plan.sparse["pages_per_block"]))
    assert _traced(c) == ran


def test_an_index_pool_alone_plans_no_sparse_walk():
    """The wrapper of the indexer's scores holds the index pool alone: the
    plan of that row is the index walk's P and says nothing of the
    attention over the selection."""
    plan = pa.paged_decode_plan("kv_index", [[128]], 16, BLOCK, jnp.float32,
                                480)
    assert plan == pa.PagedPlan("index_sparse", 128)


def test_the_arrow_between_the_kernel_modules_points_one_way():
    """`kernels/flash_attention.py` defines no paged or sparse name and
    imports nothing from `kernels/paged_attention.py`; the paged module
    takes the mask value and the guarded `pltpu` from it."""
    tree = ast.parse(open(fa.__file__).read())
    names = {n.name for n in ast.walk(tree)
             if isinstance(n, (ast.FunctionDef, ast.ClassDef))}
    names |= {t.id for n in tree.body if isinstance(n, ast.Assign)
              for t in n.targets if isinstance(t, ast.Name)}
    assert not [n for n in sorted(names | set(vars(fa)))
                if n.lstrip("_").lower().startswith(("paged", "sparse"))]
    imported = [(n.module or "") + "." + a.name for n in ast.walk(tree)
                if isinstance(n, ast.ImportFrom) for a in n.names]
    imported += [a.name for n in ast.walk(tree)
                 if isinstance(n, ast.Import) for a in n.names]
    assert not [m for m in imported if "paged" in m]
    assert pa.DEFAULT_MASK_VALUE is fa.DEFAULT_MASK_VALUE
    assert pa.pltpu is fa.pltpu
