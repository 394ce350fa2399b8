"""`kernels/paged_attention.py`: the plan that chooses a decode step's
attention kernel and its block, held to the benchmark cells' shapes, and
the arrow between the two kernel modules.

The kernels' interpret-mode parity cases live with the architecture they
were written for (`test_attention.py`, `test_decode.py`, `test_kanana.py`,
`test_keye.py`, `test_cmda.py`, `test_lfm2.py`; ROADMAP D22 folds them
here).

And the walk's byte accounting (`_paged_walk`): the bytes a block waits
for are the bytes it started. The parity cases cannot say so (under
`interpret=True` a wait is a no-op); the TPU interpreter's DMA semaphores
count bytes, so a minimal kernel over the walk runs there."""

import ast
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

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
                 sparse={"kappa": 4.2, "pages_per_block": 32,
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


# ---------------------------------------------------------------------------
# The walk's byte accounting, under the TPU interpreter
# ---------------------------------------------------------------------------

WALK_BS, WALK_W = 8, 128


def _walk_sums(pools, table, lens, *, block_pages, window=None, rows=False,
               interpret):
    """A minimal kernel over `_paged_walk`: out[s] = the sum over the
    pools (pool i weighted i + 1) of the rows of slot s the walk covers:
    its live rows, with `window` its newest `window` alone (the walk
    starts at their page), with `rows` the `lens[s]` rows `table[s]`
    names one by one (`block_size` 1 and a `source`, the sparse kernel's
    row walk)."""
    pltpu = fa.pltpu
    n, s_n = len(pools), lens.shape[0]
    bs = 1 if rows else WALK_BS
    tile = (block_pages, WALK_W) if rows else (block_pages, bs, WALK_W)
    tokens = block_pages * bs

    def kernel(bt_ref, len_ref, *refs):
        hbm, o_ref, bufs = refs[:n], refs[n], refs[n + 1:2 * n + 1]
        sem, next_ref = refs[2 * n + 1:]
        at = jax.lax.broadcasted_iota(jnp.int32, (tokens, 1), 0)

        def first_page(s):
            return jnp.maximum(len_ref[s] - window, 0) // bs

        def begin(s):
            base = 0 if window is None else first_page(s) * bs
            return base, (jnp.zeros((1, WALK_W), jnp.float32),)

        def block_fn(base, b, slot, ctx, state):
            pos = base + b * tokens + at
            live = pos < ctx
            if window is not None:
                live = live & (pos >= ctx - window)
            total, = state
            for i, buf in enumerate(bufs):
                got = jnp.where(live, buf[slot].reshape(tokens, WALK_W), 0.0)
                total = total + (i + 1) * jnp.sum(got, axis=0, keepdims=True)
            return (total,)

        def finish(s, state):
            o_ref[s] = state[0]

        walk = dict(block_size=bs, block_pages=block_pages)
        if rows:
            walk["source"] = lambda pool, row: pool.at[row // WALK_BS,
                                                       row % WALK_BS]
        if window is not None:
            walk["first_page"] = first_page
        pa._paged_walk(bt_ref, len_ref, hbm, bufs, sem, next_ref,
                       begin=begin, block_fn=block_fn, finish=finish, **walk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * n,
        out_specs=pl.BlockSpec((s_n, 1, WALK_W),
                               lambda i, bt, ln: (0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2,) + tile, jnp.float32)] * n + [
            pltpu.SemaphoreType.DMA((n, 2)),
            pltpu.SMEM((s_n,), jnp.int32)])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_n, 1, WALK_W), jnp.float32),
        interpret=interpret)(table, lens, *pools)[:, 0]


def _within(seconds, fn):
    """`fn()` if it returns within `seconds`. A wait for bytes that were
    never started waits for ever, in the interpreter as on the chip: the
    case then fails here and the run goes on."""
    box = {}

    def run():
        try:
            box["out"] = fn()
        except BaseException as e:      # handed to the test's own thread
            box["error"] = e

    thread = threading.Thread(target=run, daemon=True)
    thread.start()
    thread.join(seconds)
    assert not thread.is_alive(), \
        f"no end after {seconds} s: a wait for more bytes than were started"
    if "error" in box:
        raise box["error"]
    return box["out"]


#: name -> (pages a block, lengths, window, row walk)
FULL = 4 * WALK_BS
WALKS = {
    "no_slot_live": (4, [0, 0], None, False),
    "one_row": (4, [1], None, False),
    "one_page": (4, [WALK_BS], None, False),
    "a_full_block": (4, [FULL], None, False),
    "a_full_block_and_a_row": (4, [FULL + 1], None, False),
    "ragged": (4, [5, 0, 2 * FULL + 3, 3 * WALK_BS, 0, 17, 2 * FULL], None,
               False),
    # P no power of two: a full block is two waits a pool
    "blocks_of_six_pages": (6, [6 * WALK_BS, 0, 11 * WALK_BS + 2, 3], None,
                            False),
    # a window's walk starts at the page of its oldest row
    "from_a_first_page": (4, [3, FULL, 2 * FULL + 5, 0, 59], 20, False),
    "the_row_walk": (8, [0, 1, 8, 9, 21], None, True),
}


@pytest.mark.parametrize("n_pools", [1, 2])
@pytest.mark.parametrize("case", sorted(WALKS))
def test_a_block_waits_for_the_bytes_it_started(case, n_pools):
    """Every slot's sum equals its gathered rows', the interpreter sees
    no read of a tile racing a copy into it, and no semaphore is left
    with bytes uncounted (the interpreter raises on one)."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call as tpu
    if not fa._HAS_PLTPU:
        pytest.skip("pallas TPU backend unavailable")
    block_pages, lens, window, rows = WALKS[case]
    rng = np.random.RandomState(len(case) + n_pools)
    lens = np.asarray(lens, np.int32)
    width = max(-(-int(lens.max()) // (1 if rows else WALK_BS)), 1)
    n_blocks = len(lens) * width + 1
    pools = [rng.randint(-8, 9, (n_blocks, WALK_BS, WALK_W)).astype(
        np.float32) for _ in range(n_pools)]
    if rows:    # ids of single rows of the pool seen as [NB * BS, W]
        table = rng.permutation(n_blocks * WALK_BS)[:len(lens) * width]
    else:       # pages in no order, block 0 the null block
        table = rng.permutation(np.arange(1, n_blocks))
    table = table.reshape(len(lens), width).astype(np.int32)

    want = np.zeros((len(lens), WALK_W), np.float32)
    for s, n in enumerate(lens):
        for i, pool in enumerate(pools):
            flat = pool.reshape(-1, WALK_W)
            if rows:
                got = flat[table[s, :n]]
            else:
                got = pool[table[s]].reshape(-1, WALK_W)[:n]
                if window is not None:
                    got = got[max(n - window, 0):]
            want[s] += (i + 1) * got.sum(axis=0)

    params = fa.pltpu.InterpretParams(detect_races=True)
    got = _within(120, lambda: np.asarray(_walk_sums(
        [jnp.asarray(p) for p in pools], jnp.asarray(table),
        jnp.asarray(lens), block_pages=block_pages, window=window,
        rows=rows, interpret=params)))
    np.testing.assert_array_equal(got, want)
    assert not tpu.races.races_found


# A table's ids, as they reach a kernel that checks no bound
# ---------------------------------------------------------------------------

from paddle_tpu.kernels import block_sparse_attention as bsa    # noqa: E402

NB, S, MB = 6, 2, 3        # pool pages, slots, table width


def _f32(*shape):
    return jnp.zeros(shape, jnp.float32)


def _bad(shape):
    """A table with ids on both sides of its pool."""
    ids = np.arange(int(np.prod(shape)), dtype=np.int32).reshape(shape)
    return jnp.asarray(ids * 7 - 9)


#: name -> (the public entry's call with a table of bad ids, the module
#: whose `pallas_call` it makes, what the table's ids have to lie under)
_LENS = jnp.asarray([17, 20], jnp.int32)
TABLES = {
    "per_head": (lambda: pa.paged_decode_attention(
        _f32(S, 4, 128), _f32(NB, 8, 4, 128), _f32(NB, 8, 4, 128),
        _bad((S, MB)), _LENS, interpret=True), NB),
    "grouped": (lambda: pa.paged_decode_attention(
        _f32(S, 8, 128), _f32(NB, 8, 2, 128), _f32(NB, 8, 2, 128),
        _bad((S, MB)), _LENS, interpret=True, window=8), NB),
    "differential": (lambda: pa.paged_diff_attention(
        _f32(S, 8, 64), _f32(NB, 8, 4 * 64), _f32(NB, 8, 4 * 64),
        _bad((S, MB)), _LENS, jnp.float32(0.5), interpret=True), NB),
    "latent": (lambda: pa.paged_latent_decode_attention(
        _f32(S, 4, 256), _f32(NB, 8, 256), _bad((S, MB)), _LENS,
        value_width=128, scale=1.0, interpret=True), NB),
    "index_scores": (lambda: pa.paged_index_scores(
        _f32(S, 4, 128), _f32(S, 4), _f32(NB, 8, 128), _bad((S, MB)),
        _LENS, interpret=True), NB),
    "sparse_rows": (lambda: pa._paged_sparse_attention_pallas(
        _f32(S, 8, 128), _f32(NB, 8, 2, 128), _f32(NB, 8, 2, 128),
        _bad((S, 16)), jnp.asarray([16, 3], jnp.int32), scale=1.0,
        interpret=True), NB * 8),
    "sparse_pages": (lambda: pa._paged_sparse_attention_pallas(
        _f32(S, 8, 128), _f32(NB, 8, 2, 128), _f32(NB, 8, 2, 128),
        _bad((S, MB)), _LENS, jnp.ones((S, MB * 8), bool), scale=1.0,
        interpret=True), NB),
    "sparse_latent_rows": (lambda: pa.paged_sparse_latent_attention(
        _f32(S, 4, 256), _f32(NB, 8, 1, 256), _bad((S, 16)),
        jnp.asarray([16, 3], jnp.int32), value_width=128, scale=1.0,
        interpret=True), NB * 8),
    # an entry is page * H_kv + head, H_kv 2
    "block_sparse": (lambda: bsa.block_sparse_paged_attention(
        _f32(S, 8, 128), _f32(NB, 8, 2 * 128), _f32(NB, 8, 2 * 128),
        _bad((S, 2, MB)), jnp.full((S, 2), 20, jnp.int32),
        interpret=True), NB * 2),
}


@pytest.mark.parametrize("kernel", sorted(TABLES))
def test_a_table_reaches_its_kernel_inside_its_pool(kernel, monkeypatch):
    """The kernels over `_paged_walk` are compiled without Mosaic's
    bounds checks (`_walk_compiler_params`): what keeps a bad id's read
    inside its own pool is `_pool_ids`, outside the kernel, in EVERY
    wrapper. The `pallas_call` is replaced by one that notes the table
    it is given (its first operand) and runs nothing."""
    if not fa._HAS_PLTPU:
        pytest.skip("pallas TPU backend unavailable")
    call, entries = TABLES[kernel]
    seen = []

    def pallas_call(body, *, out_shape, compiler_params=None, **kw):
        assert compiler_params == pa._walk_compiler_params()

        def run(table, *operands):
            seen.append(np.asarray(table))
            return jnp.zeros(out_shape.shape, out_shape.dtype)
        return run

    monkeypatch.setattr(pl, "pallas_call", pallas_call)
    with jax.disable_jit():
        call()
    table, = seen
    assert table.dtype == np.int32
    assert table.min() == 0 and table.max() == entries - 1
