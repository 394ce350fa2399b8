"""Autoregressive decode subsystem (paddle_tpu/serving/decode/): paged
KV cache, continuous batching, eviction/preemption, the two-artifact
export bundle, streaming HTTP, and the Prometheus exposition.

Test planes:
  * kernel — paged attention (gather XLA path + Pallas interpret) vs the
    dense oracle; the paged write primitive;
  * accounting — KVBlockPool alloc/free/defrag, null-block reservation;
  * engine (the headline contract) — continuous-batched paged decode is
    TOKEN-IDENTICAL to a sequential per-sequence reference decode under
    greedy sampling, including sequences admitted mid-flight and
    sequences evicted then resumed; typed shedding on pool exhaustion
    and deadlines; free-on-finish returns every block;
  * front end — streaming NDJSON generate route, prometheus metrics;
  * phase clocks — every phase of the engine in the trace ring without
    PT_TRACE, the sum rules against the scheduler's own clocks, the
    profiler's view (names and nesting), the request's stamps.
"""

import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import io as pio
from paddle_tpu.kernels.flash_attention import mha_reference
from paddle_tpu.kernels.paged_attention import (paged_attention_reference,
                                                paged_decode_attention,
                                                paged_kv_update)
from paddle_tpu.models import transformer as tfm
from paddle_tpu.serving import (DeadlineExceeded, InvalidRequest,
                                Overloaded, ServingEngine)
from paddle_tpu.serving.decode import (DecodeEngine, DecodeModel,
                                       KVBlockPool, PoolExhausted)
from paddle_tpu.obs import trace
from paddle_tpu.obs.metrics import validate_exposition
from paddle_tpu.serving.http import start_http_server
from paddle_tpu.serving.metrics import DECODE_PHASES, render_prometheus


V, L, DM, H, FF, MAXC = 43, 2, 16, 2, 32, 48
BLOCK, POOL, SLOTS = 4, 40, 3
BUCKETS = (8, 16, 32)


# ---------------------------------------------------------------------------
# bundle (module-scoped: exports compile)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def bundle_dir(tmp_path_factory):
    """One tiny trained-init transformer exported as a decode bundle."""
    pt.core.program.reset_unique_names()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        avg, _ = tfm.transformer_lm_loss(
            vocab_size=V, seq_len=MAXC, n_layers=L, d_model=DM,
            n_heads=H, d_ff=FF, max_len=MAXC)
    scope = pt.Scope()
    with pt.scope_guard(scope):
        pt.Executor().run(startup)
        d = str(tmp_path_factory.mktemp("decode") / "m")
        pio.export_decode_model(
            d, dict(vocab_size=V, n_layers=L, d_model=DM, n_heads=H,
                    d_ff=FF, max_context=MAXC),
            scope=scope, length_buckets=BUCKETS, slots=SLOTS,
            block_size=BLOCK, pool_blocks=POOL)
    return d


@pytest.fixture(scope="module")
def reference_decode(bundle_dir):
    """Sequential per-sequence greedy oracle: re-prefill prompt+generated
    each step through the full-attention bucketed artifacts."""
    model = DecodeModel(bundle_dir, warmup=False)

    def decode(prompt, max_new, eos_id=None):
        toks, out = list(prompt), []
        for _ in range(max_new):
            logits, _ = model.prefill(toks)
            t = int(np.argmax(logits))
            out.append(t)
            toks.append(t)
            if eos_id is not None and t == eos_id:
                break
        return out

    return decode


def _prompts(seed, n, lo=2, hi=9):
    rng = np.random.RandomState(seed)
    return [list(int(t) for t in rng.randint(1, V, rng.randint(lo, hi)))
            for _ in range(n)]


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def test_paged_attention_reference_matches_dense():
    """Gather-path paged attention == dense attention per sequence, at
    ragged lengths; the inactive slot (len 0) yields zeros, not NaN."""
    rng = np.random.RandomState(0)
    s, h, d, nb, bs, mb = 3, 2, 8, 10, 4, 4
    import jax.numpy as jnp
    kp = jnp.zeros((nb, bs, h, d), jnp.float32)
    vp = jnp.zeros((nb, bs, h, d), jnp.float32)
    lens = np.array([7, 1, 0], np.int32)
    bt = np.zeros((s, mb), np.int32)
    bt[0, :2] = [3, 5]
    bt[1, 0] = 7
    ks = {i: rng.randn(int(lens[i]), h, d).astype(np.float32)
          for i in range(s)}
    vs = {i: rng.randn(int(lens[i]), h, d).astype(np.float32)
          for i in range(s)}
    for pos in range(int(lens.max())):
        knew = np.zeros((s, h, d), np.float32)
        vnew = np.zeros((s, h, d), np.float32)
        cl = np.zeros(s, np.int32)
        for i in range(s):
            if pos < lens[i]:
                knew[i], vnew[i], cl[i] = ks[i][pos], vs[i][pos], pos + 1
        kp, vp = paged_kv_update(kp, vp, jnp.asarray(knew),
                                 jnp.asarray(vnew), jnp.asarray(bt),
                                 jnp.asarray(cl))
    q = rng.randn(s, h, d).astype(np.float32)
    out = np.asarray(paged_attention_reference(
        jnp.asarray(q), kp, vp, jnp.asarray(bt), jnp.asarray(lens)))
    for i in range(s):
        if lens[i] == 0:
            assert np.all(out[i] == 0)
            continue
        ref = np.asarray(mha_reference(q[None, i:i + 1], ks[i][None],
                                       vs[i][None]))[0, 0]
        np.testing.assert_allclose(out[i], ref, atol=1e-5)


def test_paged_attention_pallas_interpret_parity():
    """The Pallas ragged-paged kernel (interpret mode on CPU) matches the
    gather-path oracle bit-for-tolerance on TPU-legal shapes."""
    rng = np.random.RandomState(1)
    import jax.numpy as jnp
    s, h, d, nb, bs, mb = 2, 2, 128, 6, 8, 3
    kp = jnp.asarray(rng.randn(nb, bs, h, d).astype(np.float32))
    vp = jnp.asarray(rng.randn(nb, bs, h, d).astype(np.float32))
    bt = jnp.asarray(np.array([[1, 2, 0], [4, 0, 0]], np.int32))
    lens = jnp.asarray(np.array([13, 5], np.int32))
    q = jnp.asarray(rng.randn(s, h, d).astype(np.float32))
    ref = np.asarray(paged_attention_reference(q, kp, vp, bt, lens))
    out = np.asarray(paged_decode_attention(q, kp, vp, bt, lens,
                                            interpret=True))
    np.testing.assert_allclose(out, ref, atol=1e-5)


# ---------------------------------------------------------------------------
# KV pool accounting
# ---------------------------------------------------------------------------

def test_kv_pool_alloc_free_defrag():
    pool = KVBlockPool(8, 4)               # blocks 1..7 usable
    assert pool.capacity == 7
    a = pool.alloc(3)
    assert a == [1, 2, 3], "lowest-first allocation is the contract"
    b = pool.alloc(2)
    assert b == [4, 5]
    assert pool.blocks_in_use == 5 and pool.high_water == 5
    pool.free(a)
    assert pool.blocks_free == 5
    # freed low ids are reused first
    assert pool.alloc(1) == [1]
    pool.free([1])
    # null block is never allocatable
    with pytest.raises(PoolExhausted):
        pool.alloc(99)
    with pytest.raises(ValueError):
        pool.free([0])
    # defrag compacts the live tail [4, 5] onto [1, 2]
    mapping = pool.defrag()
    assert mapping == {4: 1, 5: 2}
    assert pool.blocks_in_use == 2 and pool.alloc(1) == [3]


def test_pool_blocks_for_tokens():
    pool = KVBlockPool(8, 4)
    assert [pool.blocks_for_tokens(t) for t in (0, 1, 4, 5, 8)] \
        == [0, 1, 1, 2, 2]


# ---------------------------------------------------------------------------
# bundle layout
# ---------------------------------------------------------------------------

def test_export_bundle_layout(bundle_dir):
    with open(os.path.join(bundle_dir, "serving.json")) as f:
        meta = json.load(f)
    assert [b["length"] for b in meta["buckets"]] == list(BUCKETS)
    for b in meta["buckets"]:
        assert os.path.exists(os.path.join(bundle_dir, b["file"]))
    assert meta["fetch_names"][0] == "logits"
    dec = meta["decode"]
    assert os.path.exists(os.path.join(bundle_dir, dec["file"]))
    assert (dec["slots"], dec["block_size"], dec["pool_blocks"]) \
        == (SLOTS, BLOCK, POOL)
    assert dec["max_blocks_per_seq"] == -(-MAXC // BLOCK)
    names = [m["name"] for m in dec["feeds"]]
    assert names[:3] == ["token_ids", "context_lens", "block_tables"]
    assert names[3:5] == ["k_cache_0", "v_cache_0"]
    assert [m["name"] for m in dec["fetches"]][0] == "logits"
    # pool feeds and fetches agree on the paged shape
    assert dec["feeds"][3]["shape"] == dec["fetches"][1]["shape"] \
        == [POOL, BLOCK, H, DM // H]


# ---------------------------------------------------------------------------
# the headline contract: token-identity vs sequential reference
# ---------------------------------------------------------------------------

def test_continuous_decode_token_identical(bundle_dir, reference_decode):
    """More sequences than slots, mixed lengths: every continuous-batched
    paged generation equals its sequential full-recompute reference, and
    finishing returns every KV block."""
    eng = DecodeEngine(bundle_dir, name="lm")
    try:
        prompts = _prompts(11, 6)
        max_new = [5, 9, 3, 12, 7, 4]
        handles = [eng.generate(p, max_new_tokens=m)
                   for p, m in zip(prompts, max_new)]
        for p, m, hd in zip(prompts, max_new, handles):
            r = hd.result(timeout=120)
            assert r["tokens"] == reference_decode(p, m)
            assert r["finish_reason"] == "length"
        snap = eng.metrics_snapshot()
        assert snap["completed"] == 6
        assert snap["kv_blocks_in_use"] == 0, "free-on-finish leaked"
        assert snap["slot_occupancy"] > 0.5
    finally:
        eng.shutdown()


def test_mid_flight_admission_no_drain_barrier(bundle_dir,
                                               reference_decode):
    """A short sequence submitted while a long one is mid-decode must
    finish BEFORE the long one — only possible if admission goes into
    the in-flight batch (no drain-to-empty barrier) — and still match
    its reference."""
    eng = DecodeEngine(bundle_dir, name="lm")
    try:
        # 29 keeps the reference oracle inside the largest prefill
        # bucket: its last re-prefill is len(prompt) + 28 = 32
        long_p = _prompts(21, 1, 4, 5)[0]
        long_h = eng.generate(long_p, max_new_tokens=29)
        stream = long_h.stream(timeout=60)
        next(stream)                      # the long seq is now in flight
        short_p = _prompts(22, 1, 2, 4)[0]
        short_h = eng.generate(short_p, max_new_tokens=3)
        short_r = short_h.result(timeout=60)
        assert not long_h.done(), \
            "short seq should finish while the long one is still going"
        assert short_r["tokens"] == reference_decode(short_p, 3)
        long_r = long_h.result(timeout=120)
        assert long_r["tokens"] == reference_decode(long_p, 29)
    finally:
        eng.shutdown()


def test_eviction_resume_token_identical(bundle_dir, reference_decode):
    """Pool pressure (restricted accounting) forces preemption; evicted
    sequences resume by re-prefilling prompt+generated and their final
    tokens are identical to the never-evicted reference. Blocks all
    return at the end."""
    eng = DecodeEngine(bundle_dir, name="lm", pool_blocks=9)
    try:
        prompts = _prompts(5, 3, 7, 8)
        handles = [eng.generate(p, max_new_tokens=12, priority=pr)
                   for p, pr in zip(prompts, [1, 0, 0])]
        for p, hd in zip(prompts, handles):
            r = hd.result(timeout=180)
            assert r["tokens"] == reference_decode(p, 12)
        snap = eng.metrics_snapshot()
        assert snap["evictions"] > 0, "pool 8 must force eviction"
        assert snap["resumes"] > 0
        assert snap["kv_blocks_in_use"] == 0
    finally:
        eng.shutdown()


def test_block_reuse_never_leaks_stale_kv(bundle_dir, reference_decode):
    """Back-to-back single sequences reuse the same lowest-first block
    ids; the second sequence's output must be unpolluted by the first's
    stale K/V (every position below a sequence's mask is rewritten by
    its own prefill/decode before any read)."""
    eng = DecodeEngine(bundle_dir, name="lm", pool_blocks=6)
    try:
        a, b = _prompts(31, 2, 6, 8)
        ra = eng.generate(a, max_new_tokens=8).result(timeout=60)
        assert eng.pool.blocks_in_use == 0
        rb = eng.generate(b, max_new_tokens=8).result(timeout=60)
        assert ra["tokens"] == reference_decode(a, 8)
        assert rb["tokens"] == reference_decode(b, 8)
    finally:
        eng.shutdown()


def test_eos_stops_generation(bundle_dir, reference_decode):
    """Declaring the reference's 2nd token as EOS stops generation there
    with finish_reason 'eos' (the EOS token is included)."""
    p = _prompts(41, 1, 5, 6)[0]
    ref = reference_decode(p, 8)
    eos = ref[1]
    eng = DecodeEngine(bundle_dir, name="lm")
    try:
        r = eng.generate(p, max_new_tokens=8, eos_id=eos).result(
            timeout=60)
        assert r["finish_reason"] == "eos"
        assert r["tokens"] == reference_decode(p, 8, eos_id=eos)
        assert r["tokens"][-1] == eos and len(r["tokens"]) < 8
    finally:
        eng.shutdown()


def test_static_mode_matches_but_occupies_less(bundle_dir,
                                               reference_decode):
    """The drain-to-empty baseline is also token-identical (it is the
    same artifacts) but wastes slots on mixed lengths — the occupancy
    gap the `decode` bench config quantifies."""
    prompts = _prompts(51, 6)
    max_new = [3, 12, 3, 12, 3, 12]
    occ = {}
    for mode in (True, False):
        eng = DecodeEngine(bundle_dir, name="lm", continuous=mode)
        try:
            handles = [eng.generate(p, max_new_tokens=m)
                       for p, m in zip(prompts, max_new)]
            for p, m, hd in zip(prompts, max_new, handles):
                assert hd.result(timeout=120)["tokens"] \
                    == reference_decode(p, m)
            occ[mode] = eng.metrics_snapshot()["slot_occupancy"]
        finally:
            eng.shutdown()
    assert occ[True] > occ[False], occ


# ---------------------------------------------------------------------------
# typed shedding
# ---------------------------------------------------------------------------

def test_pool_exhaustion_sheds_typed(bundle_dir):
    """A sequence whose peak KV residency can NEVER fit the pool is pool
    exhaustion by construction: typed, retryable Overloaded at submit."""
    eng = DecodeEngine(bundle_dir, name="lm", pool_blocks=4)
    try:
        with pytest.raises(Overloaded) as ei:
            eng.generate(_prompts(61, 1, 8, 9)[0], max_new_tokens=30)
        assert ei.value.retryable and ei.value.http_status == 429
        assert eng.metrics_snapshot()["shed_overload"] == 1
    finally:
        eng.shutdown()


def test_queue_depth_sheds_typed(bundle_dir):
    eng = DecodeEngine(bundle_dir, name="lm", queue_depth=2)
    try:
        p = _prompts(62, 1, 4, 5)[0]
        eng.generate(p, max_new_tokens=25)
        eng.generate(p, max_new_tokens=25)
        with pytest.raises(Overloaded):
            for _ in range(8):   # the first two may already be running
                eng.generate(p, max_new_tokens=25)
    finally:
        eng.shutdown()


def test_expired_deadline_sheds_typed(bundle_dir):
    """A microscopic deadline expires before the scheduler reaches the
    sequence: DeadlineExceeded surfaces typed — reject-fast at submit
    when admission already sees it expired, else on the handle."""
    eng = DecodeEngine(bundle_dir, name="lm")
    try:
        with pytest.raises(DeadlineExceeded):
            h = eng.generate(_prompts(63, 1, 4, 5)[0], max_new_tokens=20,
                             deadline_ms=0.01)
            h.result(timeout=60)
        assert eng.metrics_snapshot()["shed_deadline"] >= 1
        assert eng.metrics_snapshot()["kv_blocks_in_use"] == 0
    finally:
        eng.shutdown()


def test_invalid_requests_typed(bundle_dir):
    eng = DecodeEngine(bundle_dir, name="lm")
    try:
        with pytest.raises(InvalidRequest):
            eng.generate([], max_new_tokens=4)
        with pytest.raises(InvalidRequest):
            eng.generate([1] * (BUCKETS[-1] + 1), max_new_tokens=4)
        with pytest.raises(InvalidRequest):
            eng.generate([V + 5], max_new_tokens=4)
        with pytest.raises(InvalidRequest):
            eng.generate([1, 2], max_new_tokens=MAXC)
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# defrag: compaction preserves attention outputs
# ---------------------------------------------------------------------------

def test_defrag_preserves_decode(bundle_dir):
    """Drive the DecodeModel by hand: decode a few steps, defrag (pool
    compaction + device permute + table remap), keep decoding — the
    token stream must match an un-defragged run."""
    prompt = _prompts(71, 1, 6, 8)[0]

    def run(defrag_at):
        model = DecodeModel(bundle_dir, warmup=False)
        pool = KVBlockPool(model.pool_blocks, model.block_size)
        # fragment the pool: park an allocation below ours, free later
        parked = pool.alloc(3)
        blocks = pool.alloc(pool.blocks_for_tokens(len(prompt)))
        logits, kv = model.prefill(prompt)
        model.seed_sequence(blocks, kv)
        toks = [int(np.argmax(logits))]
        cached = len(prompt)
        out = []
        for step in range(8):
            if step == defrag_at:
                pool.free(parked)
                mapping = pool.defrag()
                model.permute_blocks(mapping)
                blocks = [mapping.get(b, b) for b in blocks]
            need = pool.blocks_for_tokens(cached + 1) - len(blocks)
            if need > 0:
                blocks.extend(pool.alloc(need))
            tokens = np.zeros(model.slots, np.int64)
            lens = np.zeros(model.slots, np.int32)
            tables = np.zeros((model.slots, model.max_blocks_per_seq),
                              np.int32)
            tokens[0] = toks[-1]
            lens[0] = cached + 1
            tables[0, :len(blocks)] = blocks
            logits = model.decode_step(tokens, lens, tables)
            cached += 1
            toks.append(int(np.argmax(logits[0])))
            out.append(toks[-1])
        return out

    assert run(defrag_at=4) == run(defrag_at=None)


# ---------------------------------------------------------------------------
# admission on the device: jitted prefill + donated seeding scatter
# ---------------------------------------------------------------------------

SENTINEL = 7.0


def write_prefill_pages(pool, block_ids, rows, block_size):
    """The plain reference (the package's former per-pool host path):
    scatter a sequence's prefill K or V rows ([written, H, D]) into its
    blocks of one pool, zero-padded to whole blocks."""
    n = len(block_ids)
    written = rows.shape[0]
    pad = n * block_size - written
    if pad < 0:
        raise ValueError(f"{written} rows exceed {n} blocks x {block_size}")
    if pad:
        rows = np.concatenate(
            [rows, np.zeros((pad,) + rows.shape[1:], rows.dtype)], axis=0)
    pool[list(block_ids)] = rows.reshape((n, block_size) + rows.shape[1:])
    return pool


def _sentinel_model(bundle_dir):
    """A bare model whose pools hold SENTINEL everywhere, so a block
    the seeding should not have touched shows."""
    import jax
    import jax.numpy as jnp
    model = DecodeModel(bundle_dir, warmup=False)
    model._pools = [jax.device_put(jnp.full_like(p, SENTINEL),
                                   model._device) for p in model._pools]
    return model


def _one_shot_rows(model, prompt):
    """The one-shot plane's numpy rows for the same prompt: what
    `execute_batch` returns, at the true length."""
    n = len(prompt)
    ex = {"src_ids": np.asarray(
        prompt, dtype=model.prefill_model.feed_dtypes()["src_ids"]),
          "n_tokens": np.int32(n)}    # the head's one row is n - 1's
    bucket = model.prefill_model.bucket_of(ex)
    out = model.prefill_model.execute_batch(bucket, [ex])[0][0]
    return (bucket, out[model._logits_role][0],
            [(out[k][:n], out[v][:n]) for k, v in model._kv_roles])


def _reference_pools(model, block_ids, kv_rows, skip=0):
    pools = [np.full(p.shape, SENTINEL, np.float32) for p in model._pools]
    nb = skip // model.block_size
    for i, (k_rows, v_rows) in enumerate(kv_rows):
        write_prefill_pages(pools[2 * i], block_ids[nb:], k_rows[skip:],
                            model.block_size)
        write_prefill_pages(pools[2 * i + 1], block_ids[nb:],
                            v_rows[skip:], model.block_size)
    return pools


def _scattered_blocks(n_tokens, seed):
    """Non-contiguous, unordered block ids for a prompt: a scatter that
    wrote block i to position i would pass with range(1, ...)."""
    need = -(-n_tokens // BLOCK)
    return [int(b) for b in np.random.RandomState(seed).permutation(
        np.arange(1, POOL))[:need]]


#: every bucket x a prompt length below, on and across a block boundary,
#: and the bucket's own bound
SEED_LENGTHS = [(8, 3), (8, 4), (8, 5), (8, 8),
                (16, 11), (16, 12), (16, 13), (16, 16),
                (32, 23), (32, 24), (32, 25), (32, 32)]


@pytest.mark.parametrize("bound,n", SEED_LENGTHS)
def test_seeded_pools_equal_the_plain_reference(bundle_dir, bound, n):
    """prefill + seed_sequence leave in every pool, in every block but
    the null block, exactly the bytes the host path left: the one-shot
    plane's numpy rows written by `write_prefill_pages`. The logits row
    is `execute_batch`'s row n-1."""
    model = _sentinel_model(bundle_dir)
    prompt = _prompts(100 + n, 1, n, n + 1)[0]
    blocks = _scattered_blocks(n, seed=n)
    bucket, want_logits, kv_rows = _one_shot_rows(model, prompt)
    assert bucket == bound
    last, kv = model.prefill(prompt)
    assert (kv.n, kv.bound) == (n, bound)
    model.seed_sequence(blocks, kv)
    np.testing.assert_array_equal(np.asarray(last), want_logits)
    want = _reference_pools(model, blocks, kv_rows)
    assert len(model._pools) == len(want) == 2 * L
    for got, ref in zip(model._pools, want):
        np.testing.assert_array_equal(np.asarray(got)[1:], ref[1:])


def test_seeding_leaves_skipped_blocks_untouched(bundle_dir):
    """`skip_rows` block-aligned: the aliased blocks keep their bytes,
    the tail blocks get the tail rows."""
    model = _sentinel_model(bundle_dir)
    prompt = _prompts(131, 1, 14, 15)[0]
    blocks = _scattered_blocks(len(prompt), seed=5)
    _, _, kv_rows = _one_shot_rows(model, prompt)
    _, kv = model.prefill(prompt)
    model.seed_sequence(blocks, kv, skip_rows=2 * BLOCK)
    want = _reference_pools(model, blocks, kv_rows, skip=2 * BLOCK)
    for got, ref in zip(model._pools, want):
        got = np.asarray(got)
        np.testing.assert_array_equal(got[1:], ref[1:])
        assert np.all(got[blocks[:2]] == SENTINEL)
        assert not np.any(got[blocks[2:]] == SENTINEL)


def test_full_match_dispatches_nothing(bundle_dir):
    """A skip that covers the prompt (the partial-tail alias) moves
    nothing and replaces no pool; a partial skip that is not
    block-aligned is refused."""
    model = _sentinel_model(bundle_dir)
    moved = []
    model.count_host_bytes = moved.append
    prompt = _prompts(137, 1, 6, 7)[0]
    _, kv = model.prefill(prompt)
    before, sent = list(model._pools), list(moved)
    model.seed_sequence([1, 2], kv, skip_rows=len(prompt))
    assert all(a is b for a, b in zip(model._pools, before))
    assert moved == sent
    with pytest.raises(ValueError, match="block-aligned"):
        model.seed_sequence([1, 2], kv, skip_rows=BLOCK + 1)
    with pytest.raises(ValueError, match="exceed"):
        model.seed_sequence([1], kv)
    assert all(a is b for a, b in zip(model._pools, before))


# ---------------------------------------------------------------------------
# the decode step: the engine's jitted call, pools donated, in place
# ---------------------------------------------------------------------------

def _seeded(model, prompts, seed):
    """Seed `prompts` into slots 0, 2, ... of a model, each into
    scattered blocks of its own; returns the feeds of their next step
    (every odd slot inactive) and the one-shot plane's K/V rows of each
    prompt with that step's token appended."""
    rng = np.random.RandomState(seed)
    free = list(rng.permutation(np.arange(1, POOL)))
    tokens = np.zeros(model.slots, np.int64)
    lens = np.zeros(model.slots, np.int32)
    tables = np.zeros((model.slots, model.max_blocks_per_seq), np.int32)
    rows = {}
    for slot, prompt in zip(range(0, model.slots, 2), prompts):
        n = len(prompt)
        blocks = [int(free.pop()) for _ in range(n // BLOCK + 1)]
        last, kv = model.prefill(prompt)
        model.seed_sequence(blocks[:-(-n // BLOCK)], kv)
        tokens[slot] = int(np.argmax(np.asarray(last)))
        lens[slot] = n + 1
        tables[slot, :len(blocks)] = blocks
        rows[slot] = _one_shot_rows(model, prompt + [int(tokens[slot])])[2]
    return (tokens, lens, tables), rows


def test_step_donates_the_pools_and_nothing_else(bundle_dir):
    """The pools a step is given are deleted by it and the pools it
    leaves are live; the weights and the feeds survive; the compiled
    step updates exactly the pools' bytes in place."""
    model = _sentinel_model(bundle_dir)
    assert model.step_aliased_bytes is None    # not compiled yet
    feeds, _ = _seeded(model, _prompts(151, 2, 5, 12), seed=1)
    for _ in range(3):
        given = list(model._pools)
        logits = np.asarray(model.decode_step(*feeds))
        assert logits.shape == (SLOTS, V) and np.all(np.isfinite(logits))
        assert all(p.is_deleted() for p in given)
        assert len(model._pools) == 2 * L
        assert not any(p.is_deleted() for p in model._pools)
        assert not any(w.is_deleted() for w in model.weights.values())
    assert model.step_aliased_bytes == sum(p.nbytes for p in model._pools)
    assert model.describe()["step_aliased_bytes"] \
        == model.step_aliased_bytes


def test_step_writes_one_row_a_slot_in_place(bundle_dir):
    """A step's pools against plain numpy: each live slot's new K/V row
    at (table[pos // block], pos % block) of every layer, equal to the
    full-attention prefill's row at that position; every other row of
    every other block keeps its bytes, the sentinel included."""
    model = _sentinel_model(bundle_dir)
    prompts = _prompts(157, 2, 3, 13)
    feeds, rows = _seeded(model, prompts, seed=2)
    before = [np.asarray(p).copy() for p in model._pools]
    model.decode_step(*feeds)
    tokens, lens, tables = feeds
    written = np.zeros(before[0].shape[:2], bool)
    written[0] = True    # the null block: the inactive slot's write
    for slot, kv_rows in rows.items():
        pos = int(lens[slot]) - 1
        blk, off = int(tables[slot, pos // BLOCK]), pos % BLOCK
        assert blk != 0 and not written[blk, off]
        written[blk, off] = True
        for layer, (k_rows, v_rows) in enumerate(kv_rows):
            for pool, want in ((2 * layer, k_rows), (2 * layer + 1, v_rows)):
                got = np.asarray(model._pools[pool])[blk, off]
                assert np.all(before[pool][blk, off] == SENTINEL) \
                    or np.all(before[pool][blk, off] == 0)
                np.testing.assert_allclose(got, want[pos], atol=2e-5)
    assert written.sum() == BLOCK + len(prompts)
    for got, was in zip(model._pools, before):
        got = np.asarray(got)
        np.testing.assert_array_equal(got[~written], was[~written])
        assert np.all(np.isfinite(got[0]))


def test_warmed_model_then_a_real_sequence(bundle_dir, reference_decode):
    """The warm-up keeps the pools its all-inactive step returns (the
    ones it was given are gone), builds the step's one executable, and
    a real sequence after it decodes as the oracle does."""
    from paddle_tpu.obs.metrics import XLA_COMPILES
    model = DecodeModel(bundle_dir, warmup=True)
    assert not any(p.is_deleted() for p in model._pools)
    assert model.step_aliased_bytes == sum(p.nbytes for p in model._pools)
    for p in model._pools:    # only the null block was written
        assert not np.any(np.asarray(p)[1:])
    compiles = XLA_COMPILES.count
    prompt = _prompts(163, 1, 9, 10)[0]
    blocks = list(range(3, 3 + MAXC // BLOCK))
    logits, kv = model.prefill(prompt)
    model.seed_sequence(blocks[:-(-len(prompt) // BLOCK)], kv)
    toks, cached = [int(np.argmax(np.asarray(logits)))], len(prompt)
    tokens = np.zeros(SLOTS, np.int64)
    lens = np.zeros(SLOTS, np.int32)
    tables = np.zeros((SLOTS, model.max_blocks_per_seq), np.int32)
    tables[1, :len(blocks)] = blocks
    for _ in range(6):
        tokens[1], lens[1] = toks[-1], cached + 1
        toks.append(int(np.argmax(model.decode_step(tokens, lens,
                                                    tables)[1])))
        cached += 1
    assert XLA_COMPILES.count == compiles
    assert toks == reference_decode(prompt, 7)


# ---------------------------------------------------------------------------
# token choice on the device: a step hands the host its ids, 4 bytes a
# slot, and its logits only when somebody asks the result for them
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("drafter", [None, "ngram", "self"])
def test_device_tokens_equal_host_argmax(bundle_dir, reference_decode,
                                         watch_steps, drafter):
    """A multi-request run, plain and with borrowed slots verifying
    drafts: every step's ids equal the host arg-maxima of its logits,
    every request emits what the sequential oracle (np.argmax on the
    host) emits, and the scheduler called the method patched onto the
    instance, once a step."""
    eng = DecodeEngine(bundle_dir, name="lm", drafter=drafter, spec_k=3)
    seen = watch_steps(eng.model)
    prompts, max_new = _prompts(211, 6, 2, 14), [9, 5, 12, 7, 3, 10]
    try:
        handles = [eng.generate(p, max_new_tokens=m)
                   for p, m in zip(prompts, max_new)]
        got = [h.result(timeout=120)["tokens"] for h in handles]
        snap = eng.metrics_snapshot()
    finally:
        eng.shutdown()
    assert got == [reference_decode(p, m) for p, m in zip(prompts, max_new)]
    assert len(seen) == snap["decode_steps"] > 0
    for tokens, host_argmax in seen:
        assert tokens.dtype == np.int32 and tokens.shape == (SLOTS,)
        assert np.array_equal(tokens, host_argmax)
    if drafter is not None:
        assert snap["spec_drafted"] > 0


def test_equal_maxima_yield_the_lower_index():
    """The step's arg-max takes the first of equal maxima, as np.argmax
    does: a tie cannot make the device's token differ from the
    parent's."""
    import jax.numpy as jnp
    from paddle_tpu.serving.decode.engine import jit_step
    logits = np.full((4, 9), -1.0, np.float32)
    logits[0, [3, 7]] = 2.5          # two equal maxima
    logits[1, :] = 0.25              # all equal
    logits[2, [8, 0, 4]] = np.inf    # infinities tie too
    logits[3, 5] = 1.0               # no tie
    fed = []

    def call(tokens, lens, tables):
        fed.append(tokens)
        return [jnp.asarray(logits) + 0 * tokens[:, None]]

    step = jit_step(call, False, 0)
    zeros = np.zeros(4, np.int32)
    ids, out, pools, behind = step({}, zeros, zeros, zeros[:, None], [],
                                   zeros)
    assert np.asarray(ids).dtype == np.int32
    assert np.asarray(ids).tolist() == [3, 0, 0, 5] \
        == np.argmax(logits, axis=-1).tolist()
    assert np.array_equal(np.asarray(out), logits)
    assert pools == [] and behind == []


def test_a_previous_token_slot_is_fed_the_step_befores_id():
    """`PREVIOUS_TOKEN` in a slot of the step's tokens is replaced, on
    the device, by the id the step before chose for that slot; every
    other slot is fed what the host gave, a token 0 among them."""
    import jax.numpy as jnp
    from paddle_tpu.serving.decode import PREVIOUS_TOKEN
    from paddle_tpu.serving.decode.engine import jit_step
    step = jit_step(
        lambda tokens, lens, tables: [
            jnp.zeros((4, 9), jnp.float32).at[jnp.arange(4), tokens].set(1)],
        False, 0)
    zeros = np.zeros(4, np.int32)
    prev = np.asarray([7, 6, 5, 4], np.int32)
    tokens = np.asarray([PREVIOUS_TOKEN, 2, PREVIOUS_TOKEN, 0], np.int32)
    ids, _, _, _ = step({}, tokens, zeros, zeros[:, None], [], prev)
    assert np.asarray(ids).tolist() == [7, 2, 5, 0]


def _idle_feeds(model):
    return (np.zeros(model.slots, np.int64),
            np.zeros(model.slots, np.int32),
            np.zeros((model.slots, model.max_blocks_per_seq), np.int32))


def test_step_host_bytes_counted_and_on_the_scrape(bundle_dir):
    """Serving moves 4 bytes a slot a step to the host and never asks
    for the logits; asking a step's result for them moves them once
    and is counted."""
    eng = DecodeEngine(bundle_dir, name="lm")
    try:
        assert eng.describe()["token_choice"] == "device"
        snap = eng.metrics_snapshot()     # the warm-up's step: before
        assert snap["step_host_bytes"] == snap["logits_fetches"] == 0
        handles = [eng.generate(p, max_new_tokens=6)
                   for p in _prompts(223, 4)]
        for h in handles:
            h.result(timeout=120)
        snap = eng.metrics_snapshot()
        steps = snap["decode_steps"]
        assert steps > 0
        assert snap["step_host_bytes"] == steps * 4 * SLOTS
        assert snap["logits_fetches"] == 0
        result = eng.scheduler.while_idle(
            lambda: eng.model.decode_step(*_idle_feeds(eng.model)))
        # dispatched, nothing read: nothing has crossed yet
        assert eng.metrics_snapshot()["step_host_bytes"] \
            == steps * 4 * SLOTS
        assert result.tokens.shape == (SLOTS,) \
            and result.tokens is result.tokens
        assert eng.metrics_snapshot()["step_host_bytes"] \
            == (steps + 1) * 4 * SLOTS
        rows = np.asarray(result)
        assert rows.shape == (SLOTS, V) and rows.dtype == np.float32
        assert np.asarray(result) is rows and result[1] is not None
        snap = eng.metrics_snapshot()     # asked three times, moved once
        assert snap["step_host_bytes"] \
            == (steps + 1) * 4 * SLOTS + SLOTS * V * 4
        assert snap["logits_fetches"] == 1
    finally:
        eng.shutdown()
    text = render_prometheus({"decode": {"lm": snap}})
    assert validate_exposition(text) == []
    assert ('pt_decode_step_host_bytes_total{model="lm"} %d'
            % snap["step_host_bytes"]) in text
    assert 'pt_decode_logits_fetches_total{model="lm"} 1' in text


def test_asked_for_logits_are_the_bare_artifacts(bundle_dir):
    """The benchmark check's call: `np.asarray(model.decode_step(...))
    [0]` gives the logits row the step artifact computes when nothing
    chooses a token behind it, value for value."""
    import jax
    from paddle_tpu.core.compat import jax_export
    model = _sentinel_model(bundle_dir)
    feeds, _ = _seeded(model, _prompts(227, 2, 5, 12), seed=3)
    with open(os.path.join(bundle_dir, "serving.json")) as f:
        dec = json.load(f)["decode"]
    with open(os.path.join(bundle_dir, dec["file"]), "rb") as f:
        call = jax_export().deserialize(bytearray(f.read())).call
    dts = [np.dtype(m["dtype"]) for m in dec["feeds"][:3]]
    assert dec["weights"] is not None    # the artifact takes them
    bare = jax.jit(lambda w, *a: call(w, *a)[0])(
        model._step_weights, *(np.asarray(x, dt)
                               for x, dt in zip(feeds, dts)),
        *model._pools)
    result = model.decode_step(*feeds)
    assert np.array_equal(np.asarray(result)[0], np.asarray(bare)[0])
    assert np.array_equal(np.asarray(result), np.asarray(bare))
    assert np.array_equal(result.tokens, np.argmax(np.asarray(bare), -1))


#: greedy generations of the parent commit (the host-path admission),
#: prompts _prompts(97, 5, 2, 14) through buckets 8 and 16
PINNED_MAX_NEW = [6, 9, 4, 11, 7]
PINNED_TOKENS = [[8, 8, 8, 32, 8, 22],
                 [32, 11, 8, 34, 11, 32, 12, 32, 22],
                 [27, 27, 27, 27],
                 [8, 8, 32, 8, 11, 8, 8, 8, 8, 11, 8],
                 [28, 28, 28, 32, 28, 28, 32]]


def test_greedy_tokens_are_the_parent_commits(bundle_dir):
    eng = DecodeEngine(bundle_dir, name="lm")
    try:
        handles = [eng.generate(p, max_new_tokens=m) for p, m
                   in zip(_prompts(97, 5, 2, 14), PINNED_MAX_NEW)]
        assert [h.result(timeout=120)["tokens"] for h in handles] \
            == PINNED_TOKENS
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# dispatch ahead: step N+1 is dispatched before step N's tokens are read
# ---------------------------------------------------------------------------

#: greedy generations of the parent commit's lockstep loop for a
#: backlog of 3 x SLOTS requests (the five above and four more), without
#: an EOS and with token 32 as everybody's
BACKLOG_MAX_NEW = PINNED_MAX_NEW + [13, 2, 8, 5]
BACKLOG_TOKENS = {
    None: PINNED_TOKENS + [
        [32, 9, 9, 27, 32, 27, 32, 32, 17, 32, 8, 27, 32], [32, 8],
        [9, 4, 9, 4, 4, 32, 32, 9], [17, 34, 12, 32, 22]],
    32: [[8, 8, 8, 32], [32], [27, 27, 27, 27], [8, 8, 32],
         [28, 28, 28, 32], [32], [32], [9, 4, 9, 4, 4, 32],
         [17, 34, 12, 32]]}


def _backlog_prompts():
    return _prompts(97, 5, 2, 14) + _prompts(313, 4, 2, 14)


@pytest.mark.parametrize("eos", [None, 32])
def test_a_backlog_ahead_gives_the_parents_tokens(bundle_dir, eos):
    """Three times the slots, mixed lengths: request for request the
    tokens of the parent's lockstep loop, with and without an EOS, while
    most steps were dispatched before the tokens of the step before
    them were read: every step is either one of those or follows a
    drain."""
    eng = DecodeEngine(bundle_dir, name="lm")
    try:
        handles = [eng.generate(p, max_new_tokens=m, eos_id=eos)
                   for p, m in zip(_backlog_prompts(), BACKLOG_MAX_NEW)]
        results = [h.result(timeout=120) for h in handles]
        snap = eng.metrics_snapshot()
        assert eng.pool.blocks_in_use == 0
        ahead = eng.describe()["dispatch_ahead"]
    finally:
        eng.shutdown()
    assert [r["tokens"] for r in results] == BACKLOG_TOKENS[eos]
    for r, m in zip(results, BACKLOG_MAX_NEW):
        assert r["finish_reason"] == (
            "length" if len(r["tokens"]) == m and r["tokens"][-1] != eos
            else "eos")
    assert snap["completed"] == len(results)
    assert 0 < snap["steps_ahead"] \
        == snap["decode_steps"] - sum(snap["drains"].values())
    assert set(snap["drains"]) <= {"admission", "tail"}
    assert snap["drains"]["admission"] > 0
    # a token past an EOS was computed only where the EOS was a step's
    assert (snap["overrun_tokens"] > 0) == (eos is not None)
    assert ahead == {"depth": 1, "drains_for": [
        "admission", "eviction", "shed", "drafter", "tail"]}
    text = render_prometheus({"decode": {"lm": snap}})
    assert validate_exposition(text) == []
    assert ('pt_decode_steps_ahead_total{model="lm"} %d'
            % snap["steps_ahead"]) in text
    assert ('pt_decode_overrun_tokens_total{model="lm"} %d'
            % snap["overrun_tokens"]) in text
    assert ('pt_decode_drains_total{model="lm",reason="admission"} %d'
            % snap["drains"]["admission"]) in text


def _eos_at_a_step(reference_decode, seed, max_new=10):
    """A prompt and an EOS that is first chosen by a decode step with
    room on both sides: not the admission's token, not the last."""
    for prompt in _prompts(seed, 20, 5, 9):
        ref = reference_decode(prompt, max_new)
        for k in range(2, max_new - 2):
            if ref[k] not in ref[:k]:
                return prompt, ref[k], ref[:k + 1]
    raise AssertionError("no such prompt among these")


@pytest.mark.parametrize("crowded", [False, True])
def test_an_eos_in_flight_overruns_by_one_row_and_no_token(
        bundle_dir, reference_decode, crowded):
    """The EOS is step N's token, and step N+1 was dispatched with the
    sequence in it before the host could know: nothing is emitted past
    the EOS, exactly one token was computed for nobody, every block
    comes back, and the sequence admitted into its slot next reads no
    row of the one before (token-identical)."""
    prompt, eos, want = _eos_at_a_step(reference_decode, 401)
    others = _prompts(409, 3, 5, 9) if crowded else []
    after = _prompts(419, 1, 6, 9)[0]
    eng = DecodeEngine(bundle_dir, name="lm")
    try:
        h = eng.generate(prompt, max_new_tokens=10, eos_id=eos)
        # two fill the other slots past the EOS, the third waits for
        # the slot it frees
        peers = [eng.generate(p, max_new_tokens=14) for p in others]
        streamed = list(h.stream(timeout=60))
        r = h.result(timeout=60)
        assert streamed == r["tokens"] == want
        assert r["finish_reason"] == "eos"
        for p, ph in zip(others, peers):
            assert ph.result(timeout=120)["tokens"] \
                == reference_decode(p, 14)
        assert eng.pool.blocks_in_use == 0
        snap = eng.metrics_snapshot()
        assert snap["overrun_tokens"] == 1
        # every token but the admissions' first came from a step, and
        # none past the EOS
        assert snap["tokens_out"] + snap["prefills"] == len(want) \
            + sum(len(reference_decode(p, 14)) for p in others)
        rb = eng.generate(after, max_new_tokens=9).result(timeout=60)
        assert rb["tokens"] == reference_decode(after, 9)
        assert eng.pool.blocks_in_use == 0
        assert eng.metrics_snapshot()["overrun_tokens"] == 1
    finally:
        eng.shutdown()


@pytest.mark.parametrize("priorities", [[1, 0, 0], [0, 0, 0]],
                         ids=["a_lower_priority_victim", "peers"])
def test_pool_pressure_drains_before_it_evicts(bundle_dir,
                                               reference_decode,
                                               priorities):
    """Growth the pool cannot cover is seen BEFORE the step behind the
    one in flight is prepared: that step is collected first (drain
    `eviction`), so the victim, a peer or the sequence itself, is
    requeued holding every token it was given and resumes
    token-identical; the steps between ran ahead."""
    eng = DecodeEngine(bundle_dir, name="lm", pool_blocks=9)
    try:
        prompts = _prompts(5, 3, 7, 8)
        handles = [eng.generate(p, max_new_tokens=12, priority=pr)
                   for p, pr in zip(prompts, priorities)]
        for p, hd in zip(prompts, handles):
            assert hd.result(timeout=180)["tokens"] \
                == reference_decode(p, 12)
        snap = eng.metrics_snapshot()
        assert snap["evictions"] > 0 and snap["resumes"] > 0
        assert snap["drains"].get("eviction", 0) > 0
        assert snap["steps_ahead"] > 0 and snap["overrun_tokens"] == 0
        assert snap["kv_blocks_in_use"] == 0
        assert eng.pool.blocks_in_use == 0
    finally:
        eng.shutdown()


def _at_step(eng, k, do):
    """Wrap the model's step on the instance, as the benchmark does:
    `do()` runs on the scheduler's thread right after the k-th step of
    the run has been dispatched, that step still in flight."""
    inner, seen = eng.model.decode_step, []

    def step(token_ids, context_lens, block_tables):
        result = inner(token_ids, context_lens, block_tables)
        seen.append(int(np.sum(context_lens)))
        if len(seen) == k:
            do()
        return result

    eng.model.decode_step = step
    return seen


def test_a_deadline_shed_with_a_step_in_flight(bundle_dir,
                                               reference_decode):
    """A running sequence's deadline runs out while a step it rides in
    is on the device: that step's token is emitted first, as the
    lockstep loop would have, then the sequence is shed typed and
    nothing more is emitted for it; its neighbour never notices; every
    block comes back."""
    doomed, neighbour = _prompts(431, 2, 5, 9)
    eng = DecodeEngine(bundle_dir, name="lm")
    try:
        def expire():
            victim, = [s for s in eng.scheduler._running
                       if s.prompt == doomed]
            victim.deadline_t = 0.0

        _at_step(eng, 4, expire)
        h = eng.generate(doomed, max_new_tokens=20, deadline_ms=600000)
        hn = eng.generate(neighbour, max_new_tokens=16)
        got = []
        with pytest.raises(DeadlineExceeded):
            for tok in h.stream(timeout=60):
                got.append(tok)
        # the admission's token and the four steps', the fourth's by
        # the drain
        assert got == reference_decode(doomed, 20)[:5]
        assert hn.result(timeout=120)["tokens"] \
            == reference_decode(neighbour, 16)
        snap = eng.metrics_snapshot()
        assert snap["shed_deadline"] == 1 and snap["drains"]["shed"] == 1
        assert snap["overrun_tokens"] == 0
        assert eng.pool.blocks_in_use == 0
    finally:
        eng.shutdown()


@pytest.mark.parametrize("drafter", ["ngram", "self"])
def test_a_drafter_runs_in_lockstep(bundle_dir, reference_decode,
                                    drafter):
    """A drafter proposes from the tokens the host holds: every step is
    collected before anything else is dispatched, the same loop at
    depth 0."""
    eng = DecodeEngine(bundle_dir, name="lm", drafter=drafter, spec_k=3)
    try:
        prompts, max_new = _prompts(211, 5, 2, 14), [9, 5, 12, 7, 10]
        handles = [eng.generate(p, max_new_tokens=m)
                   for p, m in zip(prompts, max_new)]
        for p, m, h in zip(prompts, max_new, handles):
            assert h.result(timeout=120)["tokens"] \
                == reference_decode(p, m)
        snap = eng.metrics_snapshot()
        assert eng.describe()["dispatch_ahead"]["depth"] == 0
    finally:
        eng.shutdown()
    assert snap["steps_ahead"] == 0 and snap["overrun_tokens"] == 0
    assert snap["drains"] == {"drafter": snap["decode_steps"]}
    assert snap["spec_drafted"] > 0


@pytest.mark.parametrize("what", ["while_idle", "defrag", "close"])
def test_maintenance_with_a_step_in_flight(bundle_dir, reference_decode,
                                           what):
    """With a step on the device: idle-only maintenance is refused at
    once (a sequence stays counted until the last step it rode in is
    emitted), never run beside it, and works when the run is over;
    `close(drain=False)` abandons the step, fails what is left typed,
    ends the thread and leaks no block."""
    from paddle_tpu.serving import ModelUnavailable
    prompts = _prompts(443, 2, 5, 9)
    eng = DecodeEngine(bundle_dir, name="lm")
    in_flight, go_on = threading.Event(), threading.Event()

    def hold():
        in_flight.set()
        assert go_on.wait(30)

    try:
        _at_step(eng, 3, hold)
        handles = [eng.generate(p, max_new_tokens=12) for p in prompts]
        assert in_flight.wait(60)
        if what == "close":
            go_on.set()
            eng.shutdown(drain=False)
            assert not eng.scheduler._thread.is_alive()
            for p, h in zip(prompts, handles):
                with pytest.raises(ModelUnavailable):
                    h.result(timeout=5)
            assert eng.scheduler._flight is None
            assert eng.scheduler.queued() == 0
        else:
            call = (eng.defrag if what == "defrag" else
                    lambda: eng.scheduler.while_idle(lambda: 0))
            with pytest.raises(RuntimeError, match="live"):
                call()
            go_on.set()
            for p, h in zip(prompts, handles):
                assert h.result(timeout=120)["tokens"] \
                    == reference_decode(p, 12)
            # the run is over: nothing is in flight, and it runs
            assert call() == 0 and eng.scheduler._flight is None
        assert eng.pool.blocks_in_use == 0
    finally:
        go_on.set()
        eng.shutdown()


# ---------------------------------------------------------------------------
# a lockstep caller sees no change
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("wrapped", [False, True],
                         ids=["bare", "spans_wrapped"])
@pytest.mark.parametrize("first", ["logits", "tokens"])
def test_a_caller_that_reads_at_once_is_in_lockstep(bundle_dir, wrapped,
                                                    first):
    """The benchmark's check (`_serve._cached_logits`) and its
    `ProgramSpans`: the three methods wrapped on the instance, the step
    called with three positional arguments and a host array of lengths,
    `np.asarray(result)[0]` asked of every step. Step by step the
    logits are the bare step artifact's (what the parent's executable
    computed: the artifact and an arg-max behind it), bit for bit, and
    `.tokens` their arg-maxima; every step was waited for before the
    next was dispatched."""
    import jax
    from paddle_tpu.core.compat import jax_export
    trace.reset()
    model = DecodeModel(bundle_dir, warmup=False)
    counted = {"context_tokens": 0, "calls": 0}
    if wrapped:
        prefill, seed, step = (model.prefill, model.seed_sequence,
                               model.decode_step)

        def traced_step(token_ids, context_lens, block_tables):
            counted["context_tokens"] += int(np.sum(context_lens))
            counted["calls"] += 1
            return step(token_ids, context_lens, block_tables)

        model.prefill = lambda token_ids: prefill(token_ids)
        model.seed_sequence = lambda *a, **kw: seed(*a, **kw)
        model.decode_step = traced_step
    with open(os.path.join(bundle_dir, "serving.json")) as f:
        dec = json.load(f)["decode"]
    with open(os.path.join(bundle_dir, dec["file"]), "rb") as f:
        call = jax_export().deserialize(bytearray(f.read())).call
    bare = jax.jit(lambda w, *a: call(w, *a)[0])
    dts = [np.dtype(m["dtype"]) for m in dec["feeds"][:3]]
    p_len, m = 7, 6
    ids = np.random.RandomState(7).randint(1, V, p_len + m)
    blocks = list(range(1, 1 + -(-(p_len + m) // BLOCK)))
    last, kv = model.prefill([int(t) for t in ids[:p_len]])
    model.seed_sequence(blocks[:-(-p_len // BLOCK)], kv)
    np.asarray(last)
    tokens = np.zeros(model.slots, np.int64)
    lens = np.zeros(model.slots, np.int32)
    tables = np.zeros((model.slots, model.max_blocks_per_seq), np.int32)
    tables[0, :len(blocks)] = blocks
    for j in range(m):
        tokens[0], lens[0] = ids[p_len + j], p_len + j + 1
        want = np.asarray(bare(
            model._step_weights,
            *(np.asarray(x, dt) for x, dt in zip((tokens, lens, tables),
                                                 dts)), *model._pools))
        result = model.decode_step(tokens, lens, tables)
        if first == "tokens":
            chosen = result.tokens
        row = np.asarray(result)[0]
        assert np.array_equal(row, want[0])
        assert np.array_equal(np.asarray(result), want)
        assert result.tokens.dtype == np.int32
        assert np.array_equal(result.tokens, np.argmax(want, axis=-1))
        if first == "tokens":
            assert chosen is result.tokens
        assert model._drained_at is not None    # waited for: lockstep
    if wrapped:
        assert counted == {"calls": m, "context_tokens": sum(
            p_len + j + 1 for j in range(m))}
    recs = [n for c, n, _, _ in trace.phase_records() if c == "decode"]
    for p in ("step_dispatch", "step_wait", "step_fetch"):
        assert recs.count(p) == m
    # each step's wait lies behind its own dispatch and before the next
    order = [n for n in recs if n in ("step_dispatch", "step_wait")]
    assert order == ["step_dispatch", "step_wait"] * m
    trace.reset()


def test_no_compile_after_the_engines_warm_up(bundle_dir):
    """The load builds every executable an admission or a step runs:
    one prefill and one seeding per bucket, not per block count or
    prompt length, and pools from `reset_pools`, a seeding and a step
    are the same kind of argument to each."""
    from paddle_tpu.obs.metrics import XLA_COMPILES
    eng = DecodeEngine(bundle_dir, name="lm")
    try:
        before = XLA_COMPILES.count
        rng = np.random.RandomState(3)
        for n in range(1, BUCKETS[-1] + 1):
            h = eng.generate(rng.randint(1, V, n).tolist(),
                             max_new_tokens=3)
            assert len(h.result(timeout=120)["tokens"]) == 3
        eng.scheduler.while_idle(eng.model.reset_pools)
        h = eng.generate([5, 6, 7], max_new_tokens=3)
        h.result(timeout=120)
        assert XLA_COMPILES.count == before
    finally:
        eng.shutdown()


def test_prefill_host_bytes_counted_and_on_the_scrape(bundle_dir):
    """An admission moves the padded ids, the block-id vector (each
    with its length scalar) and one logits row, nothing else."""
    eng = DecodeEngine(bundle_dir, name="lm")
    try:
        assert eng.metrics_snapshot()["prefill_host_bytes"] == 0
        ids_dt = eng.model.prefill_model.feed_dtypes()["src_ids"]
        want = 0
        for n, bound in ((3, 8), (8, 8), (13, 16), (20, 32)):
            eng.generate(list(range(1, n + 1)),
                         max_new_tokens=2).result(timeout=120)
            want += (V * 4 + bound * ids_dt.itemsize + 4
                     + (bound // BLOCK) * 4 + 4)
            assert eng.metrics_snapshot()["prefill_host_bytes"] == want
        snap = eng.metrics_snapshot()
    finally:
        eng.shutdown()
    text = render_prometheus({"decode": {"lm": snap}})
    assert validate_exposition(text) == []
    assert ('pt_decode_prefill_host_bytes_total{model="lm"} %d' % want) \
        in text
    # the step's engagement figure: every pool updated in place
    pools = 2 * L * POOL * BLOCK * H * (DM // H) * 4
    assert snap["step_aliased_bytes"] == pools
    assert ('pt_decode_step_aliased_bytes{model="lm"} %d' % pools) in text
    assert "# TYPE pt_decode_step_aliased_bytes gauge" in text


def test_paged_pages_counted_and_on_the_scrape(bundle_dir):
    """The paged kernel's engagement figure: a step counts the pages its
    contexts hold and the pages the kernel's compute blocks cover, from
    the feed's lengths; `describe()` says how large a block is."""
    eng = DecodeEngine(bundle_dir, name="lm")
    try:
        per_block = eng.model.paged_block_pages
        # a page of this bundle is 256 bytes: the table's width bounds P
        assert per_block == MAXC // BLOCK
        # a head a K/V head: the per-head kernel, no product a group
        assert eng.describe()["paged_kernel"] == {
            "pages_per_block": per_block, "max_blocks_per_call": SLOTS,
            "heads_per_product": None, "score_columns_per_block": None}
        # a dense bundle has no grouped product to plan
        assert eng.describe()["expert_kernel"] is None
        assert eng.metrics_snapshot()["paged_live_pages"] == 0
        eng.generate([3, 1, 4, 1, 5], max_new_tokens=7).result(timeout=120)
        snap = eng.metrics_snapshot()
    finally:
        eng.shutdown()
    steps = snap["decode_steps"]
    assert steps >= 1
    # alone in the engine: step k attends over the prompt's 5 tokens + k
    assert snap["paged_live_pages"] == sum(
        -(-(5 + k) // BLOCK) for k in range(1, steps + 1))
    assert snap["paged_walked_pages"] == steps * per_block
    text = render_prometheus({"decode": {"lm": snap}})
    assert validate_exposition(text) == []
    for key in ("paged_live_pages", "paged_walked_pages"):
        assert ('pt_decode_%s_total{model="lm"} %d' % (key, snap[key])) \
            in text


# ---------------------------------------------------------------------------
# front end: ServingEngine integration, streaming HTTP, prometheus
# ---------------------------------------------------------------------------

def test_serving_engine_generate_and_swap(bundle_dir, reference_decode):
    engine = ServingEngine()
    try:
        desc = engine.load_decode_model("lm", bundle_dir)
        assert desc["slots"] == SLOTS
        p = _prompts(81, 1, 4, 6)[0]
        r = engine.generate("lm", p, max_new_tokens=5).result(timeout=60)
        assert r["tokens"] == reference_decode(p, 5)
        assert "decode" in engine.models()["lm"]
        # hot swap: new engine in, old drains; requests keep serving
        engine.load_decode_model("lm", bundle_dir)
        r2 = engine.generate("lm", p, max_new_tokens=5).result(timeout=60)
        assert r2["tokens"] == r["tokens"]
        engine.unload_decode_model("lm")
        with pytest.raises(Exception):
            engine.generate("lm", p)
    finally:
        engine.shutdown()


def test_http_generate_stream_and_prometheus(bundle_dir):
    engine = ServingEngine()
    server = None
    try:
        engine.load_decode_model("lm", bundle_dir)
        server, _t = start_http_server(engine)
        port = server.server_address[1]
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/models/lm:generate",
            data=json.dumps({"prompt_ids": [3, 7, 9],
                             "max_new_tokens": 5}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req) as r:
            assert r.headers["Content-Type"] == "application/x-ndjson"
            lines = [json.loads(ln)
                     for ln in r.read().decode().strip().splitlines()]
        assert lines[-1]["done"] is True
        assert [ln["token"] for ln in lines[:-1]] == lines[-1]["tokens"]
        assert [ln["index"] for ln in lines[:-1]] == list(range(5))
        # non-stream variant returns one body
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/models/lm:generate",
            data=json.dumps({"prompt_ids": [3, 7], "max_new_tokens": 3,
                             "stream": False}).encode())
        with urllib.request.urlopen(req) as r:
            body = json.loads(r.read())
        assert len(body["tokens"]) == 3
        # prometheus text exposition, on both route spellings
        for path in ("/v1/metrics?format=prometheus",
                     "/metrics?format=prometheus"):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}") as r:
                assert r.headers["Content-Type"].startswith("text/plain")
                text = r.read().decode()
            assert 'pt_decode_tokens_out_total{model="lm"}' in text
            assert 'pt_decode_slot_occupancy{model="lm"}' in text
            assert "# TYPE pt_decode_tokens_out_total counter" in text
        # JSON snapshot unchanged
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/v1/metrics") as r:
            snap = json.loads(r.read())
        assert snap["decode"]["lm"]["completed"] >= 2
    finally:
        if server is not None:
            server.shutdown()
        engine.shutdown()


def test_render_prometheus_omits_none():
    text = render_prometheus(
        {"models": {"m": {"received": 3, "batch_fill_ratio": None,
                          "latency": {"queue": {"p50_ms": None}}}}})
    assert "pt_serve_received_total" in text
    assert "batch_fill_ratio" not in text and "latency" not in text


# ---------------------------------------------------------------------------
# phase clocks inside the engine (DecodePhaseTimer)
# ---------------------------------------------------------------------------

STEP_PHASES = ("step_prep", "step_dispatch", "step_wait", "step_fetch",
               "step_emit")
PREFILL_PHASES = ("prefill_pad", "prefill_device", "seed_kv",
                  "prefill_fetch")
#: the phases the scheduler's thread is in, each a profiler annotation;
#: `device_idle`, the one phase beside them, is the device's
HOST_PHASES = tuple(p for p in DECODE_PHASES if p != "device_idle")


@pytest.fixture
def clean_ring(monkeypatch):
    monkeypatch.delenv("PT_TRACE", raising=False)
    monkeypatch.delenv("PT_TRACE_BUF", raising=False)
    trace.reset()
    yield
    trace.reset()


def _short_generate(bundle_dir, n=4, on_step=None):
    """A short run to completion: (per-request results, metrics
    snapshot, the scheduler's thread id)."""
    eng = DecodeEngine(bundle_dir, name="lm")
    trace.reset()      # the load's warm-up step is not the run's
    if on_step is not None:
        inner = eng.metrics.on_step

        def spy(used, capacity, seconds, tokens, *more, **kw):
            on_step(seconds)
            inner(used, capacity, seconds, tokens, *more, **kw)

        eng.metrics.on_step = spy
    try:
        handles = [eng.generate(p, max_new_tokens=6)
                   for p in _prompts(23, n)]
        results = [h.result(timeout=120) for h in handles]
    finally:
        eng.shutdown()
    # read after the scheduler's thread has ended: a result is
    # delivered before the step that emitted it has closed its phase
    return results, eng.metrics_snapshot(), eng.scheduler._thread.ident


def test_every_phase_in_the_ring_without_being_asked(bundle_dir,
                                                     clean_ring):
    """PT_TRACE unset: every phase of the table is in the ring with its
    end on perf_counter, nothing else is, and the scheduler's old
    after-the-fact `prefill` / `decode_step` emitters are gone."""
    t0 = time.perf_counter()
    _, snap, _ = _short_generate(bundle_dir)
    t1 = time.perf_counter()
    recs = [r for r in trace.phase_records() if r[0] == "decode"]
    assert {name for _, name, _, _ in recs} == set(DECODE_PHASES)
    assert all(t0 <= t_end <= t1 and s >= 0 for _, _, t_end, s in recs)
    count = {p: sum(1 for r in recs if r[1] == p) for p in DECODE_PHASES}
    assert {count[p] for p in STEP_PHASES} == {snap["decode_steps"]}
    assert {count[p] for p in PREFILL_PHASES + ("admit",)} \
        == {snap["prefills"]}
    # per launch from a drained device, never more than one a launch: a
    # step dispatched behind a step in flight finds the device busy
    assert 0 < snap["steps_ahead"] \
        == snap["decode_steps"] - sum(snap["drains"].values())
    assert 1 <= count["device_idle"] <= (
        snap["decode_steps"] - snap["steps_ahead"] + snap["prefills"])
    evs = trace.events()
    assert {e["cat"] for e in evs} <= {"decode", "xla"}
    assert not {"prefill", "decode_step"} & {e["name"] for e in evs}
    assert all(e["args"] == {} for e in evs)     # no ids, no attributes
    # what PT_TRACE governs stays off
    n = len(evs)
    assert trace.span("x", cat="decode") is trace.NOOP
    trace.instant("evict", cat="decode")
    trace.complete("prefill", 0.1, cat="decode")
    assert len(trace.events()) == n


def test_phase_sum_rules(bundle_dir, clean_ring):
    """The phases are one timing source with the counters the benchmark
    already reads. A step's dt (DecodeMetrics.decode_s) is what it adds
    to the loop's time, emission to emission with the admissions
    between taken out: its own wait and fetch lie inside it (the step
    queued behind it was dispatched before them), every step's prep,
    dispatch, wait and fetch lie inside the sum, and the sum with
    prefill_s inside the run's wall time: no second is counted twice,
    whatever was queued behind what. Per admission, the prefill phases
    and the seeding lie inside prefill_s, and prefill_s inside `admit`.
    The snapshot's cumulative view is the ring's sum."""
    step_dts = []
    t0 = time.perf_counter()
    _, snap, _ = _short_generate(bundle_dir, on_step=step_dts.append)
    wall = time.perf_counter() - t0
    recs = [r for r in trace.phase_records() if r[0] == "decode"]

    def series(name):
        return [s for _, n, _, s in recs if n == name]

    inside = [w + f for w, f in zip(series("step_wait"),
                                    series("step_fetch"))]
    assert len(inside) == len(step_dts) == snap["decode_steps"]
    assert snap["steps_ahead"] > 0
    for got, dt in zip(inside, step_dts):
        assert 0 < got <= dt + 1e-4
    assert sum(step_dts) == pytest.approx(snap["decode_s"], abs=1e-4)
    host = sum(sum(series(p)) for p in ("step_prep", "step_dispatch",
                                        "step_wait", "step_fetch"))
    assert host <= snap["decode_s"] + 1e-4
    assert snap["decode_s"] + snap["prefill_s"] <= wall
    prefill = sum(sum(series(p)) for p in PREFILL_PHASES)
    assert 0 < prefill <= snap["prefill_s"] + 1e-4
    assert snap["prefill_s"] <= sum(series("admit")) + 1e-4
    for p in DECODE_PHASES:
        assert snap["phases"][p + "_s"] \
            == pytest.approx(sum(series(p)), abs=1e-5)


def _intervals(recs, *names):
    return [(t_end - s, t_end) for _, n, t_end, s in recs if n in names]


def test_device_idle_lies_between_a_wait_and_the_next_launch(
        bundle_dir, clean_ring):
    """Through the scheduler: every `device_idle` interval ends inside
    the phase of a launch, starts at or after the end of a `step_wait`
    that no launch follows before it, and overlaps no `step_wait`; a
    launch leaves one exactly when the device was drained before it
    (replayed from the ring's own order: the k-th `step_wait` waits for
    the k-th step dispatched, and drains only if nothing was dispatched
    since: not with the next step queued behind it, the steady state;
    a prefill's seeding is newer than the row the admission waits for,
    so no admission drains); the snapshot's `device_idle_s` is the
    ring's sum."""
    _, snap, _ = _short_generate(bundle_dir, n=5)     # > SLOTS: some wait
    recs = [r for r in trace.phase_records() if r[0] == "decode"]
    idle = _intervals(recs, "device_idle")
    waits = _intervals(recs, "step_wait")
    launches = _intervals(recs, "step_dispatch", "prefill_device")
    assert idle and all(b >= a for a, b in idle)
    for a, b in idle:
        assert any(la <= b <= lb for la, lb in launches)
        assert not any(wa < b and a < wb for wa, wb in waits)
    # the load's warm-up ended in a read of its step's tokens: drained
    drained, expect, got = True, 0, 0
    launched, step_launch, waited, queued_behind = 0, [], 0, 0
    for _, name, _, _ in recs:
        if name == "device_idle":
            got += 1
        elif name in ("step_dispatch", "prefill_device", "seed_kv"):
            expect += drained
            drained = False
            assert got == expect     # its record precedes the launch's
            launched += 1
            if name == "step_dispatch":
                step_launch.append(launched)
        elif name == "step_wait":
            drained = step_launch[waited] == launched
            queued_behind += not drained
            waited += 1
    assert got == expect == len(idle)
    # the steps that ran ahead are the waits that did not drain
    assert 0 < snap["steps_ahead"] <= queued_behind
    assert len(idle) <= snap["decode_steps"] - snap["steps_ahead"] \
        + snap["prefills"]
    # behind every drain but the first, the interval starts where that
    # step's wait ended (the clock is read again, after the span closed)
    starts = sorted(a for a, _ in idle)[1:]
    ends = sorted(b for _, b in waits)
    for a in starts:
        before = [e for e in ends if e <= a]
        assert before and a - before[-1] < 0.05
    assert snap["phases"]["device_idle_s"] \
        == pytest.approx(sum(b - a for a, b in idle), abs=1e-5)


def test_a_launch_records_idle_exactly_when_the_device_was_drained(
        bundle_dir, clean_ring):
    """A bare model driven by hand. A step is dispatched when
    `decode_step` returns and waited for when its tokens are first
    read: only a wait on the NEWEST dispatch drains. A step read at
    once and then a step leaves one `device_idle`; a step dispatched
    behind an unread step none, and reading the older one's tokens
    then drains nothing; the prefill of an admission after a drained
    step one, its seeding none, and the step behind a seeded admission
    none (the admission waited for the prefill's row, the seeding is
    newer); an admission whose rows are all resident dispatches no
    seeding, so its wait drains, and the step behind it leaves one."""
    model = DecodeModel(bundle_dir, warmup=False)
    prompt = _prompts(163, 1, 5, 8)[0]
    blocks = [1, 2]
    tokens = np.zeros(SLOTS, np.int64)
    lens = np.zeros(SLOTS, np.int32)
    tables = np.zeros((SLOTS, model.max_blocks_per_seq), np.int32)
    tables[0, :2] = blocks
    lens[0] = len(prompt) + 1

    def idles():
        return len(_intervals(trace.phase_records(), "device_idle"))

    def phases(name):
        return len(_intervals(trace.phase_records(), name))

    assert model._drained_at is None     # the pools' zeros in flight
    last, kv = model.prefill(prompt)
    model.seed_sequence(blocks, kv)
    np.asarray(last)
    assert model._drained_at is None and idles() == 0   # seeded: busy
    first = model.decode_step(tokens, lens, tables)
    assert model._drained_at is None     # dispatched, not waited for
    assert phases("step_dispatch") == 1 and phases("step_wait") == 0
    first.tokens
    assert model._drained_at is not None and idles() == 0
    assert phases("step_wait") == phases("step_fetch") == 1
    first.tokens                         # asked again: waits for nothing
    assert phases("step_wait") == 1
    t_drained = model._drained_at
    second = model.decode_step(tokens, lens, tables)
    assert idles() == 1                  # a step that follows a read step
    (a, b), = _intervals(trace.phase_records(), "device_idle")
    assert a == pytest.approx(t_drained, abs=1e-7) and b > a
    third = model.decode_step(tokens, lens, tables)
    assert idles() == 1                  # queued behind an unread step
    second.tokens                        # not the newest dispatch
    assert model._drained_at is None
    np.asarray(third)                    # the logits: its tokens first
    assert model._drained_at is not None and phases("step_wait") == 3
    last, kv = model.prefill(prompt)
    assert idles() == 2 and model._drained_at is None   # its prefill
    model.seed_sequence(blocks, kv)
    np.asarray(last)
    assert idles() == 2 and model._drained_at is None
    np.asarray(last)                     # asked again: waits for nothing
    model.decode_step(tokens, lens, tables).tokens
    assert idles() == 2                  # behind a seeded admission
    # every row resident (a whole-prompt alias): nothing to seed
    last, kv = model.prefill(prompt)
    assert idles() == 3
    model.seed_sequence(blocks, kv, skip_rows=kv.n)
    assert model._drained_at is None
    np.asarray(last)
    assert model._drained_at is not None
    model.decode_step(tokens, lens, tables).tokens
    assert idles() == 4
    # a copy-on-write copy is a dispatch like any other
    model.copy_block(1, 3)
    assert idles() == 5 and model._drained_at is None
    total = sum(b - a for a, b in
                _intervals(trace.phase_records(), "device_idle"))
    assert model.timer.snapshot()["device_idle_s"] \
        == pytest.approx(total, abs=1e-5)


def test_executables_carry_their_names(bundle_dir):
    """What a profile's XLA Modules line shows: `jit_decode_step`,
    `jit_prefill_<bound>`, `jit_seed_kv_<bound>`."""
    model = DecodeModel(bundle_dir, warmup=False)
    assert model._step_fn.__name__ == "decode_step"
    for bound, calls in model._admit_fns.items():
        assert calls.prefill.__name__ == f"prefill_{bound}"
        assert calls.seed.__name__ == f"seed_kv_{bound}"
    lowered = model._admit_fns[BUCKETS[0]].seed.lower(
        model._pools, tuple(np.zeros((1, BUCKETS[0], H, DM // H),
                                     np.float32) for _ in range(2 * L)),
        np.zeros(BUCKETS[0] // BLOCK, np.int32), np.int32(3))
    assert f"jit_seed_kv_{BUCKETS[0]}" in lowered.as_text()[:400]


def test_phase_seconds_on_the_scrape(bundle_dir, clean_ring):
    results, snap, _ = _short_generate(bundle_dir)
    assert set(snap["phases"]) == {p + "_s" for p in DECODE_PHASES}
    assert snap["admitted"] == len(results)
    assert snap["queue_wait_s"] >= 0
    text = render_prometheus({"decode": {"lm": snap}})
    assert validate_exposition(text) == []
    for p in DECODE_PHASES + ("prefill", "decode"):
        assert ('pt_decode_phase_seconds_total{model="lm",phase="%s"}'
                % p) in text
    assert 'pt_decode_queue_wait_seconds_total{model="lm"}' in text
    assert 'pt_decode_admitted_total{model="lm"} %d' % len(results) \
        in text


def test_request_stamps_on_the_rings_clock(bundle_dir, clean_ring):
    t0 = time.perf_counter()
    results, snap, _ = _short_generate(bundle_dir, n=5)   # > SLOTS: some wait
    t1 = time.perf_counter()
    waits = 0.0
    for r in results:
        assert t0 <= r["t_submit"] <= r["t_admit"] \
            <= r["t_first_token"] <= r["t_done"] <= t1
        waits += r["t_admit"] - r["t_submit"]
    assert snap["queue_wait_s"] == pytest.approx(waits, abs=1e-4)
    # each admission's stamp falls inside one `admit` phase of the ring
    admits = [(t_end - s, t_end) for c, n, t_end, s
              in trace.phase_records() if (c, n) == ("decode", "admit")]
    for r in results:
        assert any(a <= r["t_admit"] <= b for a, b in admits)


def test_profilers_view_names_and_nesting(bundle_dir, clean_ring,
                                          monkeypatch):
    """The profiler's clock, by substituting the annotation factory:
    on the scheduler's thread the spans are `program/decode/<phase>`,
    properly nested; the prefill's and the seeding's sit inside
    `admit`, a step's five come in their order with `step_wait`
    between its dispatch and its fetch, and between its dispatch and
    its wait lies at most the one step dispatched ahead."""
    log = []

    class Recorder:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name, threading.get_ident()))

        def __exit__(self, *exc):
            log.append(("exit", self.name, threading.get_ident()))

    monkeypatch.setattr(trace, "_annotation_factory", Recorder)
    _, snap, sched_tid = _short_generate(bundle_dir)
    mine = [(e, n) for e, n, tid in log if tid == sched_tid]
    assert {n for _, n in mine} \
        == {"program/decode/" + p for p in HOST_PHASES}
    stack, parent_of, top_level = [], {}, []
    for ev, name in mine:
        if ev == "enter":
            parent_of.setdefault(name, set()).add(
                stack[-1] if stack else None)
            if not stack:
                top_level.append(name.rsplit("/", 1)[1])
            stack.append(name)
        else:
            assert stack.pop() == name            # proper nesting
    assert stack == []
    for p in PREFILL_PHASES:
        assert parent_of["program/decode/" + p] \
            == {"program/decode/admit"}
    for p in STEP_PHASES + ("admit", "sched_idle"):
        assert parent_of["program/decode/" + p] == {None}
    # every step leaves its five, in its own order; between a step's
    # dispatch and its wait lies at most the ONE step queued behind it
    steps = [p for p in top_level if p.startswith("step_")]
    at = {p: [i for i, q in enumerate(steps) if q == p]
          for p in STEP_PHASES}
    assert {len(v) for v in at.values()} == {snap["decode_steps"]}
    behind = []
    for k in range(snap["decode_steps"]):
        mine = [at[p][k] for p in STEP_PHASES]
        assert mine == sorted(mine)
        behind.append(sum(at["step_dispatch"][k] < i < at["step_wait"][k]
                          for i in at["step_dispatch"]))
        # wait, fetch and emission follow each other, nothing between
        assert mine[2:] == list(range(mine[2], mine[2] + 3))
    assert set(behind) == {0, 1} and sum(behind) == snap["steps_ahead"]


def test_armed_timeline_carries_sids_and_parents(bundle_dir, clean_ring,
                                                 monkeypatch):
    """PT_TRACE=1: the same phases with ids and attributes: the step's
    sids on step_prep, the admission's sid and tokens on admit, the
    prefill phases parented under their admit; not doubled."""
    monkeypatch.setenv("PT_TRACE", "1")
    results, snap, _ = _short_generate(bundle_dir, n=2)
    evs = [e for e in trace.events() if e["cat"] == "decode"]
    assert not {"prefill", "decode_step"} & {e["name"] for e in evs}
    by_name = {}
    for e in evs:
        by_name.setdefault(e["name"], []).append(e)
    assert len(by_name["step_prep"]) == snap["decode_steps"]
    assert len(by_name["admit"]) == snap["prefills"] == 2
    for e in by_name["step_prep"]:
        assert e["args"]["n"] == len(e["args"]["sids"]) >= 1
        assert e["args"]["model"] == "lm"
    admit_ids = {e["args"]["span_id"]: e for e in by_name["admit"]}
    assert sorted(e["args"]["sid"] for e in admit_ids.values()) == [0, 1]
    assert all(e["args"]["tokens"] >= 2 for e in admit_ids.values())
    for p in PREFILL_PHASES:
        for e in by_name[p]:
            admit = admit_ids[e["args"]["parent_id"]]
            assert e["args"]["trace_id"] == admit["args"]["trace_id"]
            assert admit["ts"] <= e["ts"] \
                and e["ts"] + e["dur"] <= admit["ts"] + admit["dur"] + 1
