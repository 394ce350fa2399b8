"""KV economics (serving/decode/): copy-on-write prefix sharing over the
refcounted block pool, the host-side prefix index, and speculative
decoding through the fixed-shape step.

Test planes:
  * accounting — KVBlockPool refcounts (alloc=1, share adds an owner,
    free returns a block only at zero), defrag moving shared blocks
    with their counts;
  * index — PrefixIndex full-chain and partial-tail matches, leaf-first
    LRU release, defrag remap;
  * engine (the headline contracts) — a shared-prefix fleet's pool
    high-water is at least halved with token-identical outputs; the
    aliased-block extension of the no-stale-leak invariant; CoW fires
    exactly on the partial-tail block and never corrupts, including
    under pool exhaustion; speculative decode is TOKEN-IDENTICAL to
    plain greedy over >= 64 tokens for both the self drafter (100%
    acceptance by construction) and the n-gram drafter;
  * resilience — the spec_verify chaos site degrades a drafter crash to
    plain decode, token-identical, session alive;
  * exposition + artifact — pt_kv_*/pt_spec_* families conformant, the
    kv-economics bench row's validator refuses impossible readings.
"""

import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import io as pio
from paddle_tpu.analysis.artifacts import validate_kv_economics
from paddle_tpu.models import transformer as tfm
from paddle_tpu.obs.metrics import validate_exposition
from paddle_tpu.resilience import faults
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.decode import (DecodeEngine, DecodeModel,
                                       KVBlockPool, NGramDrafter,
                                       PrefillDrafter, PrefixIndex,
                                       accept_greedy)
from paddle_tpu.serving.fleet.pool import Replica
from paddle_tpu.serving.metrics import ServingMetrics, render_prometheus

V, L, DM, H, FF, MAXC = 43, 2, 16, 2, 32, 96
BLOCK, POOL, SLOTS = 4, 60, 4
BUCKETS = (8, 16, 96)


@pytest.fixture(autouse=True)
def fresh_fault_plan(monkeypatch):
    monkeypatch.delenv("PT_FAULT_INJECT", raising=False)
    faults.reset()
    yield
    faults.reset()


@pytest.fixture(scope="module")
def bundle_dir(tmp_path_factory):
    """One tiny trained-init transformer exported as a decode bundle.
    max_context 96 (vs test_decode's 48) so the speculation identity
    tests can run >= 64 generated tokens, and bucket 96 lets the `self`
    drafter's prefill reach the whole context."""
    pt.core.program.reset_unique_names()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        tfm.transformer_lm_loss(
            vocab_size=V, seq_len=MAXC, n_layers=L, d_model=DM,
            n_heads=H, d_ff=FF, max_len=MAXC)
    scope = pt.Scope()
    with pt.scope_guard(scope):
        pt.Executor().run(startup)
        d = str(tmp_path_factory.mktemp("kv") / "m")
        pio.export_decode_model(
            d, dict(vocab_size=V, n_layers=L, d_model=DM, n_heads=H,
                    d_ff=FF, max_context=MAXC),
            scope=scope, length_buckets=BUCKETS, slots=SLOTS,
            block_size=BLOCK, pool_blocks=POOL)
    return d


@pytest.fixture(scope="module")
def reference_decode(bundle_dir):
    """Sequential per-sequence greedy oracle (re-prefill each step)."""
    model = DecodeModel(bundle_dir, warmup=False)

    def decode(prompt, max_new):
        toks, out = list(prompt), []
        for _ in range(max_new):
            logits, _ = model.prefill(toks)
            t = int(np.argmax(logits))
            out.append(t)
            toks.append(t)
        return out

    return decode


def _prompt(seed, n):
    rng = np.random.RandomState(seed)
    return [int(t) for t in rng.randint(1, V, n)]


# ---------------------------------------------------------------------------
# pool refcounts
# ---------------------------------------------------------------------------

class TestRefcountedPool:
    def test_share_free_matrix(self):
        """alloc -> 1 owner; share adds; free drops; the block returns
        to the (lowest-first) free list only at zero owners."""
        pool = KVBlockPool(8, 4)
        a = pool.alloc(3)
        assert a == [1, 2, 3]
        assert [pool.refcount(b) for b in a] == [1, 1, 1]
        pool.share([1, 2])
        assert pool.refcount(1) == 2 and pool.refcount(2) == 2
        assert pool.blocks_shared == 2
        pool.free([1, 2, 3])            # 3 dies, 1 and 2 survive
        assert pool.refcount(3) == 0
        assert pool.blocks_in_use == 2 and pool.blocks_shared == 0
        assert pool.alloc(1) == [3], "freed block 3 is the lowest free id"
        pool.free([1, 2])
        assert pool.blocks_in_use == 1  # just block 3

    def test_share_dead_or_null_block_raises(self):
        pool = KVBlockPool(8, 4)
        pool.alloc(1)
        with pytest.raises(ValueError):
            pool.share([2])             # never allocated
        with pytest.raises(ValueError):
            pool.share([0])             # the null block
        with pytest.raises(ValueError):
            pool.free([5])

    def test_defrag_moves_shared_blocks_with_their_counts(self):
        """Compaction is owner-blind: a twice-owned block moves once and
        keeps both owners on its new id."""
        pool = KVBlockPool(10, 4)
        blocks = pool.alloc(5)          # 1..5
        pool.share([4, 5])
        pool.free([1, 2, 3])
        mapping = pool.defrag()
        assert mapping == {4: 1, 5: 2}
        assert pool.refcount(1) == 2 and pool.refcount(2) == 2
        assert pool.blocks_in_use == 2 and pool.blocks_shared == 2
        del blocks


# ---------------------------------------------------------------------------
# prefix index
# ---------------------------------------------------------------------------

class TestPrefixIndex:
    def test_full_chain_match_and_divergence(self):
        pool = KVBlockPool(16, 4)
        idx = PrefixIndex(pool)
        toks = list(range(1, 13))       # 3 full blocks
        blocks = pool.alloc(3)
        assert idx.insert(toks, blocks) == 3
        # insert took one index reference per block
        assert [pool.refcount(b) for b in blocks] == [2, 2, 2]
        got, matched = idx.match(toks)
        assert (got, matched) == (blocks, 12)
        # longer prompt: the cached chain still matches its prefix
        got, matched = idx.match(toks + [99, 98, 97, 96, 95])
        assert (got, matched) == (blocks, 12)
        # divergence inside block 2 ends the match at block 1
        div = toks[:4] + [40] + toks[5:]
        got, matched = idx.match(div)
        assert (got, matched) == ([blocks[0]], 4)

    def test_partial_tail_aliases_only_proper_prefixes(self):
        pool = KVBlockPool(16, 4)
        idx = PrefixIndex(pool)
        toks = list(range(1, 9))        # 2 full blocks
        blocks = pool.alloc(2)
        idx.insert(toks, blocks)
        # prompt ends 2 tokens INTO the second cached block: full alias,
        # matched == len(prompt) — the CoW case
        got, matched = idx.match(toks[:6])
        assert (got, matched) == (blocks, 6)
        # a tail that diverges from the cached block gets NO alias on it
        got, matched = idx.match(toks[:5] + [40])
        assert (got, matched) == ([blocks[0]], 4)

    def test_release_lru_is_leaf_first(self):
        """A chain can only be walked from the root: release drops the
        least-recently-used LEAF, never an interior node."""
        pool = KVBlockPool(16, 4)
        idx = PrefixIndex(pool)
        toks = list(range(1, 13))
        blocks = pool.alloc(3)
        idx.insert(toks, blocks)
        assert idx.release_lru(1) == 1
        assert pool.refcount(blocks[2]) == 1    # leaf released
        assert pool.refcount(blocks[0]) == 2    # root kept
        got, matched = idx.match(toks)
        assert (got, matched) == (blocks[:2], 8)
        assert idx.clear() == 2
        assert idx.blocks_indexed == 0
        assert [pool.refcount(b) for b in blocks] == [1, 1, 1]

    def test_partial_tail_probes_siblings_under_one_parent(self):
        """Two cached chains forking after block 1: the tail probe must
        pick the sibling whose tokens start with the prompt tail (and
        only ever scan that parent's direct children)."""
        pool = KVBlockPool(32, 4)
        idx = PrefixIndex(pool)
        a = pool.alloc(2)
        b = pool.alloc(2)
        idx.insert([1, 2, 3, 4, 5, 6, 7, 8], a)
        idx.insert([1, 2, 3, 4, 9, 10, 11, 12], b)
        # block 1 is shared between the chains; only the divergent
        # second block of each was newly indexed
        got, matched = idx.match([1, 2, 3, 4, 9, 10])
        assert (got, matched) == ([a[0], b[1]], 6)
        got, matched = idx.match([1, 2, 3, 4, 5, 6])
        assert (got, matched) == ([a[0], a[1]], 6)
        # a tail matching NO sibling aliases nothing past the fork
        got, matched = idx.match([1, 2, 3, 4, 7, 7])
        assert (got, matched) == ([a[0]], 4)

    def test_remap_follows_defrag(self):
        pool = KVBlockPool(16, 4)
        parked = pool.alloc(2)
        blocks = pool.alloc(2)          # 3, 4
        idx = PrefixIndex(pool)
        toks = list(range(1, 9))
        idx.insert(toks, blocks)
        pool.free(parked)
        pool.free(blocks)               # only index references remain
        mapping = pool.defrag()
        assert mapping == {3: 1, 4: 2}
        idx.remap(mapping)
        got, matched = idx.match(toks)
        assert (got, matched) == ([1, 2], 8)


# ---------------------------------------------------------------------------
# acceptance rule + drafters (pure host-side units)
# ---------------------------------------------------------------------------

class TestSpecUnits:
    def test_accept_greedy_chain(self):
        # all drafts confirmed: the whole chain advances
        assert accept_greedy([7, 8], [7, 8, 9]) == [7, 8, 9]
        # first mismatch ends the chain; e_0 always emits
        assert accept_greedy([7, 8], [5, 8, 9]) == [5]
        assert accept_greedy([7, 8], [7, 9, 1]) == [7, 9]
        assert accept_greedy([], [4]) == [4]

    def test_ngram_drafter_prompt_lookup(self):
        d = NGramDrafter(n=2)
        # most recent earlier occurrence of the tail bigram wins
        ctx = [1, 2, 3, 9, 1, 2, 4, 8, 1, 2]
        assert d.propose(ctx, 2) == [4, 8]
        assert d.propose([1, 2, 3], 2) == []    # no earlier occurrence
        assert d.propose([1, 2], 2) == []       # context too short

    def test_prefill_drafter_is_greedy_argmax(self, bundle_dir,
                                              reference_decode):
        model = DecodeModel(bundle_dir, warmup=False)
        d = PrefillDrafter(model, name="self")
        p = _prompt(3, 6)
        assert d.propose(p, 4) == reference_decode(p, 4)


# ---------------------------------------------------------------------------
# engine: sharing capacity + identity
# ---------------------------------------------------------------------------

def test_shared_prefix_at_least_halves_pool_residency(bundle_dir):
    """The acceptance floor: N concurrent sequences over one shared
    prompt must touch at most half the blocks with sharing on, with
    token-identical outputs. Deterministic block accounting: 4 prefix
    blocks + per-sequence tails vs 6 blocks per sequence."""
    prompt = _prompt(11, 4 * BLOCK)     # 4 full blocks, aligned
    hw, outs = {}, {}
    for share in (False, True):
        eng = DecodeEngine(bundle_dir, name="lm", kv_share=share)
        try:
            handles = [eng.generate(prompt, max_new_tokens=8)
                       for _ in range(SLOTS)]
            outs[share] = [h.result(timeout=120)["tokens"]
                           for h in handles]
            hw[share] = eng.pool.high_water
            snap = eng.metrics_snapshot()
        finally:
            eng.shutdown()
    assert outs[True] == outs[False]
    assert hw[False] / hw[True] >= 2.0, (hw, "sharing must at least "
                                         "halve the high-water mark")
    assert snap["kv_shared_hits"] >= SLOTS - 1
    assert snap["kv_shared_tokens"] >= (SLOTS - 1) * 4 * BLOCK


def test_aliased_blocks_extend_no_stale_leak(bundle_dir,
                                             reference_decode):
    """The no-stale-leak invariant over aliased blocks: a sequence
    admitted onto another's resident prefix reads rows IT never wrote —
    legal exactly because causal K/V is a pure function of the token
    prefix. Every aliased generation must equal the re-prefill oracle."""
    base = _prompt(13, 10)
    eng = DecodeEngine(bundle_dir, name="lm", kv_share=True)
    try:
        r1 = eng.generate(base, max_new_tokens=8).result(timeout=60)
        assert r1["tokens"] == reference_decode(base, 8)
        # same prompt again: full alias, zero prefix rewrites
        r2 = eng.generate(base, max_new_tokens=8).result(timeout=60)
        assert r2["tokens"] == r1["tokens"]
        # an EXTENDED prompt aliases the cached chain, writes only past it
        ext = base + _prompt(14, 6)
        r3 = eng.generate(ext, max_new_tokens=8).result(timeout=60)
        assert r3["tokens"] == reference_decode(ext, 8)
        snap = eng.metrics_snapshot()
        assert snap["kv_shared_hits"] >= 2
        # at idle only the index's own references remain resident
        assert eng.pool.blocks_in_use == eng.index.blocks_indexed
        assert eng.pool.blocks_shared == 0
    finally:
        eng.shutdown()


def test_aliased_admission_rewrites_no_shared_block(bundle_dir):
    """The seeding is one fixed-length scatter per bucket: the entries
    of aliased blocks name the null block, so a shared block keeps its
    bytes, and a full alias dispatches no scatter at all (the counter
    of host-moved bytes shows no block-id vector)."""
    base = _prompt(53, 2 * BLOCK)       # blocks 1, 2 (lowest id first)
    eng = DecodeEngine(bundle_dir, name="lm", kv_share=True)
    ids_size = eng.model.prefill_model.feed_dtypes()["src_ids"].itemsize

    def shared_bytes():
        return eng.scheduler.while_idle(
            lambda: [np.asarray(p)[[1, 2]] for p in eng.model._pools])

    def moved():
        return eng.metrics_snapshot()["prefill_host_bytes"]

    try:
        eng.generate(base, max_new_tokens=2).result(timeout=60)
        assert eng.index.blocks_indexed == 2
        before, m0 = shared_bytes(), moved()
        assert all(np.any(b != 0) for b in before)
        # an extended prompt (bucket 16) writes only past the alias
        eng.generate(base + _prompt(54, 6),
                     max_new_tokens=2).result(timeout=60)
        m1 = moved()
        assert m1 - m0 == V * 4 + (16 * ids_size + 4) + (16 // BLOCK * 4 + 4)
        # the same prompt again (bucket 8): every row resident
        eng.generate(base, max_new_tokens=2).result(timeout=60)
        assert moved() - m1 == V * 4 + (8 * ids_size + 4)
        assert eng.metrics_snapshot()["kv_shared_hits"] == 2
        for was, now in zip(before, shared_bytes()):
            np.testing.assert_array_equal(now, was)
    finally:
        eng.shutdown()


def test_cow_on_partial_tail_is_token_identical(bundle_dir,
                                                reference_decode):
    """A prompt ending INSIDE a cached block aliases it; the first
    decode write lands in that block and must copy-on-write, leaving
    the original owner's cached rows frozen."""
    base = _prompt(17, 8)               # 2 full blocks
    mid = base[:6]                      # ends 2 tokens into block 2
    eng = DecodeEngine(bundle_dir, name="lm", kv_share=True)
    try:
        r1 = eng.generate(base, max_new_tokens=8).result(timeout=60)
        assert r1["tokens"] == reference_decode(base, 8)
        r2 = eng.generate(mid, max_new_tokens=8).result(timeout=60)
        assert r2["tokens"] == reference_decode(mid, 8)
        snap = eng.metrics_snapshot()
        assert snap["kv_cow_copies"] >= 1, \
            "the partial-tail alias must trigger exactly the CoW path"
        # the donor's cached prefix was not corrupted by the copy
        r3 = eng.generate(base, max_new_tokens=8).result(timeout=60)
        assert r3["tokens"] == r1["tokens"]
    finally:
        eng.shutdown()


def test_cow_under_pool_exhaustion_degrades_never_corrupts(
        bundle_dir, reference_decode):
    """CoW needs a fresh block mid-flight; on a starved pool the
    scheduler releases index references and preempts rather than
    writing into a shared block. Outputs stay oracle-identical."""
    base = _prompt(19, 8)
    mid = base[:6]
    eng = DecodeEngine(bundle_dir, name="lm", kv_share=True,
                       pool_blocks=9)
    try:
        r0 = eng.generate(base, max_new_tokens=10).result(timeout=120)
        assert r0["tokens"] == reference_decode(base, 10)
        handles = [eng.generate(p, max_new_tokens=10)
                   for p in (mid, base, mid)]
        for p, h in zip((mid, base, mid), handles):
            assert h.result(timeout=180)["tokens"] == \
                reference_decode(p, 10)
        snap = eng.metrics_snapshot()
        assert snap["kv_blocks_in_use"] <= 8
    finally:
        eng.shutdown()


def test_admission_pins_matched_blocks_against_lru_release(
        bundle_dir, reference_decode):
    """Ordering regression: a big admission that matches a resident
    prefix AND needs eviction. _evict_for releases index references
    LRU-first — including, once everything else is drained, the very
    blocks the admission just matched. The admission's own pool
    references (taken at match time) must keep those blocks live;
    taking them only after eviction used to let the pool reclaim them
    and pool.share() then killed the scheduler thread."""
    cap = 19                              # pool_blocks=20
    base = _prompt(43, 2 * BLOCK)         # the donor prefix: 2 blocks
    low = _prompt(47, 3 * BLOCK)          # the low-priority victim
    big = base + _prompt(45, 73 - 2 * BLOCK)
    # the oracle's answers first: computed while the victim runs (a
    # step is a millisecond here) they would let it finish before the
    # big admission arrives, and nothing would be evicted
    want = [reference_decode(base, 2), reference_decode(big, 3),
            reference_decode(low, 48)]
    eng = DecodeEngine(bundle_dir, name="lm", kv_share=True,
                       pool_blocks=cap + 1)
    try:
        a = eng.generate(base, max_new_tokens=2)
        v = eng.generate(low, max_new_tokens=48, priority=-1)
        assert a.result(timeout=120)["tokens"] == want[0]
        # the victim must be RUNNING (holding blocks) before the big
        # admission arrives
        deadline = time.monotonic() + 60
        while eng.metrics_snapshot()["prefills"] < 2:
            assert time.monotonic() < deadline, "victim never admitted"
            time.sleep(0.01)
        # 73 tokens = 19 blocks = the whole pool: admission matches the
        # donor's 2 blocks, must evict the victim for the other 17, and
        # along the way release_lru drains the index — donor chain
        # included
        r = eng.generate(big, max_new_tokens=3).result(timeout=300)
        assert r["tokens"] == want[1]
        # the victim was preempted, resumed, and stayed token-identical
        assert v.result(timeout=300)["tokens"] == want[2]
        snap = eng.metrics_snapshot()
        assert snap["evictions"] >= 1, \
            "the scenario must actually exercise the eviction path"
        assert snap["kv_shared_hits"] >= 1, \
            "the pinned prefix must still be aliased after eviction"
    finally:
        eng.shutdown()


def test_admission_failure_never_kills_scheduler(bundle_dir,
                                                 reference_decode):
    """One bad sequence fails typed; the scheduler thread survives and
    keeps serving everyone else."""
    from paddle_tpu.serving.admission import RequestFailed

    p = _prompt(53, 6)
    eng = DecodeEngine(bundle_dir, name="lm", kv_share=True)
    try:
        real, state = eng.index.match, {"armed": True}

        def boom(tokens):
            if state["armed"]:
                state["armed"] = False
                raise RuntimeError("index corrupted")
            return real(tokens)

        eng.index.match = boom
        with pytest.raises(RequestFailed):
            eng.generate(p, max_new_tokens=4).result(timeout=60)
        r = eng.generate(p, max_new_tokens=4).result(timeout=60)
        assert r["tokens"] == reference_decode(p, 4)
    finally:
        eng.shutdown()


def test_defrag_remaps_index_and_preserves_aliasing(bundle_dir,
                                                    reference_decode):
    """Engine defrag at idle compacts index-held blocks; the remapped
    chains must still alias (and still be the right bytes)."""
    base = _prompt(23, 8)
    eng = DecodeEngine(bundle_dir, name="lm", kv_share=True)
    try:
        ref = reference_decode(base, 8)
        assert eng.generate(base, max_new_tokens=8).result(
            timeout=60)["tokens"] == ref
        before = eng.metrics_snapshot()["kv_shared_hits"]
        eng.defrag()
        r = eng.generate(base, max_new_tokens=8).result(timeout=60)
        assert r["tokens"] == ref
        assert eng.metrics_snapshot()["kv_shared_hits"] > before, \
            "the defragged chain must still produce index hits"
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# engine: speculative decoding identity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("drafter", ["self", "ngram"])
def test_speculative_decode_is_token_identical(bundle_dir,
                                               reference_decode,
                                               drafter):
    """>= 64 generated tokens, bit-identical to plain greedy decode.
    The self drafter is the deterministic upper bound (acceptance 1.0
    by construction); the n-gram drafter accepts whatever it earns —
    identity must hold at EVERY acceptance rate."""
    p = _prompt(29, 6)
    plain = DecodeEngine(bundle_dir, name="lm")
    try:
        ref = plain.generate(p, max_new_tokens=64).result(
            timeout=300)["tokens"]
        plain_steps = plain.metrics_snapshot()["decode_steps"]
    finally:
        plain.shutdown()
    assert ref == reference_decode(p, 64)
    eng = DecodeEngine(bundle_dir, name="lm", drafter=drafter, spec_k=3)
    try:
        r = eng.generate(p, max_new_tokens=64).result(timeout=300)
        assert r["tokens"] == ref
        snap = eng.metrics_snapshot()
        assert snap["spec_drafted"] > 0
        assert snap["decode_steps"] <= plain_steps
        if drafter == "self":
            assert snap["spec_acceptance_rate"] == 1.0
            assert snap["decode_steps"] < plain_steps, \
                "full acceptance must save dispatches"
    finally:
        eng.shutdown()


def test_speculation_survives_pool_pressure(bundle_dir,
                                            reference_decode):
    """Speculation never evicts a peer: on a starved pool drafts are
    dropped (plain steps) rather than stealing blocks, and outputs stay
    oracle-identical through the eviction churn."""
    prompts = [_prompt(s, 7) for s in (31, 32, 33)]
    eng = DecodeEngine(bundle_dir, name="lm", drafter="ngram", spec_k=3,
                       kv_share=True, pool_blocks=9)
    try:
        handles = [eng.generate(p, max_new_tokens=12) for p in prompts]
        for p, h in zip(prompts, handles):
            assert h.result(timeout=300)["tokens"] == \
                reference_decode(p, 12)
    finally:
        eng.shutdown()


def test_spec_verify_fault_falls_back_to_plain_decode(
        bundle_dir, reference_decode, monkeypatch):
    """Chaos site spec_verify: the drafter crashes mid-step; the
    scheduler eats it, falls back to a plain step, and the output is
    still token-identical. The session never sees the fault."""
    monkeypatch.setenv("PT_FAULT_INJECT", "spec_verify@2")
    faults.reset()
    p = _prompt(37, 6)
    eng = DecodeEngine(bundle_dir, name="lm", drafter="self", spec_k=3)
    try:
        r = eng.generate(p, max_new_tokens=16).result(timeout=120)
        assert r["tokens"] == reference_decode(p, 16)
        snap = eng.metrics_snapshot()
        assert snap["spec_fallbacks"] >= 1
        assert snap["spec_steps"] >= 1, \
            "speculation must resume after the one-shot fault"
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# knobs, fleet health, exposition, artifact floors
# ---------------------------------------------------------------------------

def test_env_knobs_wire_through(bundle_dir, monkeypatch):
    monkeypatch.setenv("PT_KV_SHARE", "1")
    monkeypatch.setenv("PT_SPEC_DRAFT", "ngram")
    monkeypatch.setenv("PT_SPEC_K", "2")
    eng = DecodeEngine(bundle_dir, name="lm", warmup=False)
    try:
        assert eng.kv_share and eng.index is not None
        assert isinstance(eng.drafter, NGramDrafter)
        assert eng.spec_k == 2
        desc = eng.describe()
        assert desc["kv_share"] is True
        assert desc["drafter"] == "ngram" and desc["spec_k"] == 2
    finally:
        eng.shutdown()


def test_fleet_replica_health_reports_decode_residency(bundle_dir):
    """The session-affinity health signal: a replica hosting decode
    engines exposes shared-block residency + acceptance next to queue
    depth in the same health dict the router and autoscaler read."""
    engine = ServingEngine()
    try:
        engine.load_decode_model("lm", bundle_dir, warmup=False,
                                 kv_share=True, drafter="self", spec_k=2)
        rep = Replica("r0", engine)
        p = _prompt(41, 8)
        engine.generate("lm", p, max_new_tokens=8).result(60)
        engine.generate("lm", p, max_new_tokens=8).result(60)
        h = rep.health()
        dec = h["decode"]
        assert dec["prefix_hits"] >= 1
        assert dec["kv_blocks_indexed"] >= 2
        assert dec["spec_acceptance_rate"] == 1.0
        assert set(h) >= {"queue_depth", "ewma_ms", "healthy"}
    finally:
        engine.shutdown()


def test_exposition_kv_and_spec_families_conform():
    sm = ServingMetrics()
    dm = sm.decode("lm")
    dm.on_prefix_hit(12, 3)
    dm.on_cow()
    dm.on_spec(3, 2)
    dm.on_spec_fallback()
    dm.set_gauges(active=1, waiting=0, blocks_in_use=4,
                  blocks_capacity=16, high_water=6, blocks_shared=2,
                  blocks_indexed=3)
    text = render_prometheus(sm.snapshot())
    assert not validate_exposition(text)
    families = {ln.split("{")[0] for ln in text.splitlines()
                if ln and not ln.startswith("#")}
    for fam in ("pt_kv_shared_hits_total", "pt_kv_shared_tokens_total",
                "pt_kv_cow_copies_total", "pt_kv_blocks_shared",
                "pt_kv_blocks_indexed", "pt_spec_steps_total",
                "pt_spec_drafted_total", "pt_spec_accepted_total",
                "pt_spec_fallbacks_total", "pt_spec_acceptance_rate"):
        assert fam in families, (fam, sorted(families))


def _valid_kv_row():
    return {
        "arms": {
            "unshared": {"high_water_blocks": 24, "tokens_per_s": 300.0},
            "shared": {"high_water_blocks": 12, "tokens_per_s": 900.0,
                       "shared_hits": 3, "shared_tokens": 96,
                       "cow_copies": 0},
        },
        "capacity_ratio_x": 2.0,
        "capacity_token_identical": True,
        "spec": {
            "plain_tokens_per_s": 1400.0, "spec_tokens_per_s": 1500.0,
            "speedup_x": 1.07, "token_identical": True,
            "drafted": 33, "accepted": 3, "acceptance_rate": 0.09,
            "fallbacks": 0,
            "decode_steps": {"plain": 189, "spec": 186},
        },
    }


class TestKvEconomicsValidator:
    def test_valid_row_passes(self):
        assert validate_kv_economics(_valid_kv_row()) == []

    @pytest.mark.parametrize("mutate,needle", [
        (lambda d: d.pop("arms"), "$.arms"),
        (lambda d: d["arms"].pop("shared"), "$.arms.shared"),
        (lambda d: d["arms"]["shared"].update(shared_hits=0),
         "shared_hits"),
        (lambda d: d.update(capacity_ratio_x=1.9), "capacity_ratio_x"),
        (lambda d: d.update(capacity_ratio_x=float("nan")),
         "capacity_ratio_x"),
        (lambda d: d.update(capacity_token_identical=False),
         "capacity_token_identical"),
        (lambda d: d["spec"].update(token_identical=False),
         "token_identical"),
        (lambda d: d["spec"].update(drafted=0), "drafted"),
        (lambda d: d["spec"].update(accepted=99), "accepted"),
        (lambda d: d["spec"].update(acceptance_rate=1.5),
         "acceptance_rate"),
        (lambda d: d["spec"]["decode_steps"].update(spec=200),
         "decode_steps"),
        (lambda d: d["spec"].update(speedup_x=0.8), "speedup_x"),
    ])
    def test_impossible_readings_refused(self, mutate, needle):
        row = _valid_kv_row()
        mutate(row)
        problems = validate_kv_economics(row)
        assert problems and any(needle in p for p in problems), \
            (needle, problems)

    def test_explained_slowdown_passes(self):
        row = _valid_kv_row()
        row["spec"].update(speedup_x=0.8,
                           explanation="CPU-tiny model: host drafting "
                                       "outweighs saved dispatches")
        assert validate_kv_economics(row) == []
