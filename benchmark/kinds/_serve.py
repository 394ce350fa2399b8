"""What the two serving kinds share: weights from the seed, the bundle
through `io.export_decode_model`, the engine through
`ServingEngine.load_decode_model`, the comparison with the reference,
the warm-up of every shape the cell's traffic uses, and the counters at
a window's two ends."""

from __future__ import annotations

import gc
import math
import shutil
import time
from typing import Dict, List

import numpy as np

import common
import reference
import workload
from kinds import _model

MODEL_NAME = "lm"

# The server holds f32 weights and f32 K/V pools, and on the TPU its f32
# matmuls run at XLA's default precision (one bf16 pass; PR 22), while
# the reference runs them at the highest. The check is the largest
# absolute difference over the compared positions (5 x 50,257 logits),
# as a share of the reference logits' standard deviation. Through 24
# layers the chip gave 0.032-0.034 (root mean square 0.007; PERF.md,
# PR 24), so 0.1 is three times what this precision gives, and far
# under what a wrong block table, a stale cache row or a shifted
# position give (those move logits by a sizeable part of their spread:
# 0.5 and more). The same check on the CPU, where f32 is f32, gives
# 2e-6: a server that claims the highest precision would be held to
# that, not to this.
LOGIT_TOL = 0.1


def bring_up(cell, args, device):
    """Returns (serving engine, decode engine, observations so far,
    correct)."""
    import jax
    import paddle_tpu as pt
    from paddle_tpu import io as pio
    from paddle_tpu.serving import ServingEngine

    cfg, tr = cell.config, cell.traffic
    sz, srv = _model.sizes(cfg), cfg["serving"]
    seed = args.seed
    obs: Dict = {}

    # weights on the device, from the seed, in one start-up program
    _, startup = _model.build_params_only(pt, sz, seed)
    scope = pt.Scope()
    with pt.scope_guard(scope):
        pt.Executor().run(startup)

    # the reference's logits for one seeded sequence, before the
    # weights move into the bundle: positions P-1 .. P+m-1
    chk = tr["check"]
    p_len, m = int(chk["prompt_len"]), int(chk["decode_steps"])
    rng = np.random.RandomState((seed + 1) % (2 ** 32))
    ids = rng.randint(0, sz["vocab"], p_len + m)
    want = np.asarray(reference.logits(
        _model.reference_weights(scope, sz["n_layers"]), ids,
        sz["n_heads"])[p_len - 1:p_len + m])

    bundle = common.fresh_work_dir("bundle_" + cell.name)
    t0 = time.perf_counter()
    pio.export_decode_model(
        bundle, dict(vocab_size=sz["vocab"], n_layers=sz["n_layers"],
                     d_model=sz["d_model"], n_heads=sz["n_heads"],
                     d_ff=sz["d_ff"], max_context=sz["max_len"]),
        scope=scope, length_buckets=tuple(tr["prefill_buckets"]),
        slots=int(srv["slots"]), block_size=int(srv["block_size"]),
        pool_blocks=int(srv["pool_blocks"]))
    obs["export_s"] = time.perf_counter() - t0
    for name in list(scope.local_var_names()):
        scope.erase(name)        # the server loads its own copy
    del scope
    gc.collect()

    engine = ServingEngine()
    t0 = time.perf_counter()
    engine.load_decode_model(MODEL_NAME, bundle,
                             queue_depth=int(tr["queue_depth"]),
                             max_new_tokens=int(srv["max_new_tokens"]))
    obs["load_warm_s"] = time.perf_counter() - t0
    shutil.rmtree(bundle, ignore_errors=True)   # 4 bytes a parameter
    dec = engine.decode_engine(MODEL_NAME)

    got = dec.scheduler.while_idle(
        lambda: _cached_logits(dec.model, ids, p_len, m))
    err = float(np.max(np.abs(got - want)) / np.std(want))
    rms = float(np.sqrt(np.mean(np.square(got - want))) / np.std(want))
    correct = bool(np.all(np.isfinite(got)) and err <= LOGIT_TOL)
    common.note(check="prefill_then_decode_logits", positions=m + 1,
                max_abs_err_over_std=err, rms_err_over_std=rms,
                tol=LOGIT_TOL,
                reference_std=float(np.std(want)), correct=correct)

    # every shape the traffic uses: one short request per prompt length
    # (the K/V seeding path builds an executable per block count). The
    # shortest goes alone first: the pools `reset_pools` makes are
    # uncommitted arrays, the pools a decode step returns are committed
    # ones, and XLA builds the seeding scatter again for those. Serving
    # only ever sees the second kind, so the warm-up must too (PR 24
    # found one build per prompt length inside the window otherwise).
    t0 = time.perf_counter()
    lens = sorted(set(workload.lengths_of(tr["prompt_lens"])))
    rng = np.random.RandomState((seed + 2) % (2 ** 32))
    for batch in (lens[:1], lens):
        handles = [engine.generate(MODEL_NAME,
                                   rng.randint(0, sz["vocab"], n).tolist(),
                                   max_new_tokens=2) for n in batch]
        for h in handles:
            h.result(timeout=600)
    obs["warm_requests_s"] = time.perf_counter() - t0
    return engine, dec, obs, correct


def _cached_logits(model, ids, p_len, m):
    """Prefill ids[:p_len], then feed ids[p_len:] one token a step
    through the paged cache (teacher-forced, so the comparison does not
    hang on an argmax of random logits). Rows: the last prompt position,
    then each decoded position. Runs with the scheduler idle, on pool
    blocks 1.. (0 is the null block); the pools are zeroed after."""
    bs = model.block_size
    blocks = list(range(1, 1 + math.ceil((p_len + m) / bs)))
    last, kv = model.prefill([int(t) for t in ids[:p_len]])
    model.seed_sequence(blocks[:math.ceil(p_len / bs)], kv)
    rows = [np.asarray(last)]
    tokens = np.zeros(model.slots, np.int64)
    lens = np.zeros(model.slots, np.int32)
    tables = np.zeros((model.slots, model.max_blocks_per_seq), np.int32)
    tables[0, :len(blocks)] = blocks
    for j in range(m):
        tokens[0] = ids[p_len + j]
        lens[0] = p_len + j + 1
        rows.append(np.asarray(model.decode_step(tokens, lens,
                                                 tables))[0])
    model.reset_pools()
    return np.stack(rows)


# `paged_live_pages`: the pages a layer's paged call had to read, summed
# over slots and steps (`DecodeMetrics.on_paged_pages`): every serve
# cell's `serve_step_mfu` prices its cache rows by it (PR 52)
COUNTERS = ("tokens_out", "decode_steps", "prefills", "prefill_tokens",
            "prefill_s", "decode_s", "completed", "failed",
            "shed_overload", "shed_deadline", "evictions",
            "paged_live_pages")


def counters(dec) -> Dict:
    snap = dec.metrics_snapshot()
    out = {k: snap[k] for k in COUNTERS}
    out["slots_used_sum"] = dec.metrics.slots_used_sum
    out["slots_capacity_sum"] = dec.metrics.slots_capacity_sum
    return out


def window_counts(before: Dict, after: Dict) -> Dict:
    return {k: after[k] - before[k] for k in before}


class ProgramSpans:
    """Traced runs only: puts the engine's prefill, K/V seeding and
    decode step on the profiler's clock from the benchmark's side, by
    wrapping the three methods on this one `DecodeModel` object, and
    keeps what the kernel roofline needs (context rows a step attends
    to) and the start of every prefill (queue wait). Spans inside the
    program are for the tracing issue."""

    def __init__(self, model):
        import jax
        self.context_tokens = 0
        self.decode_calls = 0
        self.prefill_starts: List[float] = []
        self.counting = False
        prefill, seed, step = (model.prefill, model.seed_sequence,
                               model.decode_step)
        annotate = jax.profiler.TraceAnnotation

        def traced_prefill(token_ids):
            self.prefill_starts.append(time.perf_counter())
            with annotate("program/prefill"):
                return prefill(token_ids)

        def traced_seed(*a, **kw):
            with annotate("program/seed_kv"):
                return seed(*a, **kw)

        def traced_step(token_ids, context_lens, block_tables):
            if self.counting:
                self.context_tokens += int(np.sum(context_lens))
                self.decode_calls += 1
            with annotate("program/decode_step"):
                return step(token_ids, context_lens, block_tables)

        model.prefill = traced_prefill
        model.seed_sequence = traced_seed
        model.decode_step = traced_step


def trace_for(tracer, spans, seconds: float, snapshot=None):
    """Trace `seconds` of the open window from the calling thread.
    `snapshot`, where given, is called where the spans' counting begins
    and where it ends (inside the profiler's start and stop, which take
    seconds of their own), and what it returned there comes back as a
    pair: the program's counters over the steps `spans` counted."""
    if not tracer.enabled:
        return None
    tracer.start()
    first = snapshot() if snapshot else None
    spans.counting = True
    time.sleep(seconds)
    spans.counting = False
    last = snapshot() if snapshot else None
    tracer.stop()
    return (first, last) if snapshot else None


def kernel_shape(cell, spans) -> Dict:
    sz = _model.sizes(cell.config)
    if spans is None or not spans.decode_calls:
        return {}
    return dict(context_tokens=spans.context_tokens,
                calls=spans.decode_calls, layers=sz["n_layers"],
                heads=sz["n_heads"],
                head_dim=sz["d_model"] // sz["n_heads"],
                slots=int(cell.config["serving"]["slots"]))
