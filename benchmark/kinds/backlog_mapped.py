"""Traffic kind `backlog_mapped`: what `backlog` does (offline
generation: the whole backlog submitted in one piece before the window,
the window opened on a count of decode steps, tokens counted when
emitted), for a configuration of any architecture. The configuration
names, under `harness`, the module beside `_model.py` that maps its
published keys onto the program (`mapping`; `_model_olmoe.py` lists what
such a module gives) and the plain reference beside `reference.py`
(`reference`). A new architecture adds those two files and no kind.

What is architecture-neutral comes from `_serve.py` and `workload.py`
as it is; `backlog` and the Cerebras cells keep `_model.py`, which this
file does not touch.

Observations: those of `backlog`, plus `moe_assignments`,
`moe_experts_touched`, `moe_layer_steps` over the window where the
engine counts them (a model with experts), `model` and `block_size`, the
configuration's sizes for the readers that price bytes, and in a traced
run `traced`, the same counters over the traced seconds alone (read
where `kernel.calls` starts and stops counting, after the window).
"""

from __future__ import annotations

import gc
import importlib
import math
import shutil
import time
from typing import Dict

import numpy as np

import common
import workload
from kinds import _serve
from kinds.backlog import _whole

MODEL_NAME = _serve.MODEL_NAME

# The check: logits after a 256-token prefill and after each of 4
# teacher-forced decode steps through the paged cache, against the
# reference's full forward at the highest precision. Per compared
# position, the largest absolute difference over its 50,304 logits as a
# share of the reference logits' standard deviation. The server's f32
# matmuls run at the TPU's default precision (operands rounded to
# bfloat16, one pass), the reference's at the highest, so the number is
# what that precision gives through the configuration's layers, not
# zero: on the CPU, where f32 is f32, the same path gives 3e-6
# (tests/test_olmoe.py).
#
# With experts that rounding also reaches the router's INPUT (about 1%
# of the residual stream), and where a token's 8th and 9th gates lie
# closer than that the program takes the other expert: three tokens of
# four do in some layer, and such a position's logits are 0.08-0.23
# from the reference's, because one expert's output is several per cent
# of the stream: as much as the mildest fault of the cache gives (a
# context one token short: from 0.22), so no limit on the plain
# difference can hold the one and let the other pass. The check
# therefore takes the experts
# the program chose, in every layer and for every one of the 260 tokens
# (`DecodeModel.last_routes`), and has the reference compute the same
# equations on THOSE experts, with its own gates for them
# (`reference.logits_on_routes`). Four limits, each from readings on the
# chip (PERF.md section 6, PR 27: 48 seeded sequences on three sets of
# weights with `tools/olmoe_check_readings.py`, and 12 runs of the cell):
#   ROW_MAX, every compared position against the reference on the
# program's routes: 0.06. The program reads 0.025-0.039 at every one of
# 300 positions. A fault of ONE position shows here: the slot's context
# one token short at the last step (its newest K/V row unread, RoPE one
# position early) reads 0.22-0.53 there, gates renormalised over the
# chosen eight 1.3-1.8 everywhere.
#   RMS_MAX, the root mean square over all five positions' logits: 0.0085.
# This is the limit that holds the configuration to its precision. The
# program reads 0.0065-0.0079 in 60 checks (mean 0.00716, standard
# deviation 0.00032: the limit is 4.2 of them out); the reference
# computed in bfloat16 THROUGHOUT (weights, residual stream, every
# intermediate) and compared the same way reads 0.0086-0.0104 in 96
# checks of 96 (mean 0.0097, the limit 3.2 of its deviations under), so
# it comes out as not correct, while its largest position (0.040-0.056)
# stays under ROW_MAX: on this chip an f32 matmul at the default
# precision IS a product of bfloat16 operands, so float32 storage is
# worth a factor of 1.35 and no more, and only a statistic as steady as
# this one can hold it. A file of bfloat16 weights (ROADMAP S4) is
# another configuration with a limit of its own.
#   TIE_MAX, every layer and token: how far the program's choice may lie
# from the reference's own, as the shortfall of the weakest chosen gate
# under the reference's 8th (0 where the sets are equal): 0.1. Three
# tokens of four sit on another expert than the reference's own in some
# layer, all of them near ties: the largest shortfall of a check read
# 0.017-0.047 (0.062 for the bfloat16 reference). This is what keeps the
# forcing honest: a program whose router is wrong cannot hide behind it
# (the faulty step above: 0.13-0.43).
#   the weights the reference reads are the server's own copy on the
# device (the scope's are gone by then: two copies do not fit the chip),
# so their fingerprints are taken from the scope before the export and
# must come back bit for bit: an export or a load that changes a weight
# (or stores it in fewer bits) fails here, whatever the logits say.
ROW_MAX = 0.06
RMS_MAX = 0.0085
TIE_MAX = 0.1

MOE_COUNTERS = ("moe_assignments", "moe_experts_touched", "moe_layer_steps")


def _harness(cell):
    names = cell.config["harness"]
    return (importlib.import_module("kinds." + names["mapping"]),
            importlib.import_module(names["reference"]))


def _fingerprint(weights) -> np.ndarray:
    """Three float32 sums of every weight of a tree, taken on the
    device: plain, of squares, and against a ramp over the flat index
    (so that a transposed or shifted weight shows). The same executable
    on the same values gives the same bits."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def sums(w):
        flat = w.reshape(-1).astype(jnp.float32)
        ramp = (jnp.arange(flat.shape[0], dtype=jnp.int32) % 251).astype(
            jnp.float32)
        return jnp.stack([jnp.sum(flat), jnp.sum(flat * flat),
                          jnp.sum(flat * ramp)])

    return np.stack([np.asarray(sums(w))
                     for w in jax.tree_util.tree_leaves(weights)])


def _cached(model, ids, p_len, m):
    """What `_serve._cached_logits` does (prefill ids[:p_len], then
    ids[p_len:] one token a step through the paged cache, teacher-forced,
    in slot 0 on pool blocks 1.., scheduler idle, pools zeroed after),
    and beside the logits rows the experts the program chose for each of
    the p_len + m tokens, [layers, p_len + m, k]; None for a model
    without experts."""
    bs = model.block_size
    blocks = list(range(1, 1 + math.ceil((p_len + m) / bs)))
    last, kv = model.prefill([int(t) for t in ids[:p_len]])
    dense = model.last_routes is None
    routes = [] if dense else [np.asarray(model.last_routes)[:, :p_len]]
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
        if not dense:
            routes.append(np.asarray(model.last_routes)[:, :1])
    model.reset_pools()
    return np.stack(rows), None if dense else np.concatenate(routes, 1)


def check(mapping, reference, model, sz, cfg, ids, p_len, m, prints):
    """The comparison that decides `correct` (the text at ROW_MAX).
    Returns (correct, what it read)."""
    got, routes = _cached(model, ids, p_len, m)
    weights = mapping.reference_weights(model.weights.__getitem__,
                                        sz["n_layers"])
    same = bool(np.array_equal(_fingerprint(weights), prints))
    want, shortfall = mapping.reference_on_routes(
        reference, weights, cfg, ids, routes)
    want = np.asarray(want)[p_len - 1:p_len + m]
    by_row = np.max(np.abs(got - want), axis=-1) / np.std(want)
    rms = float(np.sqrt(np.mean(np.square(got - want))) / np.std(want))
    read = dict(
        max_abs_err_over_std=float(by_row.max()),
        max_by_position=[round(float(v), 5) for v in by_row],
        rms_err_over_std=rms, row_max=ROW_MAX, rms_max=RMS_MAX,
        reference_std=float(np.std(want)),
        weights_came_back_bit_for_bit=same)
    ok = (same and bool(np.all(np.isfinite(got)))
          and by_row.max() <= ROW_MAX and rms <= RMS_MAX)
    if shortfall is not None:
        shortfall = np.asarray(shortfall)
        read.update(tie_max=TIE_MAX, max_shortfall=float(shortfall.max()),
                    tokens_on_another_expert=int(
                        np.sum(np.any(shortfall > 0, axis=0))),
                    compared_on_another_expert=int(
                        np.sum(np.any(shortfall[:, p_len - 1:] > 0, axis=0))))
        ok = ok and float(shortfall.max()) <= TIE_MAX
    return bool(ok), read


def bring_up(cell, args, device):
    """`_serve.bring_up` with the architecture's part behind the
    mapping. Returns (serving engine, decode engine, sizes, observations
    so far, correct)."""
    import paddle_tpu as pt
    from paddle_tpu import io as pio
    from paddle_tpu.serving import ServingEngine

    cfg, tr = cell.config, cell.traffic
    mapping, reference = _harness(cell)
    sz, srv = mapping.sizes(cfg), cfg["serving"]
    seed = args.seed
    obs: Dict = {}

    # weights on the device, from the seed, in one start-up program
    _, startup = mapping.build_params_only(pt, sz, seed)
    scope = pt.Scope()
    with pt.scope_guard(scope):
        pt.Executor().run(startup)

    # what the check will hold the server's copy of the weights to
    prints = _fingerprint(mapping.reference_weights(scope.find_var,
                                                    sz["n_layers"]))

    bundle = common.fresh_work_dir("bundle_" + cell.name)
    t0 = time.perf_counter()
    pio.export_decode_model(
        bundle, mapping.export_cfg(sz), scope=scope,
        length_buckets=tuple(tr["prefill_buckets"]),
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

    # one seeded sequence, positions P-1 .. P+m-1
    chk = tr["check"]
    p_len, m = int(chk["prompt_len"]), int(chk["decode_steps"])
    rng = np.random.RandomState((seed + 1) % (2 ** 32))
    ids = rng.randint(0, sz["vocab"], p_len + m)
    t0 = time.perf_counter()
    correct, read = dec.scheduler.while_idle(lambda: check(
        mapping, reference, dec.model, sz, cfg, ids, p_len, m, prints))
    obs["check_s"] = time.perf_counter() - t0
    common.note(check="prefill_then_decode_logits_on_the_programs_routes",
                positions=m + 1, correct=correct, **read)

    # every shape the traffic uses: one short request per prompt length,
    # the shortest alone first (`_serve.bring_up` says why)
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
    return engine, dec, sz, obs, correct


def counters(dec) -> Dict:
    """What `_serve.counters` gives, and the routing counters where the
    engine has them, all from ONE snapshot: `DecodeMetrics.snapshot`
    takes them under the lock that guards its step counts, so
    `moe_assignments`, `decode_steps` and `slots_used_sum` describe the
    same steps to the step."""
    snap = dec.metrics_snapshot()
    keys = _serve.COUNTERS + ("slots_used_sum", "slots_capacity_sum")
    out = {k: snap[k] for k in keys}
    out.update({k: snap[k] for k in MOE_COUNTERS if k in snap})
    return out


def run(cell, args, device, t_start):
    cfg, tr = cell.config, cell.traffic
    traced = bool(args.trace)
    mapping, _ = _harness(cell)
    engine, dec, sz, obs, correct = bring_up(cell, args, device)
    try:
        spans = _serve.ProgramSpans(dec.model) if traced else None
        tracer = common.Tracer(traced, cell.name, bool(args.rehearse))
        slots = int(cfg["serving"]["slots"])
        requests = workload.request_groups(
            tr, args.seed, int(tr["requests"]), sz["vocab"])
        workload.stagger_first(requests, slots)
        compiles = common.CompileCounter()
        gc.collect()
        gc.freeze()

        # one piece: the scheduler sees the whole backlog at once, in
        # submission order
        handles = dec.scheduler.while_idle(lambda: [
            engine.generate(MODEL_NAME, r["prompt"],
                            max_new_tokens=r["max_new"])
            for r in requests])
        base = counters(dec)
        lead_in = int(tr["lead_in_steps"])
        deadline = time.perf_counter() + 300
        while counters(dec)["decode_steps"] - base["decode_steps"] \
                < lead_in:
            if time.perf_counter() > deadline:
                raise SystemExit("benchmark: the lead-in never ended")
            time.sleep(0.002)

        # -- the measured window ----------------------------------------------
        compiles_before = compiles.count
        before = counters(dec)
        t_open = time.perf_counter()
        obs["setup_s"] = t_open - t_start
        time.sleep(max(0.0, t_open + args.seconds - time.perf_counter()))
        window_s = time.perf_counter() - t_open
        after = counters(dec)
        compiles_in_window = compiles.count - compiles_before
        gauges = dec.metrics_snapshot()
        waiting, active = gauges["waiting"], gauges["active"]
        # a traced run profiles the seconds after the window has closed,
        # on the same backlog (`backlog.run` says why), and reads the
        # counters again at those seconds' two ends: what the traced
        # steps routed is not the window's mean
        traced_ends = _serve.trace_for(tracer, spans,
                                       float(tr["trace_seconds"]),
                                       lambda: counters(dec))
    finally:
        engine.shutdown(drain=False)

    counts = _serve.window_counts(before, after)
    whole = all(_whole(h, r) for h, r in zip(handles, requests))
    failed = (counts["failed"] + counts["shed_overload"]
              + counts["shed_deadline"])
    if waiting == 0:
        raise SystemExit("benchmark: the backlog ran dry inside the "
                         "window; the traffic file needs more requests")
    kernel = {}
    if spans is not None and spans.decode_calls:
        kernel = dict(mapping.kernel_shape(sz), slots=slots,
                      context_tokens=spans.context_tokens,
                      calls=spans.decode_calls)
    obs.update(counts, window_s=window_s,
               compiles_in_window=compiles_in_window, kernel=kernel,
               model=dict(sz, **sz["block"]),
               block_size=int(cfg["serving"]["block_size"]))
    if traced_ends:
        obs["traced"] = _serve.window_counts(*traced_ends)
        common.note(traced_seconds=obs["traced"])
    common.note(window=dict(
        counts, seconds=window_s, active_at_close=active,
        waiting_at_close=waiting,
        slot_occupancy=(counts["slots_used_sum"]
                        / max(counts["slots_capacity_sum"], 1))))
    return dict(obs=obs, correct=bool(correct and whole and not failed),
                attempted=counts["completed"] + active, failed=failed,
                reduced=tracer.reduce())
