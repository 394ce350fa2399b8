#!/usr/bin/env python3
"""The readings behind the limits in
`configs/phi-4-mini-flash-reasoning-serve.json` (`harness.limits`, which
`kinds/backlog_mapped_hybrid.py` holds its check to), on the chip: run it
again when the numerics change (another XLA, another kernel, another
precision of the file) and set the limits from what it prints. After
`lfm2_check_readings.py`.

    python3 benchmark/tools/phi4flash_check_readings.py <weights seed> <n> [--program-only]

On one set of weights and `n` seeded sequences, every line one check's
readings as the kind takes them (per position, root mean square) and
whether the configuration's limits pass it. (1) While the scope holds the
weights, the reference made wrong in one part and taken for the program,
against the highest-precision reference: `bf16_throughout` (every weight
cast where it is used, the residual stream and every intermediate: the
precision below the configuration's), `fault_window_511` / `_513`,
`fault_lambda_dropped` (no second softmax), `fault_lambda_cut_index`
(lambda_init from the layer's index in the cut, not the published one),
`fault_no_subnorm`, `fault_subnorm_unscaled` (its (1 - lambda_init) left
out), `fault_memory_after_gate`, `fault_cross_windowed` (a cross layer
reading a window layer's rows), `fault_dt_bias_after_softplus`,
`fault_former_state` (the decode steps start from the state the slot's
former owner left) and `fault_state_at_bucket_end` (from the state the
padded bucket's last rows leave: padding rows that moved it). Each has to
fail at least one limit. (2) The served bundle: the program itself,
admitted as the kind admits it, `fault_short` (the slot's context one
row short at the last step) and `fault_cross_on_window_table` (the step's
full-pool readers given the window layers' table).
"""
import gc
import json
import os
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]
import numpy as np  # noqa: E402

import common  # noqa: E402
import reference_phi4flash as ref  # noqa: E402
from kinds import _model_phi4flash as mapping  # noqa: E402
from kinds import backlog_mapped as bm  # noqa: E402
from kinds import backlog_mapped_hybrid as hy  # noqa: E402
import paddle_tpu as pt  # noqa: E402
from paddle_tpu import io as pio  # noqa: E402
from paddle_tpu.core.compile_cache import enable_compile_cache  # noqa: E402
from paddle_tpu.serving.decode.engine import DecodeModel  # noqa: E402

enable_compile_cache()
seed, n_seq = int(sys.argv[1]), int(sys.argv[2])
# (2) alone, where only the program has changed since the last readings
program_only = "--program-only" in sys.argv
args = [a for a in sys.argv if a != "--program-only"]
manifest = args[3] if len(args) > 3 \
    else os.path.join(ROOT, "BENCHMARK.json")   # a rehearsal brings its own
cell = common.Cell(manifest, "phi4flash_serve_rollout_reason_s64")
cfg, tr = cell.config, cell.traffic
sz, srv = mapping.sizes(cfg), cfg["serving"]
hp = ref.Hyper.of(cfg)
limits = {k: float(v) for k, v in cfg["harness"]["limits"].items()}
chk = tr["check"]
p_len, m = int(chk["prompt_len"]), int(chk["decode_steps"])
slot, former_len = int(chk["slot"]), int(chk["former_len"])
ROWS = list(range(p_len - 1, p_len + m))
buckets = sorted(tr["prefill_buckets"])
bucket = min(b for b in buckets if b >= p_len)
former_bucket = min(b for b in buckets if b >= former_len)
NO_TIE = np.zeros((1, p_len + m), np.float32)

FAULTS = dict(
    fault_window_511=dict(window=hp.window - 1),
    fault_window_513=dict(window=hp.window + 1),
    fault_lambda_dropped=dict(lam="dropped"),
    fault_lambda_cut_index=dict(lam="cut_index"),
    fault_no_subnorm=dict(subnorm="none"),
    fault_subnorm_unscaled=dict(subnorm="unscaled"),
    fault_memory_after_gate=dict(memory="after"),
    fault_cross_windowed=dict(cross="windowed"),
    fault_dt_bias_after_softplus=dict(dt_bias="after"))


def say(who, j, got, want):
    read = hy.readings(got, want, NO_TIE, p_len)
    read["passes"] = hy.within(read, limits)
    for key in ("max_shortfall", "tokens_on_another_expert",
                "compared_on_another_expert"):
        del read[key]
    print(json.dumps(dict(weights_seed=seed, seq=j, who=who, **read)),
          flush=True)


t0 = time.perf_counter()
_, startup = mapping.build_params_only(pt, sz, seed)
scope = pt.Scope()
with pt.scope_guard(scope):
    pt.Executor().run(startup)
weights = mapping.reference_weights(scope.find_var, sz["n_layers"])
prints = bm._fingerprint(weights)
seqs, plains = [], []
for j in range(n_seq):
    ids = np.random.RandomState((seed + 2000 + j) % (2 ** 32)).randint(
        0, sz["vocab"], p_len + m)
    seqs.append(ids)
    plains.append(np.asarray(ref.logits(weights, ids, hp, rows=ROWS)))
    if j >= 2 or program_only:
        continue
    say("bf16_throughout", j, np.asarray(ref.logits(
        weights, ids, hp._replace(dtype="bfloat16"), rows=ROWS)),
        plains[-1])
    if j >= 1:
        continue
    for who, wrong in FAULTS.items():
        say(who, j, np.asarray(ref.logits(
            weights, ids, hp._replace(**wrong), rows=ROWS)), plains[-1])
    padded = np.concatenate([ids[:p_len],
                             np.zeros(bucket - p_len, ids.dtype)])
    former = np.concatenate([hy.former_ids(ids, former_len),
                             hy.former_ids(ids, 1)])
    for who, other in (("fault_state_at_bucket_end", padded),
                       ("fault_former_state", former)):
        say(who, j, np.asarray(ref.logits(
            weights, ids, hp, rows=ROWS,
            state=(p_len, ref.states(weights, other, hp)))), plains[-1])
print(json.dumps(dict(phase1_s=time.perf_counter() - t0)), flush=True)
del weights
bundle = common.fresh_work_dir("bundle_check_readings")
pio.export_decode_model(
    bundle, mapping.export_cfg(sz), scope=scope,
    length_buckets=tuple(sorted({former_bucket, bucket})),
    slots=int(srv["slots"]), block_size=int(srv["block_size"]),
    pool_blocks=int(srv["pool_blocks"]))
for name in list(scope.local_var_names()):
    scope.erase(name)
del scope
gc.collect()
model = DecodeModel(bundle, warmup=True)
shutil.rmtree(bundle, ignore_errors=True)
weights = mapping.reference_weights(model.weights.__getitem__, sz["n_layers"])
print(json.dumps(dict(
    weights_came_back_bit_for_bit=bool(
        np.array_equal(bm._fingerprint(weights), prints)),
    cache=model.cache, pages_per_block=model.paged_block_pages,
    state_bytes=model.state_bytes)), flush=True)


def wrongly(change):
    """`hy._cached` with the step's arguments changed by `change(lens,
    tables, wtables) -> (lens, tables, wtables)` at the LAST step."""
    step = model.decode_step

    def wrong_last(tokens, lens, tables, wtables):
        if lens[slot] == p_len + m:
            lens, tables, wtables = change(lens.copy(), tables, wtables)
        return step(tokens, lens, tables, wtables)

    def run(ids):
        model.decode_step = wrong_last
        try:
            return hy._cached(model, ids, p_len, m, slot, former_len)
        finally:
            model.decode_step = step

    return run


def _short(lens, tables, wtables):
    lens[slot] -= 1
    return lens, tables, wtables


one_short = wrongly(_short)
# the full pool's writer and readers on the window layers' table
on_window_table = wrongly(lambda lens, tables, wtables:
                          (lens, wtables, wtables))

for j, ids in enumerate(seqs):
    say("program", j, hy._cached(model, ids, p_len, m, slot, former_len),
        plains[j])
    if j < 2:
        say("fault_short", j, one_short(ids), plains[j])
        say("fault_cross_on_window_table", j, on_window_table(ids),
            plains[j])
print(json.dumps(dict(step_aliased_bytes=model.step_aliased_bytes,
                      total_s=time.perf_counter() - t0)), flush=True)
