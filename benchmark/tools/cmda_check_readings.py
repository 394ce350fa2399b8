#!/usr/bin/env python3
"""The readings behind the limits in
`configs/command-a-plus-05-2026-serve.json` (`harness.limits`, which
`kinds/backlog_mapped_win.py` holds its check to), on the chip: run it
again when the numerics change (another XLA, another kernel, another
precision of the file) and set the limits from what it prints. After
`keye_check_readings.py`.

    python3 benchmark/tools/cmda_check_readings.py <weights seed> <n> [--program-only]

On one set of weights and `n` seeded sequences, every line one check's
readings as the kind takes them (per position, root mean square, the
experts' largest shortfall) and whether the configuration's limits pass
it. (1) While the scope holds the weights, the reference made wrong in
one part and taken for the program, its logits with the routes it
reports, against the highest-precision reference on those:
`bf16_throughout` (every weight cast where it is used, the residual
stream and every intermediate: the precision below the configuration's),
`fault_window_long` / `fault_window_short` (the window one row long or
short), `fault_full_rotated` (a full layer rotated), `fault_window_plain`
(no layer rotated), `fault_oldest_page` (the oldest page of a window
read whole: the rows behind the window in it unmasked),
`fault_shared_sum` (the shared experts summed, not averaged),
`fault_wrong_group` (query head j reading K/V head j % 8) and
`fault_pair_dropped` (a token's weakest pair on a held expert left out).
Each of these has to fail at least one limit. (2) The served bundle: the
program itself, and `fault_short`, the slot's context one row short at
the last step (its newest K and V rows unread, one position early).
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
import reference_cmda as ref  # noqa: E402
from kinds import _model_cmda as mapping  # noqa: E402
from kinds import backlog_mapped as bm  # noqa: E402
from kinds import backlog_mapped_win as win  # noqa: E402
import paddle_tpu as pt  # noqa: E402
from paddle_tpu import io as pio  # noqa: E402
from paddle_tpu.serving.decode.engine import DecodeModel  # noqa: E402

seed, n_seq = int(sys.argv[1]), int(sys.argv[2])
# (2) alone, where only the program has changed since the last readings
program_only = "--program-only" in sys.argv
args = [a for a in sys.argv if a != "--program-only"]
manifest = args[3] if len(args) > 3 \
    else os.path.join(ROOT, "BENCHMARK.json")   # a rehearsal brings its own
cell = common.Cell(manifest, "cmdaplus_serve_rollout_10k")
cfg, tr = cell.config, cell.traffic
sz, srv = mapping.sizes(cfg), cfg["serving"]
hp = ref.Hyper.of(cfg)
limits = {k: float(v) for k, v in cfg["harness"]["limits"].items()}
p_len, m = int(tr["check"]["prompt_len"]), int(tr["check"]["decode_steps"])
ROWS = list(range(p_len - 1, p_len + m))
bucket = min(b for b in tr["prefill_buckets"] if b >= p_len)

FAULTS = dict(
    fault_window_long=dict(window_off=1),
    fault_window_short=dict(window_off=-1),
    fault_full_rotated=dict(rotate="all"),
    fault_window_plain=dict(rotate="none"),
    fault_oldest_page=dict(page=int(srv["block_size"])),
    fault_shared_sum=dict(shared="sum"),
    fault_wrong_group=dict(pairing="strided"),
    fault_pair_dropped=dict(drop=True))


def say(who, j, got, routes, weights, ids, plain=None):
    want, tie = ref.logits_on_routes(weights, ids, hp, routes, rows=ROWS)
    read = win.readings(got, want, tie, p_len)
    read["passes"] = win.within(read, limits)
    if plain is not None:    # what the routes explain
        d = np.abs(np.asarray(got, np.float32) - plain) / np.std(plain)
        read["max_by_position_plain"] = [round(float(v), 4)
                                         for v in d.max(-1)]
    print(json.dumps(dict(weights_seed=seed, seq=j, who=who, **read)),
          flush=True)


def as_program(weights, ids, wrong):
    """The reference under `wrong` taken for the program: its logits,
    and the routes it would report."""
    got, routes = ref.logits_and_choices(weights, ids, wrong, rows=ROWS)
    return np.asarray(got), np.asarray(routes)


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
    say("bf16_throughout", j,
        *as_program(weights, ids, hp._replace(dtype="bfloat16")), weights,
        ids, plains[-1])
    if j >= 1:
        continue
    for who, wrong in FAULTS.items():
        say(who, j, *as_program(weights, ids, hp._replace(**wrong)),
            weights, ids)
print(json.dumps(dict(phase1_s=time.perf_counter() - t0)), flush=True)
del weights
bundle = common.fresh_work_dir("bundle_check_readings")
pio.export_decode_model(
    bundle, mapping.export_cfg(sz), scope=scope, length_buckets=(bucket,),
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
    cache=model.cache, pages_per_block=model.paged_block_pages)),
    flush=True)


def one_short(ids):
    """`win._cached` with the slot's context one row short at the LAST
    step."""
    step = model.decode_step

    def short_last(tokens, lens, *tables):
        if lens[0] == p_len + m:
            lens = lens.copy()
            lens[0] -= 1
        return step(tokens, lens, *tables)

    model.decode_step = short_last
    try:
        return win._cached(model, ids, p_len, m)
    finally:
        model.decode_step = step


for j, ids in enumerate(seqs):
    say("program", j, *win._cached(model, ids, p_len, m), weights, ids,
        plains[j])
    if j < 2:
        say("fault_short", j, *one_short(ids), weights, ids)
print(json.dumps(dict(total_s=time.perf_counter() - t0)), flush=True)
