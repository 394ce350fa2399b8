#!/usr/bin/env python3
"""The readings behind the limits in `configs/glm-5-serve.json`
(`harness.limits`, which `kinds/backlog_mapped_sel.py` holds its check
to), on the chip: run it again when the numerics change (another XLA,
another kernel, another precision of the file, other gains of the two
low-rank norms) and set the limits from what it prints. Made the way
`keye_check_readings.py` was.

    python3 benchmark/tools/glm5_check_readings.py <weights seed> <n> [--program-only]

On one set of weights and `n` seeded sequences, every line one check's
readings as the kind takes them (per position, root mean square, the
experts' and the selection's largest shortfall) and whether the
configuration's limits pass it. (1) While the scope holds the weights,
the reference made wrong in one part and taken for the program, its
logits with the routes and selections it reports, against the
highest-precision reference on those: `bf16_throughout` (every weight
cast where it is used, the residual stream and every intermediate: the
precision below the configuration's), `fault_all_rows` (attention over
all live rows, the selection ignored, its own selection reported),
`fault_newest` (the newest 2,048 rows in place of the top 2,048, and
reported), `fault_index_from_h` (qI projected from the normed stream in
place of the query's low-rank), `fault_index_turn_all` (the indexer
rotated over all 128), `fault_q_norm_dropped` (the query's low-rank norm
left out), `fault_scale_1` (gates not scaled by 2.5), `fault_first_off`
(the share's `first` off by one). Each of these has to fail at least one
limit. (2) The served bundle: the program itself, and `fault_short`, the
slot's context one row short at the last step (its newest latent and
index rows unread, RoPE one position early).
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
import reference_glm5 as ref  # noqa: E402
from kinds import _model_glm5 as mapping  # noqa: E402
from kinds import backlog_mapped as bm  # noqa: E402
from kinds import backlog_mapped_sel as sel  # noqa: E402
import paddle_tpu as pt  # noqa: E402
from paddle_tpu import io as pio  # noqa: E402
from paddle_tpu.serving.decode.engine import DecodeModel  # noqa: E402

seed, n_seq = int(sys.argv[1]), int(sys.argv[2])
# (2) alone, where only the program has changed since the last readings
program_only = "--program-only" in sys.argv
args = [a for a in sys.argv if a != "--program-only"]
manifest = args[3] if len(args) > 3 \
    else os.path.join(ROOT, "BENCHMARK.json")   # a rehearsal brings its own
cell = common.Cell(manifest, "glm5_serve_rollout_12k_lsel")
cfg, tr = cell.config, cell.traffic
sz, srv = mapping.sizes(cfg), cfg["serving"]
hp = ref.Hyper.of(cfg)
limits = {k: float(v) for k, v in cfg["harness"]["limits"].items()}
p_len, m = int(tr["check"]["prompt_len"]), int(tr["check"]["decode_steps"])
ROWS = list(range(p_len - 1, p_len + m))
bucket = min(b for b in tr["prefill_buckets"] if b >= p_len)


def say(who, j, got, routes, masks, weights, ids, plain=None):
    want, tie, sel_tie = ref.logits_on(weights, ids, hp, routes, masks,
                                       rows=ROWS)
    read = sel.readings(got, want, tie, sel_tie, p_len)
    read["passes"] = sel.within(read, limits)
    if plain is not None:    # what the routes and selections explain
        d = np.abs(np.asarray(got, np.float32) - plain) / np.std(plain)
        read["max_by_position_plain"] = [round(float(v), 4)
                                         for v in d.max(-1)]
    print(json.dumps(dict(weights_seed=seed, seq=j, who=who, **read)),
          flush=True)


def as_program(weights, ids, wrong, report=None):
    """The reference under `wrong` taken for the program: its logits,
    and the routes and selections it would report (those of `report`)."""
    report = report or wrong
    routes, masks = ref.choices(weights, ids, report)
    return (np.asarray(ref.logits(weights, ids, wrong, rows=ROWS)),
            np.asarray(routes), np.asarray(masks))


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
    low = hp._replace(dtype="bfloat16")
    say("bf16_throughout", j, *as_program(weights, ids, low), weights, ids,
        plains[-1])
    if j >= 1:
        continue
    say("fault_all_rows", j,
        *as_program(weights, ids, hp._replace(select="all"), hp),
        weights, ids)
    for who, wrong in (
            ("fault_newest", hp._replace(select="newest")),
            ("fault_index_from_h", hp._replace(index_from="h")),
            ("fault_index_turn_all", hp._replace(index_turn=hp.index_dim)),
            ("fault_q_norm_dropped", hp._replace(q_norm=False)),
            ("fault_scale_1", hp._replace(routed_scale=1.0)),
            ("fault_first_off", hp._replace(first=hp.first + 1))):
        say(who, j, *as_program(weights, ids, wrong), weights, ids)
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
    cache=model.cache, index_pages_per_block=model.paged_block_pages)),
    flush=True)


def one_short(ids):
    """`sel._cached` with the slot's context one row short at the LAST
    step."""
    step = model.decode_step

    def short_last(tokens, lens, tables):
        if lens[0] == p_len + m:
            lens = lens.copy()
            lens[0] -= 1
        return step(tokens, lens, tables)

    model.decode_step = short_last
    try:
        return sel._cached(model, ids, p_len, m)
    finally:
        model.decode_step = step


for j, ids in enumerate(seqs):
    say("program", j, *sel._cached(model, ids, p_len, m), weights, ids,
        plains[j])
    if j < 4:
        say("fault_short", j, *one_short(ids), weights, ids)
print(json.dumps(dict(total_s=time.perf_counter() - t0)), flush=True)
