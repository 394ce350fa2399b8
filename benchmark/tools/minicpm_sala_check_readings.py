#!/usr/bin/env python3
"""The readings behind the limits in `configs/minicpm-sala-serve.json`
(`harness.limits`, which `kinds/backlog_mapped_blk.py` holds its check
to), on the chip: run it again when the numerics change (another XLA,
another kernel, another precision of the file) and set the limits from
what it prints. After `nemotron3_check_readings.py`.

    python3 benchmark/tools/minicpm_sala_check_readings.py <weights seed> <n> [--program-only]

On one set of weights and `n` seeded sequences, every line one check's
readings as the kind takes them (per position, root mean square, the
choices' shortfall) and whether the configuration's limits pass it. (1)
While the scope holds the weights, the reference made wrong in one part
and taken for the program (its logits WITH the blocks it reports, the
right reference forced onto them), against the highest-precision
reference: `bf16_throughout` (every weight cast where it is used, the
residual stream and every intermediate: the precision below the
configuration's) and the faults of `FAULTS` below. Each has to fail at
least one limit. (2) The served bundle: the program itself, admitted as
the kind admits it (into a used slot, at a length that is not its
bucket's end).
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
import reference_minicpm_sala as ref  # noqa: E402
from kinds import _model_minicpm_sala as mapping  # noqa: E402
from kinds import backlog_mapped as bm  # noqa: E402
from kinds import backlog_mapped_blk as blk  # noqa: E402
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
cell = common.Cell(manifest, "minicpm_sala_serve_rollout_32k")
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

FAULTS = dict(
    # the cut's layers are the published 0-3: the wrong index is the one
    # a cut of OTHER layers would have (its place in the cut plus one)
    fault_decay_of_the_next_layer=dict(
        layer_ids=tuple(i + 1 for i in hp.layer_ids)),
    fault_linear_gate_dropped=dict(linear_gate=False),
    fault_sparse_gate_dropped=dict(sparse_gate=False),
    fault_initial_block_dropped=dict(init=0),
    fault_window_one_block_short=dict(window=hp.window - hp.block),
    fault_group_sum_of_one_head=dict(group_sum="one"),
    fault_rotation_on_the_sparse_layer=dict(sparse_rotary=True),
    fault_scale_emb_dropped=dict(scale_emb=1.0),
    fault_scale_depth_dropped=dict(scale_depth=hp.depth ** 0.5),
    fault_logit_scale_dropped=dict(dim_model_base=hp.hidden))


def say(who, j, got, want, tie):
    read = blk.readings(got, want, tie, p_len)
    read["passes"] = blk.within(read, limits)
    print(json.dumps(dict(weights_seed=seed, seq=j, who=who, **read)),
          flush=True)


def wrong_reference(who, j, ids, weights, wrong):
    """The reference made wrong (`wrong`: fields of `Hyper`) and taken
    for the program: its logits and the blocks it chose, against the
    right reference on those blocks."""
    got, chosen = ref.logits_and_choices(
        weights, ids, hp._replace(**wrong), rows=ROWS, prompt_len=p_len)
    want, tie = ref.logits_on_choices(weights, ids, hp, np.asarray(chosen),
                                      rows=ROWS, prompt_len=p_len)
    say(who, j, np.asarray(got), np.asarray(want), np.asarray(tie))


t0 = time.perf_counter()
_, startup = mapping.build_params_only(pt, sz, seed)
scope = pt.Scope()
with pt.scope_guard(scope):
    pt.Executor().run(startup)
weights = mapping.reference_weights(scope.find_var, sz["n_layers"])
prints = bm._fingerprint(weights)
seqs = []
for j in range(n_seq):
    ids = np.random.RandomState((seed + 2000 + j) % (2 ** 32)).randint(
        0, sz["vocab"], p_len + m)
    seqs.append(ids)
    if j >= 2 or program_only:
        continue
    wrong_reference("bf16_throughout", j, ids, weights,
                    dict(dtype="bfloat16"))
    if j >= 1:
        continue
    for who, wrong in FAULTS.items():
        wrong_reference(who, j, ids, weights, wrong)
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
    cache=model.cache, block_sparse_kernel=model.block_sparse_kernel,
    state_bytes=model.state_bytes)), flush=True)

for j, ids in enumerate(seqs):
    got, chosen = blk._cached(model, ids, p_len, m, slot, former_len)
    want, tie = mapping.reference_on(ref, weights, cfg, ids, chosen, ROWS,
                                     p_len)
    say("program", j, got, np.asarray(want), np.asarray(tie))
print(json.dumps(dict(step_aliased_bytes=model.step_aliased_bytes,
                      total_s=time.perf_counter() - t0)), flush=True)
