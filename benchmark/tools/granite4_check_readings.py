#!/usr/bin/env python3
"""The readings behind the limits in
`configs/granite-4.0-h-micro-serve.json` (`harness.limits`, which
`kinds/backlog_mapped_dense_ssd.py` holds its check to), on the chip: run
it again when the numerics change (another XLA, another kernel, another
precision of the file) and set the limits from what it prints. After
`nemotron3_check_readings.py`.

    python3 benchmark/tools/granite4_check_readings.py <weights seed> <n> [--program-only] [manifest [cell]]

On one set of weights and `n` seeded sequences, every line one check's
readings as the kind takes them (per position, root mean square) and
whether the configuration's limits pass it. (1) The served bundle: the
program itself, admitted as the kind admits it, and `fault_short` (the
slot's context one row short at the last step; not of the issue's list).
(2) On the SERVER's weights (the bundle's bfloat16 matrices: a float32
copy of the model does not fit beside them), the reference made wrong in
one part and taken for the program, against the highest-precision
reference on the same matrices: `bf16_throughout` (the residual stream,
the scan, the states and every intermediate in bfloat16: the precision
below the configuration's) and the faults of `FAULTS` below, the three
of the state among them (`fault_former_state`: the decode steps start
from the matrices the slot's former owner left; `fault_former_conv_rows`:
from its convolution rows; `fault_conv_rows_a_row_late`: from the
prompt's own rows a row early). Each has to fail at least one limit.
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
import reference_granite4 as ref  # noqa: E402
from kinds import _model_granite4 as mapping  # noqa: E402
from kinds import backlog_mapped as bm  # noqa: E402
from kinds import backlog_mapped_dense_ssd as kind  # noqa: E402
from kinds.backlog_mapped_state import former_ids  # noqa: E402
import paddle_tpu as pt  # noqa: E402
from paddle_tpu import io as pio  # noqa: E402
from paddle_tpu.core.compile_cache import enable_compile_cache  # noqa: E402
from paddle_tpu.serving.decode.engine import DecodeModel  # noqa: E402

enable_compile_cache()
seed, n_seq = int(sys.argv[1]), int(sys.argv[2])
# (1) alone, where only the program has changed since the last readings
program_only = "--program-only" in sys.argv
args = [a for a in sys.argv if a != "--program-only"]
manifest = args[3] if len(args) > 3 \
    else os.path.join(ROOT, "BENCHMARK.json")   # a rehearsal brings its own
cell = common.Cell(manifest, args[4] if len(args) > 4
                   else "granite4_h_micro_serve_rollout_reason_s48")
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
    fault_softmax_scale_rsqrt_d=dict(softmax="rsqrt"),
    fault_mixer_residual_unscaled=dict(residual="mixer_unscaled"),
    fault_ffn_residual_unscaled=dict(residual="ffn_unscaled"),
    fault_embedding_multiplier_dropped=dict(embedding="unscaled"),
    fault_gate_after_norm=dict(gate="after"),
    fault_norm_over_groups_of_512=dict(norm="groups_512"),
    fault_conv_bias_dropped=dict(conv="no_bias"),
    fault_dskip_dropped=dict(skip="dropped"),
    fault_dt_bias_after_softplus=dict(dt_bias="after"),
    fault_rotation_in_attention=dict(rotary="half"),
    fault_ffn_halves_swapped=dict(halves="swapped"))


def say(who, j, got, want):
    read = kind.readings(got, want)
    read["passes"] = kind.within(read, limits) \
        and bool(np.all(np.isfinite(got)))
    print(json.dumps(dict(weights_seed=seed, seq=j, who=who, **read)),
          flush=True)


t0 = time.perf_counter()
_, startup = mapping.build_params_only(pt, sz, seed)
scope = pt.Scope()
with pt.scope_guard(scope):
    pt.Executor().run(startup)
prints = bm._fingerprint(mapping.reference_weights(scope.find_var,
                                                   sz["n_layers"]))
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
    weight_dtype=model.weight_dtype, weight_bytes=model.weight_bytes,
    cache=model.cache, pages_per_block=model.paged_block_pages,
    state_bytes=model.state_bytes,
    setup_s=time.perf_counter() - t0)), flush=True)


def one_short(ids):
    """`kind._cached` with the slot's context one row short at the LAST
    step."""
    step = model.decode_step

    def wrong_last(tokens, lens, tables):
        if lens[slot] == p_len + m:
            lens = lens.copy()
            lens[slot] -= 1
        return step(tokens, lens, tables)

    model.decode_step = wrong_last
    try:
        return kind._cached(model, ids, p_len, m, slot, former_len)
    finally:
        model.decode_step = step


seqs = [np.random.RandomState((seed + 2000 + j) % (2 ** 32)).randint(
    0, sz["vocab"], p_len + m) for j in range(n_seq)]
right = []
for j, ids in enumerate(seqs):
    want = np.asarray(ref.logits(weights, ids, hp, rows=ROWS))
    right.append(want)
    for who, run in (("program", lambda i: kind._cached(
            model, i, p_len, m, slot, former_len)),
            ("fault_short", one_short)):
        if who != "program" and j >= 1:
            continue
        say(who, j, run(ids), want)
print(json.dumps(dict(step_aliased_bytes=model.step_aliased_bytes,
                      phase1_s=time.perf_counter() - t0)), flush=True)


def wrong_reference(who, j, ids, wrong, state=None):
    """The reference made wrong (`wrong`: fields of `Hyper`; `state`:
    another state than the prompt's own) and taken for the program."""
    got = ref.logits(weights, ids, hp._replace(**wrong), rows=ROWS,
                     state=state)
    say(who, j, np.asarray(got), right[j])


for j, ids in enumerate(seqs):
    if program_only or j >= 2:
        break
    wrong_reference("bf16_throughout", j, ids, dict(dtype="bfloat16"))
    if j >= 1:
        continue
    for who, wrong in FAULTS.items():
        wrong_reference(who, j, ids, wrong)
    own = ref.states(weights, ids[:p_len], hp)
    former = ref.states(weights, np.concatenate(
        [former_ids(ids, former_len), former_ids(ids, 1)]), hp)
    early = ref.states(weights, ids[:p_len - 1], hp)
    for who, state in (
            ("fault_former_state",
             [(f[0], o[1]) for f, o in zip(former, own)]),
            ("fault_former_conv_rows",
             [(o[0], f[1]) for f, o in zip(former, own)]),
            ("fault_conv_rows_a_row_late",
             [(o[0], e[1]) for e, o in zip(early, own)])):
        wrong_reference(who, j, ids, {}, state=(p_len, state))
print(json.dumps(dict(total_s=time.perf_counter() - t0)), flush=True)
