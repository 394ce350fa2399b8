#!/usr/bin/env python3
"""The readings behind the limits in `configs/lfm2-24b-a2b-serve.json`
(`harness.limits`, which `kinds/backlog_mapped_state.py` holds its check
to), on the chip: run it again when the numerics change (another XLA,
another kernel, another precision of the file) and set the limits from
what it prints. After `cmda_check_readings.py`.

    python3 benchmark/tools/lfm2_check_readings.py <weights seed> <n> [--program-only]

On one set of weights and `n` seeded sequences, every line one check's
readings as the kind takes them (per position, root mean square, the
experts' largest shortfall) and whether the configuration's limits pass
it. (1) While the scope holds the weights, the reference made wrong in
one part and taken for the program, its logits with the routes it
reports, against the highest-precision reference on those:
`bf16_throughout` (every weight cast where it is used, the residual
stream and every intermediate: the precision below the configuration's),
`fault_state_at_bucket_end` (the decode steps start from the state the
padded bucket's last rows leave, not row n - 1's), `fault_former_state`
(from the state the slot's former owner left), `fault_taps_reversed`,
`fault_tap_dropped`, `fault_gates_swapped` (B and C exchanged),
`fault_gate_left_out`, `fault_select_unbiased` (the experts chosen by
the unbiased score), `fault_weigh_biased` (weighted by the biased one),
`fault_norm_after_rotation` (the q/k-norm after the rotation) and
`fault_wrong_group` (query head j reading K/V head j % 8). Each of these
has to fail at least one limit. Also `bias_moved_share`: the share of
(token, choice) pairs the selection bias moves. (2) The served bundle:
the program itself, admitted as the kind admits it, and `fault_short`,
the slot's context one row short at the last step (its newest K and V
rows unread, one position early).
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
import reference_lfm2 as ref  # noqa: E402
from kinds import _model_lfm2 as mapping  # noqa: E402
from kinds import backlog_mapped as bm  # noqa: E402
from kinds import backlog_mapped_state as st  # noqa: E402
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
cell = common.Cell(manifest, "lfm2_24b_serve_rollout_6k_s64")
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
    fault_taps_reversed=dict(taps="reversed"),
    fault_tap_dropped=dict(taps="dropped"),
    fault_gates_swapped=dict(gates="swapped"),
    fault_gate_left_out=dict(gates="no_c"),
    fault_select_unbiased=dict(select="unbiased"),
    fault_weigh_biased=dict(weigh="biased"),
    fault_norm_after_rotation=dict(qk_norm="after"),
    fault_wrong_group=dict(pairing="strided"))


def say(who, j, got, routes, weights, ids, plain=None):
    want, tie = ref.logits_on_routes(weights, ids, hp, routes, rows=ROWS)
    read = st.readings(got, want, tie, p_len)
    read["passes"] = st.within(read, limits)
    if plain is not None:    # what the routes explain
        d = np.abs(np.asarray(got, np.float32) - plain) / np.std(plain)
        read["max_by_position_plain"] = [round(float(v), 4)
                                         for v in d.max(-1)]
    print(json.dumps(dict(weights_seed=seed, seq=j, who=who, **read)),
          flush=True)


def as_program(weights, ids, wrong, state=None):
    """The reference under `wrong` (or started from `state`) taken for
    the program: its logits, and the routes it would report."""
    got, routes = ref.logits_and_choices(weights, ids, wrong, rows=ROWS,
                                         state=state)
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
    own = np.asarray(ref.chosen_experts(weights, ids, hp))
    plain = np.asarray(ref.chosen_experts(
        weights, ids, hp._replace(select="unbiased")))
    moved = np.mean([[len(set(a) - set(b)) / len(a)
                      for a, b in zip(la, lb)]
                     for la, lb in zip(own, plain)])
    print(json.dumps(dict(weights_seed=seed, bias_moved_share=float(moved),
                          bias_scale=mapping.ROUTER_BIAS_SCALE)), flush=True)
    for who, wrong in FAULTS.items():
        say(who, j, *as_program(weights, ids, hp._replace(**wrong)),
            weights, ids)
    padded = np.concatenate([ids[:p_len],
                             np.zeros(bucket - p_len, ids.dtype)])
    former = np.concatenate([st.former_ids(ids, former_len),
                             st.former_ids(ids, 1)])
    for who, other in (
            ("fault_state_at_bucket_end",
             ref.conv_state(weights, padded, hp)),
            ("fault_former_state", ref.conv_state(weights, former, hp))):
        say(who, j, *as_program(weights, ids, hp, (p_len, other)), weights,
            ids)
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


def one_short(ids):
    """`st._cached` with the slot's context one row short at the LAST
    step."""
    step = model.decode_step

    def short_last(tokens, lens, *tables):
        if lens[slot] == p_len + m:
            lens = lens.copy()
            lens[slot] -= 1
        return step(tokens, lens, *tables)

    model.decode_step = short_last
    try:
        return st._cached(model, ids, p_len, m, slot, former_len)
    finally:
        model.decode_step = step


for j, ids in enumerate(seqs):
    say("program", j, *st._cached(model, ids, p_len, m, slot, former_len),
        weights, ids, plains[j])
    if j < 2:
        say("fault_short", j, *one_short(ids), weights, ids)
print(json.dumps(dict(step_aliased_bytes=model.step_aliased_bytes,
                      total_s=time.perf_counter() - t0)), flush=True)
