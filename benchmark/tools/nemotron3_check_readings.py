#!/usr/bin/env python3
"""The readings behind the limits in
`configs/nemotron-3-nano-30b-a3b-serve.json` (`harness.limits`, which
`kinds/backlog_mapped_ssd.py` holds its check to through
`backlog_mapped_state`), on the chip: run it again when the numerics
change (another XLA, another kernel, another precision of the file) and
set the limits from what it prints. After `phi4flash_check_readings.py`.

    python3 benchmark/tools/nemotron3_check_readings.py <weights seed> <n> [--program-only]

On one set of weights and `n` seeded sequences, every line one check's
readings as the kind takes them (per position, root mean square, the
experts' shortfall) and whether the configuration's limits pass it. (1)
While the scope holds the weights, the reference made wrong in one part
and taken for the program (its logits WITH the routes it reports, the
right reference forced onto them), against the highest-precision
reference: `bf16_throughout` (every weight cast where it is used, the
residual stream and every intermediate: the precision below the
configuration's) and the faults of `FAULTS` below, the three of the
state among them (`fault_former_state`: the decode steps start from the
state the slot's former owner left; `fault_state_at_bucket_end`: from
the state the padded bucket's last rows leave; `fault_state_a_row_behind`:
from the prompt's own state a row early). Each has to fail at least one
limit. (2) The served bundle: the program itself, admitted as the kind
admits it, and `fault_short` (the slot's context one row short at the
last step; not of the issue's list).
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
import reference_nemotron3 as ref  # noqa: E402
from kinds import _model_nemotron3 as mapping  # noqa: E402
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
cell = common.Cell(manifest, "nemotron3_nano_serve_rollout_reason_s128")
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
    fault_gate_after_norm=dict(gate="after"),
    fault_norm_over_all_channels=dict(norm="whole"),
    fault_head_reads_group_h_mod_8=dict(pairing="strided"),
    fault_dt_bias_after_softplus=dict(dt_bias="after"),
    fault_dskip_dropped=dict(skip="dropped"),
    fault_conv_bias_dropped=dict(conv="no_bias"),
    fault_conv_silu_dropped=dict(conv="no_silu"),
    fault_relu_for_relu2=dict(act="relu"),
    fault_gated_silu_expert=dict(act="gated_silu"),
    fault_gates_from_biased_scores=dict(weigh="biased"),
    fault_scale_left_out=dict(routed_scale=1.0),
    fault_norm_topk_left_out=dict(norm_topk=False),
    fault_shared_expert_left_out=dict(shared="dropped"),
    fault_experts_1_32_for_0_31=dict(experts_first=hp.experts_first + 1),
    fault_rotation_in_attention=dict(rotary="half"))


def say(who, j, got, want, tie):
    read = st.readings(got, want, tie, p_len)
    read["passes"] = st.within(read, limits)
    print(json.dumps(dict(weights_seed=seed, seq=j, who=who, **read)),
          flush=True)


def wrong_reference(who, j, ids, weights, wrong, state=None):
    """The reference made wrong (`wrong`: fields of `Hyper`; `state`:
    another state than the prompt's own) and taken for the program."""
    got, routes = ref.logits_and_choices(
        weights, ids, hp._replace(**wrong), rows=ROWS, state=state)
    want, tie = ref.logits_on_routes(weights, ids, hp, routes, rows=ROWS)
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
    padded = np.concatenate([ids[:p_len],
                             np.zeros(bucket - p_len, ids.dtype)])
    former = np.concatenate([st.former_ids(ids, former_len),
                             st.former_ids(ids, 1)])
    for who, other in (("fault_state_at_bucket_end", padded),
                       ("fault_former_state", former),
                       ("fault_state_a_row_behind", ids[:p_len - 1])):
        wrong_reference(who, j, ids, weights, {}, state=(
            p_len, ref.states(weights, other, hp)))
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

    def wrong_last(tokens, lens, tables):
        if lens[slot] == p_len + m:
            lens = lens.copy()
            lens[slot] -= 1
        return step(tokens, lens, tables)

    model.decode_step = wrong_last
    try:
        return st._cached(model, ids, p_len, m, slot, former_len)
    finally:
        model.decode_step = step


for j, ids in enumerate(seqs):
    for who, run in (("program", lambda i: st._cached(
            model, i, p_len, m, slot, former_len)),
            ("fault_short", one_short)):
        if who != "program" and j >= 2:
            continue
        got, routes = run(ids)
        want, tie = mapping.reference_on(ref, weights, cfg, ids, routes,
                                         ROWS)
        say(who, j, got, np.asarray(want), np.asarray(tie))
print(json.dumps(dict(step_aliased_bytes=model.step_aliased_bytes,
                      total_s=time.perf_counter() - t0)), flush=True)
