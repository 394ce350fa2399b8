#!/usr/bin/env python3
"""The readings behind the limits in `configs/kanana-2-30b-a3b-serve.json`
(`harness.limits`, which `kinds/backlog_mapped_limits.py` hands to
`backlog_mapped`'s check), on the chip: run it again when the numerics
change (another XLA, another kernel, another precision of the file) and
set the limits from what it prints. After `olmoe_check_readings.py`.

    python3 benchmark/tools/kanana_check_readings.py <weights seed> <n>

On one set of weights and `n` seeded sequences, every line one check's
readings as the kind takes them (per position, root mean square, the
largest shortfall): (1) while the scope holds the weights, the reference
computed in bfloat16 THROUGHOUT (`Hyper.dtype`: every weight cast where
it is used, the residual stream and every intermediate) taken for the
program, its logits and ITS routes against the highest-precision
reference on those routes: the precision below the configuration's,
which the limits have to fail; (2) the served bundle: the program
itself, the plain difference beside it (what the routes explain), and
two faults for scale: the slot's context one token short at the last
step (its newest latent row unread, RoPE one position early), and the
reference told not to scale the renormalised gates.
"""
import gc
import json
import math
import os
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import common  # noqa: E402
import reference_kanana as ref  # noqa: E402
from kinds import _model_kanana as mapping  # noqa: E402
from kinds import backlog_mapped as bm  # noqa: E402
import paddle_tpu as pt  # noqa: E402
from paddle_tpu import io as pio  # noqa: E402
from paddle_tpu.serving.decode.engine import DecodeModel  # noqa: E402

seed, n_seq = int(sys.argv[1]), int(sys.argv[2])
manifest = sys.argv[3] if len(sys.argv) > 3 \
    else os.path.join(ROOT, "BENCHMARK.json")   # a rehearsal brings its own
cell = common.Cell(manifest, "kanana2_30b_serve_rollout_6k")
cfg, tr = cell.config, cell.traffic
sz, srv = mapping.sizes(cfg), cfg["serving"]
hp = ref.Hyper.of(cfg)
p_len, m = int(tr["check"]["prompt_len"]), int(tr["check"]["decode_steps"])
ROWS = slice(p_len - 1, p_len + m)
bucket = min(b for b in tr["prefill_buckets"] if b >= p_len)


def reading(got, routes, weights, ids, plain=None):
    want, shortfall = ref.logits_on_routes(weights, ids, hp, routes)
    want, shortfall = np.asarray(want)[ROWS], np.asarray(shortfall)
    got = np.asarray(got, np.float32)
    d = np.abs(got - want) / np.std(want)
    off = shortfall > 0
    out = dict(by_row=[round(float(v), 4) for v in d.max(-1)],
               rms=round(float(np.sqrt(np.mean(d ** 2))), 5),
               max_shortfall=round(float(shortfall.max()), 4),
               tokens_flipped=int(np.sum(np.any(off, axis=0))),
               compared_flipped=int(np.sum(np.any(off[:, ROWS], axis=0))))
    if plain is not None:
        d = np.abs(got - plain) / np.std(plain)
        out["by_row_plain"] = [round(float(v), 4) for v in d.max(-1)]
    return out


def say(who, j, **fields):
    print(json.dumps(dict(weights_seed=seed, seq=j, who=who, **fields)),
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
    plains.append(np.asarray(ref.logits(weights, ids, hp))[ROWS])
    low = hp._replace(dtype="bfloat16")
    got = np.asarray(ref.logits(weights, ids, low))[ROWS]
    routes = np.asarray(ref.chosen_experts(weights, ids, low))
    say("bf16_throughout", j, **reading(got, routes, weights, ids,
                                        plains[-1]))
# how often the selection bias changes a choice: the reference's own
# routes against those of a zero bias, over the first sequence's tokens
own = np.asarray(ref.chosen_experts(weights, seqs[0], hp))
unbiased = dict(weights, layers=[
    dict(lay, router_bias=jnp.zeros_like(lay["router_bias"]))
    if "router_bias" in lay else lay for lay in weights["layers"]])
plain_choice = np.asarray(ref.chosen_experts(unbiased, seqs[0], hp))
moved = np.sort(own, -1) != np.sort(plain_choice, -1)
print(json.dumps(dict(
    bias_changes_a_choice_share=float(np.mean(np.any(moved, axis=-1))),
    bias_changed_pairs_share=float(np.mean(moved)),
    phase1_s=time.perf_counter() - t0)), flush=True)
del weights, unbiased
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
    pages_per_block=model.paged_block_pages,
    blocks_walked_at_the_last_step=math.ceil(
        math.ceil((p_len + m) / model.block_size)
        / model.paged_block_pages))), flush=True)


def one_short(ids):
    """bm._cached with the slot's context one token short at the LAST
    step: its newest latent row unread, RoPE one position early."""
    bs = model.block_size
    blocks = list(range(1, 1 + math.ceil((p_len + m) / bs)))
    last, kv = model.prefill([int(t) for t in ids[:p_len]])
    routes = [np.asarray(model.last_routes)[:, :p_len]]
    model.seed_sequence(blocks[:math.ceil(p_len / bs)], kv)
    rows = [np.asarray(last)]
    tokens = np.zeros(model.slots, np.int64)
    lens = np.zeros(model.slots, np.int32)
    tables = np.zeros((model.slots, model.max_blocks_per_seq), np.int32)
    tables[0, :len(blocks)] = blocks
    for j in range(m):
        tokens[0], lens[0] = ids[p_len + j], p_len + j + 1
        if j == m - 1:
            lens[0] -= 1
        rows.append(np.asarray(model.decode_step(tokens, lens, tables))[0])
        routes.append(np.asarray(model.last_routes)[:, :1])
    model.reset_pools()
    return np.stack(rows), np.concatenate(routes, 1)


for j, ids in enumerate(seqs):
    got, routes = bm._cached(model, ids, p_len, m)
    say("program", j, **reading(got, routes, weights, ids, plains[j]))
    if j < 4:
        got, routes = one_short(ids)
        say("fault_short", j, **reading(got, routes, weights, ids))
# gates renormalised and NOT scaled: the reference told so, taken for
# the program, on the reference's own routes
for j, ids in enumerate(seqs[:2]):
    own = np.asarray(ref.chosen_experts(weights, ids, hp))
    got = np.asarray(ref.logits(
        weights, ids, hp._replace(routed_scale=1.0)))[ROWS]
    say("fault_unscaled", j, **reading(got, own, weights, ids))
print(json.dumps(dict(total_s=time.perf_counter() - t0)), flush=True)
