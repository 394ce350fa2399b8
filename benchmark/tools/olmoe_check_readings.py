#!/usr/bin/env python3
"""The readings behind the limits of `kinds/backlog_mapped.py` for the
OLMoE cell, on the chip: run it again when the numerics change (another
XLA, another kernel, another precision of the file) and set the limits
from what it prints.

    python3 benchmark/tools/olmoe_check_readings.py <weights seed> <n>

On one set of weights and `n` seeded sequences, every line one check's
readings as the kind takes them (per position, root mean square, the
largest shortfall): (1) while the scope holds the weights, the reference
computed in bfloat16 THROUGHOUT (weights and every intermediate) taken
for the program, its logits and ITS routes against the highest-precision
reference on those routes, once with a bfloat16 router and once with
the op's float32 one: the precision below the configuration's, which
the limits have to fail; (2) the served bundle: the program itself, the
plain difference beside it (what the routes explain), and two faults
for scale: the slot's context one token short at the last step, and the
reference told to renormalise the chosen gates.
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
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import common  # noqa: E402
import reference_olmoe as ref  # noqa: E402
from kinds import _model_olmoe as mapping  # noqa: E402
from kinds import backlog_mapped as bm  # noqa: E402
import paddle_tpu as pt  # noqa: E402
from paddle_tpu import io as pio  # noqa: E402
from paddle_tpu.serving.decode.engine import DecodeModel  # noqa: E402

seed, n_seq = int(sys.argv[1]), int(sys.argv[2])
manifest = sys.argv[3] if len(sys.argv) > 3 \
    else os.path.join(ROOT, "BENCHMARK.json")   # a rehearsal brings its own
cell = common.Cell(manifest, "olmoe1b7b_serve_rollout")
cfg, tr = cell.config, cell.traffic
sz, srv = mapping.sizes(cfg), cfg["serving"]
hp = ref.Hyper.of(cfg)
p_len, m = int(tr["check"]["prompt_len"]), int(tr["check"]["decode_steps"])
ROWS = slice(p_len - 1, p_len + m)
BF = jnp.bfloat16


def bf16_forward(weights, ids, f32_router):
    """Every weight and every intermediate in bfloat16."""
    def rms(x, g):
        return ref._rms(x, g, hp.eps).astype(BF)

    def rope(t):
        return ref._rope(t.astype(jnp.float32), hp.theta).astype(BF)

    def attention(x, lw):
        seq, d = x.shape
        dh = d // hp.n_head

        def heads(t):
            return t.reshape(seq, hp.n_head, dh)

        q = rope(heads(rms(x @ lw["q"], lw["q_norm"])))
        k = rope(heads(rms(x @ lw["k"], lw["k_norm"])))
        v = heads(x @ lw["v"])
        s = (jnp.einsum("qhd,khd->hqk", q, k)
             / jnp.sqrt(jnp.float32(dh)).astype(BF)).astype(BF)
        s = jnp.where(jnp.tril(jnp.ones((seq, seq), bool))[None], s,
                      -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(BF)
        ctx = jnp.einsum("hqk,khd->qhd", p, v).astype(BF)
        return (ctx.reshape(seq, d) @ lw["out"]).astype(BF)

    x = weights["tok_emb"][ids].astype(BF)
    routes = []
    for layer in weights["layers"]:
        lw = {k: v.astype(BF) for k, v in layer.items()}
        x = (x + attention(rms(x, lw["ln1"]), lw)).astype(BF)
        n2 = rms(x, lw["ln2"])
        if f32_router:
            with jax.default_matmul_precision("highest"):
                chosen, w, _ = ref._route(n2.astype(jnp.float32), layer,
                                          hp)
        else:
            chosen, w, _ = ref._route(n2, lw, hp)
        routes.append(chosen)
        x = (x + ref._experts(n2, lw, w.astype(BF)).astype(BF)).astype(BF)
    logits = rms(x, weights["ln_f"].astype(BF)) @ weights["head"].astype(BF)
    return logits[ROWS].astype(jnp.float32), jnp.stack(routes)


bf16_jit = jax.jit(bf16_forward, static_argnames=("f32_router",))


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
    jids = jnp.asarray(ids, jnp.int32)
    for f32_router in (False, True):
        got, routes = bf16_jit(weights, jids, f32_router=f32_router)
        say("bf16_throughout", j, f32_router=f32_router,
            **reading(got, np.asarray(routes), weights, ids, plains[-1]))
print(json.dumps(dict(phase1_s=time.perf_counter() - t0)), flush=True)
del weights
bundle = common.fresh_work_dir("bundle_check_readings")
pio.export_decode_model(
    bundle, mapping.export_cfg(sz), scope=scope, length_buckets=(p_len,),
    slots=int(srv["slots"]), block_size=int(srv["block_size"]),
    pool_blocks=int(srv["pool_blocks"]))
for name in list(scope.local_var_names()):
    scope.erase(name)
del scope
gc.collect()
model = DecodeModel(bundle, warmup=True)
shutil.rmtree(bundle, ignore_errors=True)
weights = mapping.reference_weights(model.weights.__getitem__, sz["n_layers"])
print(json.dumps(dict(weights_came_back_bit_for_bit=bool(
    np.array_equal(bm._fingerprint(weights), prints)))), flush=True)


def one_short(ids):
    """bm._cached with the slot's context one token short at the LAST
    step: its newest K/V row unread, RoPE one position early."""
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
# renormalised gates: the reference told so, taken for the program, on
# the reference's own routes
for j, ids in enumerate(seqs[:2]):
    own = np.asarray(ref.chosen_experts(weights, ids, hp))
    got = np.asarray(ref.logits(
        weights, ids, hp._replace(norm_topk_prob=True)))[ROWS]
    say("fault_renormalised", j, **reading(got, own, weights, ids))
print(json.dumps(dict(total_s=time.perf_counter() - t0)), flush=True)
