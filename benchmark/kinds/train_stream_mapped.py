"""Traffic kind `train_stream_mapped`: what `train_stream` does (a token
stream from the seed through the trainer's `run_loop` windows on one chip,
the same window and the same observations), for a configuration of any
architecture. The configuration names, under `harness`, the module beside
`_model.py` that maps its published keys onto the program (`mapping`;
`_model_mellum2.py` lists what such a module gives) and the plain
reference beside `reference.py` (`reference`), as `backlog_mapped` does
for serving. A new trained family adds those two files, its operations
(`flops_<family>.py`) and no kind. `train_stream` and the Cerebras cells
keep `_model.py`, which this file does not touch; a `mesh` is theirs.

Observations: those of `train_stream` (window_s, window_tokens,
window_steps, host_s, compiles_in_window, flops_per_token, chips), what
the mapping's `kernel_shapes` gives the kernels' readers of a traced run
(the traced `run_loop` call's calls and counts), and where the model has experts the
window's `pt_train_moe_*` counts (`moe_assignments`, `moe_held_pairs`,
`moe_held_touched`, `moe_largest_rows_x_held`, `moe_layer_steps`).

`correct` holds the first call of the TIMED object (the same program,
executor and state the window then times): the first step's loss and
expert counts, and what the call's backward and Adam did to the state
(`hold_update`).
"""

from __future__ import annotations

import gc
import importlib
import time

import numpy as np

import common
import workload
from kinds.train_stream import LOSS_RTOL

# What decides `correct`, on the chip at the timed sizes:
#   the first step's loss against the reference's forward over every row
# of the step (float32, highest precision, attention in blocks of query
# rows): `train_stream`'s limit and its reasons (`LOSS_RTOL` 2e-4: the
# trainer computes in bf16 over f32 masters; PERF.md section 6, PR 62 has
# this cell's readings). What it catches: a dropped layer, a shifted
# target, a wrong mask over MOST of a row's keys, a share that computes
# other experts (each over 1e-2). What it is BLIND to at random initial
# weights, where the loss sits near ln(vocab) whatever attention reads: a
# band one row off, the plain table on a full layer, a pair dropped in
# the backward. The update below holds the last two; all three are held
# one gradient at a time by tier-1 at tiny sizes (tests/test_mellum2.py)
# and by `tools/mellum2_check_readings.py` at the published widths on
# the chip;
#   every loss of every call finite;
#   the first step's expert counts (`pt_train_moe_*`, fetched with the
# losses) against the reference's own count of ITS routes. Routed pairs:
# exactly tokens x k x layers, a count of the router's shape. Pairs on
# held experts: within HELD_RTOL. The program's router reads bf16
# activations, the reference's float32 ones: where a row's k-th and
# (k+1)-th gates lie closer than that rounding the program takes the
# other expert, and one time in three that moves a pair on or off the
# held quarter. The reference counts such rows (`near_tie_row_share`:
# gates within 2^-7 relative, about 7% of rows a layer). Read on the chip
# (PERF.md section 6, PR 62): 0.04%, 0.09%, 0.16% and 0.25% of 59.8-66.4 k
# held pairs over four seeds under the builder's own draw of the
# embedding, 0.002-0.06% over six under the configuration's. The
# program's count is summed INSIDE the share's walk (`ops/moe_ops.py
# _held_sum`: the rows each held expert's products took in the waves that
# ran), so a wave of the forward that does not run shows in it: at the
# cell's even routing the one wave is every pair (100%), and one expert
# of the sixteen too few or too many is 6%. HELD_RTOL 1% is four times
# the largest sound reading and a sixth of that. What the count cannot
# see: under a routing of several waves, a last wave that held under 1%
# of the pairs, and any wave of the BACKWARD (the update below holds
# both: the experts of a lost wave have a first moment that is not the
# reference's).
HELD_RTOL = 0.01

#   the first call's UPDATE, read off the timed object's own state: the
# leaves the mapping names (`update_leaves`: a window layer's and the full
# layer's q, k, v, a router, the held experts' three matrices, an expert
# at a time) are fetched before and after the call, and Adam's two
# moments of each after it. The moments start at 0, so after the call's n
# steps they are (1 - beta) sum_i beta^(n - i) of the steps' gradients
# (of their squares): what the backward computed in every step, kept by
# the optimizer in float32. The plain reference's gradients of the SAME n
# batches at the call's first weights (`reference.gradients`, float32 at
# the highest precision over all the step's rows, on its own routes; the
# rate moves no weight by 2e-4 of itself in a call) are put through the
# same two sums. Three readings a leaf (an expert):
#   `moved_share`, the share of its entries the call changed. Adam moves
# an entry by about the rate whatever its gradient's size, so a leaf
# whose gradient came reads near 1, and a state left unchanged 0.
# MOVED_MIN stands between them;
#   `moment_distance`, |m - m_ref| / |m_ref| of the first moments: the
# instructions' norm of the change of the state against the reference's,
# where a state left unchanged, a leaf the backward never reached or an
# expert whose wave of the backward did not run reads 1. It holds the
# gradients' DIRECTION: the windowed dq, dk, dv, the two rotary tables'
# transposes, the held share's backward. Read on the chip (PERF.md
# section 6, PR 62): 0.018-0.033 on q, k and v, 0.042-0.046 on the
# experts' matrices, 0.047-0.054 on the router (the rows whose routes
# differ at a near tie are its own) over nine seeds. The configuration's
# precision hardly moves it (the reference in bfloat16 throughout reads
# what the AMP program reads, `tools/mellum2_check_readings.py
# --qk-gain 1`: 0.009-0.018 for 0.008-0.016 at 2,048 rows), so
# MOMENT_RTOL 0.15 stands between the first readings and 1, nearer the
# readings: three times the largest. What it separates at the cell's own
# draw (the same tool, seed 13): the plain table on the full layer reads
# 0.21-0.79, a token's weakest held pair dropped 0.15-0.57 (0.54 on the
# experts). What it CANNOT: the window one row long or short reads
# 0.002-0.013 there, under the AMP program's own distance (at Xavier's q
# and k a head's softmax over a thousand rows is nearly flat and one more
# row moves nothing); that is held in float32 (the tool's second pass:
# 0.0001-0.0003 for the program; at `QK_GAIN` 3, 0.012-0.034 for the
# program and 0.34-0.69 for the fault) and by tier-1 at tiny sizes;
#   `grad_norm_ratio`, sqrt(sum(v) / sum(v_ref)) of the second moments:
# a sound call reads 0.9997-1.0005 on every leaf and expert; the first
# chip run of PR 62, in which rows of no group reached the transposes,
# read 18,000-99,000 on every leaf with its loss right; a wave of the
# backward that did not run reads 0 on the experts it held.
# GRAD_NORM_BAND (0.9, 1.1) is two hundred times the sound readings' room
# and what a tenth of a gradient's size lost or gained gives.
MOVED_MIN = 0.5
GRAD_NORM_BAND = (0.9, 1.1)
MOMENT_RTOL = 0.15


def _harness(cell):
    names = cell.config["harness"]
    return (importlib.import_module("kinds." + names["mapping"]),
            importlib.import_module(names["reference"]))


def _moe_counts(load, held):
    """The `pt_train_moe_*` counts of fetched Load rows [steps, 4]."""
    from paddle_tpu.obs.metrics import TrainMetrics
    metrics = TrainMetrics()
    metrics.observe_moe(load)
    snap = metrics.snapshot()
    return dict(moe_assignments=snap["moe_routed_pairs"],
                moe_held_pairs=snap["moe_held_pairs"],
                moe_held_touched=snap["moe_held_touched"],
                moe_largest_rows_x_held=snap["moe_largest_rows"] * held)


def _adam_of(main, name):
    """(the first and the second moment's names, beta1, beta2) of the
    `adam` op that updates the parameter `name` in `main`."""
    for op in main.global_block.ops:
        if op.type == "adam" and op.input("Param")[0] == name:
            return (op.input("Moment1")[0], op.input("Moment2")[0],
                    float(op.attrs["beta1"]), float(op.attrs["beta2"]))
    raise KeyError(f"no adam op updates {name!r}")


def _per_expert(values, reduce):
    """`reduce` over a leaf, or over each expert of a stacked one."""
    values = np.asarray(values)
    return reduce(values.reshape(values.shape[0], -1), axis=1) \
        if values.ndim == 3 else np.asarray([reduce(values)])


def reference_moments(gradients_of, betas, n_steps):
    """Adam's two moments after `n_steps` steps from 0, of the gradients
    `gradients_of(step)` gives (a list of device arrays, a leaf each;
    `betas` [(beta1, beta2)] likewise): (the first moments, on the host;
    the second moments' sums, one an expert of a stacked leaf)."""
    import jax.numpy as jnp
    first = second = None
    for step in range(n_steps):
        grads = gradients_of(step)
        first = [(1.0 - b1) * g if first is None
                 else b1 * first[i] + (1.0 - b1) * g
                 for i, (g, (b1, _)) in enumerate(zip(grads, betas))]
        sums = [jnp.sum(jnp.square(g), axis=(
            tuple(range(1, g.ndim)) if g.ndim == 3 else None))
            for g in grads]
        second = [(1.0 - b2) * q if second is None
                  else b2 * second[i] + (1.0 - b2) * q
                  for i, (q, (_, b2)) in enumerate(zip(sums, betas))]
    return ([np.asarray(m) for m in first],
            [np.asarray(v, np.float64).reshape(-1) for v in second])


def update_readings(before, after, moment1, moment2, want1, want2):
    """What one leaf's first call reads ([C] for C stacked experts, else
    [1]): `moved_share`, `change_rms` (of the parameters), and against
    the reference's moments `want1` (an array like the leaf) and `want2`
    (the second moment's sums [C]): `moment_distance`, `grad_norm_ratio`
    (nan where the reference's gradient is 0: it leaves that expert where
    it is, and nothing is asked of it)."""
    delta = np.asarray(after, np.float64) - np.asarray(before, np.float64)
    apart = np.asarray(moment1, np.float64) - np.asarray(want1, np.float64)
    size = _per_expert(np.square(np.asarray(want1, np.float64)), np.sum)
    with np.errstate(divide="ignore", invalid="ignore"):
        return dict(
            moved_share=_per_expert(delta != 0, np.mean),
            change_rms=np.sqrt(_per_expert(np.square(delta), np.mean)),
            moment_distance=np.sqrt(
                _per_expert(np.square(apart), np.sum) / size),
            grad_norm_ratio=np.sqrt(_per_expert(
                np.asarray(moment2, np.float64), np.sum)
                / np.asarray(want2, np.float64)))


def hold_update(leaves):
    """(correct, what was read) of the first call's update: `leaves`
    {label: `update_readings`}. A leaf (an expert) the reference moves
    has to have moved, by gradients whose first moment is the reference's
    within MOMENT_RTOL and whose size is its within GRAD_NORM_BAND."""
    read, correct = {}, True
    for label, got in leaves.items():
        live = np.isfinite(got["grad_norm_ratio"])
        if not live.any():
            continue
        moved, ratio, apart = (got[key][live] for key in (
            "moved_share", "grad_norm_ratio", "moment_distance"))
        ok = bool(np.all(moved >= MOVED_MIN)
                  and np.all(apart <= MOMENT_RTOL)
                  and np.all(ratio >= GRAD_NORM_BAND[0])
                  and np.all(ratio <= GRAD_NORM_BAND[1]))
        correct = correct and ok
        read[label] = dict(
            moved_share=float(moved.min()),
            change_rms=[float(got["change_rms"][live].min()),
                        float(got["change_rms"][live].max())],
            moment_distance=float(apart.max()),
            grad_norm_ratio=[float(ratio.min()), float(ratio.max())],
            ok=ok)
    return correct and bool(read), read


def run(cell, args, device, t_start):
    import jax
    import paddle_tpu as pt

    cfg, tr = cell.config, cell.traffic
    if cfg.get("mesh"):
        raise SystemExit("benchmark: train_stream_mapped drives one chip")
    mapping, reference = _harness(cell)
    sz = mapping.sizes(cfg)
    seq_len, batch = int(tr["seq_len"]), int(tr["sequences_per_step"])
    n_steps = int(tr["steps_per_call"])
    traced = bool(args.trace)

    main, startup, avg, load = mapping.build_trainer(
        pt, sz, seq_len, args.seed, cfg["train"])
    fetches = [avg] + ([] if load is None else [load])
    scope = pt.Scope()
    with pt.scope_guard(scope):
        pt.Executor().run(startup)

    windows = workload.token_windows(args.seed, sz["vocab"], n_steps,
                                     batch, seq_len)
    src, tgt = next(windows)
    weights = mapping.reference_weights(scope.find_var, sz["n_layers"])
    ref_loss, ref_counts = mapping.reference_step(
        reference, weights, cfg, src[0], tgt[0, ..., 0])
    leaves = mapping.update_leaves(sz)
    params = [(label, name) for label, name, _ in leaves]
    adam = {label: _adam_of(main, name) for label, name in params}
    want1, want2 = reference_moments(
        lambda step: mapping.reference_gradients(
            reference, weights, cfg, src[step], tgt[step, ..., 0], leaves),
        [adam[label][2:] for label, _ in params], n_steps)
    del weights
    exe = pt.Executor()

    def state(names):
        """Host copies: the call gives its state's buffers away."""
        return {label: np.asarray(scope.find_var(name))
                for label, name in names}

    def call(src, tgt):
        """(the steps' losses [n_steps], their expert counts [n_steps, 4]
        or None), fetched together."""
        with pt.scope_guard(scope):
            got = exe.run_loop(main, feed={"src_ids": src, "tgt_ids": tgt},
                               fetch_list=fetches, n_steps=n_steps,
                               per_step_feeds=True)
        return np.ravel(got[0]), (None if load is None else
                                  np.asarray(got[1]).reshape(n_steps, 4))

    compiles = common.CompileCounter()
    before = state(params)
    losses, counts = call(src, tgt)          # compiles, or reads the cache
    after = state(params)
    first = state([(label, adam[label][0]) for label, _ in params])
    second = state([(label, adam[label][1]) for label, _ in params])
    updated, update_read = hold_update(
        {label: update_readings(before[label], after[label], first[label],
                                second[label], want1[i], want2[i])
         for i, (label, _) in enumerate(params)})
    del before, after, first, second, want1, want2
    first_loss = float(losses[0])
    correct = bool(abs(first_loss - ref_loss) <= LOSS_RTOL * abs(ref_loss)
                   and np.all(np.isfinite(losses)) and updated)
    read = dict(program=first_loss, reference=ref_loss, rtol=LOSS_RTOL,
                rel_err=abs(first_loss - ref_loss) / abs(ref_loss),
                update=update_read, moved_min=MOVED_MIN,
                moment_rtol=MOMENT_RTOL,
                grad_norm_band=list(GRAD_NORM_BAND))
    held = sz["block"].get("experts_held") or sz["block"].get(
        "num_experts", 0)
    if counts is not None:
        routed, on_held, touched, largest = (int(v) for v in counts[0])
        want_routed, want_held, want_touched, want_largest = \
            ref_counts["counts"]
        held_err = abs(on_held - want_held) / max(want_held, 1)
        correct = correct and routed == want_routed \
            and held_err <= HELD_RTOL
        read.update(routed_pairs=routed, reference_routed=want_routed,
                    held_pairs=on_held, reference_held=want_held,
                    held_rel_err=held_err, held_rtol=HELD_RTOL,
                    held_touched=touched, reference_touched=want_touched,
                    largest_rows=largest, reference_largest=want_largest,
                    near_tie_row_share=ref_counts["near_tie_row_share"])
    for _ in range(4):   # until a call compiles nothing: the state comes
        seen = compiles.count   # back laid out as the step left it
        t0 = time.perf_counter()
        losses, _ = call(*next(windows))
        call_s = time.perf_counter() - t0
        correct = correct and bool(np.all(np.isfinite(losses)))
        if compiles.count == seen:
            break
    common.note(check="first_call_loss_expert_counts_and_update",
                correct=correct,
                warm_call_s=call_s, **read)

    gc.collect()
    gc.freeze()
    tracer = common.Tracer(traced, cell.name, bool(args.rehearse))
    exe.step_timings(reset=True)
    compiles_before = compiles.count
    setup_s = time.perf_counter() - t_start

    # -- the measured window --------------------------------------------------
    calls, window_s, window_load = 0, 0.0, []
    t_open = time.perf_counter()
    while not calls or window_s + call_s <= args.seconds:
        src, tgt = next(windows)
        t0 = time.perf_counter()
        losses, counts = call(src, tgt)
        call_s = max(call_s, time.perf_counter() - t0)
        calls += 1
        window_load.append(counts)
        correct = correct and bool(np.all(np.isfinite(losses)))
        window_s = time.perf_counter() - t_open   # data and all
    timings = exe.step_timings()
    compiles_in_window = compiles.count - compiles_before

    # a traced run profiles one more call, after the window has closed
    # (`train_stream.run` says why)
    traced_load = None
    if traced:
        src, tgt = next(windows)
        tracer.start()
        with jax.profiler.TraceAnnotation("bench/run_loop_call"):
            _, traced_load = call(src, tgt)
        tracer.stop()

    tokens = calls * n_steps * batch * seq_len
    obs = {
        "setup_s": setup_s,
        "window_s": window_s,
        "window_tokens": tokens,
        "window_steps": calls * n_steps,
        "host_s": (timings["host_prep_s"] + timings["dispatch_s"]
                   + timings["fetch_s"]),
        "compiles_in_window": compiles_in_window,
        "chips": cell.chips,
    }
    layers = sz["n_layers"]
    pairs_per_token = None
    if load is not None:
        obs.update(_moe_counts(np.concatenate(window_load), held),
                   moe_layer_steps=calls * n_steps * layers)
        # the step's model operations at the pairs that DID fall here
        pairs_per_token = obs["moe_held_pairs"] / float(tokens * layers)
    obs["flops_per_token"] = mapping.train_flops_per_token(
        sz, seq_len, pairs_per_token)
    if traced:      # the traced call's kernels, priced at its own counts
        obs.update(mapping.kernel_shapes(
            sz, batch, seq_len, n_steps,
            None if traced_load is None
            else _moe_counts(traced_load, held)["moe_held_pairs"]))
    common.note(window=dict(calls=calls, steps=obs["window_steps"],
                            tokens=tokens, seconds=window_s,
                            last_loss=float(losses[-1])))
    return dict(obs=obs, correct=correct, attempted=calls, failed=0,
                reduced=tracer.reduce())
