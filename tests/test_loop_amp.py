"""Device-side training loop (build_loop_fn / Executor.run_loop) + AMP.

Covers round-2 perf machinery:
  * build_loop_fn parity with repeated build_step_fn (≙ the reference's
    invariant that N executor runs == one N-iteration loop, executor.cc:322)
  * per_step_feeds indexing
  * Executor.run_loop state continuity with the scope
  * amp_dtype mixed precision: f32 master weights, bf16 compute
  * master-weight policy: bf16 activations still yield f32 parameters
  * amp_dtype survives clone()/JSON round-trip
"""

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.core import lowering


def _mlp_program(in_dim=4, hidden=8, lr=0.1, dtype="float32"):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [in_dim], dtype=dtype)
        y = layers.data("y", [1], dtype=dtype)
        h = layers.fc(input=x, size=hidden, act="relu")
        pred = layers.fc(input=h, size=1)
        loss = layers.mean(layers.square_error_cost(input=pred, label=y))
        opt = pt.optimizer.SGDOptimizer(learning_rate=lr)
        opt.minimize(loss)
    return main, startup, loss


def _feed(rng, batch=8, in_dim=4, dtype="float32"):
    x = rng.rand(batch, in_dim).astype("float32")
    y = (x.sum(axis=1, keepdims=True) * 0.5).astype("float32")
    if dtype == "bfloat16":
        import ml_dtypes
        x = x.astype(ml_dtypes.bfloat16)
        y = y.astype(ml_dtypes.bfloat16)
    return {"x": x, "y": y}


class TestBuildLoopFn:
    def test_matches_repeated_steps(self):
        import jax
        main, startup, loss = _mlp_program()
        rng = np.random.RandomState(0)
        feed = _feed(rng)
        scope = pt.Scope()
        with pt.scope_guard(scope):
            exe = pt.Executor()
            exe.run(startup)
            state0 = {k: np.asarray(v)
                      for k, v in exe._state_for(main, scope).items()}
            fa = exe._prep_feed(main, feed)

            step, _ = lowering.build_step_fn(main, list(fa), [loss.name],
                                             sorted(state0))
            st = dict(state0)
            key = jax.random.PRNGKey(7)
            step_losses = []
            for i in range(4):
                (l,), st = step(st, fa, jax.random.fold_in(key, i))
                step_losses.append(float(np.ravel(l)[0]))

            loop, _ = lowering.build_loop_fn(main, list(fa), [loss.name],
                                             sorted(state0), n_steps=4)
            (stacked,), st_loop = loop(dict(state0), fa, key)
            np.testing.assert_allclose(np.ravel(stacked), step_losses,
                                       rtol=1e-5)
            for k in st:
                np.testing.assert_allclose(np.asarray(st[k]),
                                           np.asarray(st_loop[k]), rtol=1e-5)

    def test_per_step_feeds_indexing(self):
        import jax
        main, startup, loss = _mlp_program()
        rng = np.random.RandomState(1)
        feeds = [_feed(rng) for _ in range(3)]
        scope = pt.Scope()
        with pt.scope_guard(scope):
            exe = pt.Executor()
            exe.run(startup)
            state0 = {k: np.asarray(v)
                      for k, v in exe._state_for(main, scope).items()}
            fa0 = exe._prep_feed(main, feeds[0])

            step, _ = lowering.build_step_fn(main, list(fa0), [loss.name],
                                             sorted(state0))
            st = dict(state0)
            key = jax.random.PRNGKey(3)
            want = []
            for i, f in enumerate(feeds):
                fa = exe._prep_feed(main, f)
                (l,), st = step(st, fa, jax.random.fold_in(key, i))
                want.append(float(np.ravel(l)[0]))

            stacked_feed = {k: np.stack([np.asarray(f[k]) for f in feeds])
                            for k in feeds[0]}
            loop, _ = lowering.build_loop_fn(main, list(fa0), [loss.name],
                                             sorted(state0), n_steps=3,
                                             per_step_feeds=True)
            (stacked,), _ = loop(dict(state0), stacked_feed, key)
            np.testing.assert_allclose(np.ravel(stacked), want, rtol=1e-5)

    def test_unroll_matches(self):
        import jax
        main, startup, loss = _mlp_program()
        rng = np.random.RandomState(2)
        feed = _feed(rng)
        scope = pt.Scope()
        with pt.scope_guard(scope):
            exe = pt.Executor()
            exe.run(startup)
            state0 = {k: np.asarray(v)
                      for k, v in exe._state_for(main, scope).items()}
            fa = exe._prep_feed(main, feed)
            key = jax.random.PRNGKey(5)
            outs = []
            for unroll in (1, 2):
                loop, _ = lowering.build_loop_fn(
                    main, list(fa), [loss.name], sorted(state0), n_steps=4,
                    unroll=unroll)
                (stacked,), _ = loop(dict(state0), fa, key)
                outs.append(np.ravel(stacked))
            np.testing.assert_allclose(outs[0], outs[1], rtol=1e-6)


class TestRunLoop:
    def test_trains_and_threads_scope_state(self):
        main, startup, loss = _mlp_program()
        rng = np.random.RandomState(0)
        feed = _feed(rng)
        scope = pt.Scope()
        with pt.scope_guard(scope):
            exe = pt.Executor()
            exe.run(startup)
            (losses,) = exe.run_loop(main, feed=feed, fetch_list=[loss],
                                     n_steps=6)
            assert losses.shape[0] == 6
            assert losses[-1] < losses[0]
            # scope carries the trained params into a plain run
            (l,) = exe.run(main, feed=feed, fetch_list=[loss])
            assert float(np.ravel(l)[0]) <= float(losses[-1]) * 1.5


    @pytest.mark.parametrize("per_step_feeds", [False, True])
    def test_leaves_its_plan_in_the_trace_ring(self, per_step_feeds):
        """Each loop executable BUILT leaves one `program/loop_plan`
        record (no duration: what the scan's body is), outside the
        `program/exec/<phase>` records whose sum `step_timings()` is; a
        cached call leaves none."""
        from paddle_tpu.obs import trace
        main, startup, loss = _mlp_program()
        feed = _feed(np.random.RandomState(0))
        if per_step_feeds:
            feed = {k: np.stack([v] * 3) for k, v in feed.items()}
        scope = pt.Scope()
        with pt.scope_guard(scope):
            exe = pt.Executor()
            exe.run(startup)
            trace.reset()
            exe.step_timings(reset=True)
            for _ in range(2):
                exe.run_loop(main, feed=feed, fetch_list=[loss], n_steps=3,
                             per_step_feeds=per_step_feeds)
            exe.run_loop(main, feed=feed, fetch_list=[loss], n_steps=3,
                         per_step_feeds=per_step_feeds, unroll=2)
        plans = [e for e in trace.events()
                 if (e["cat"], e["name"]) == ("program", "loop_plan")]
        assert [p["args"] for p in plans] == [
            dict(n_steps=3, unroll=1, per_step_feeds=per_step_feeds),
            dict(n_steps=3, unroll=2, per_step_feeds=per_step_feeds)]
        assert all(p["dur"] == 0 for p in plans)
        timed = exe.step_timings()
        # the executor's PhaseTimer records: cat "exec", the annotation's
        # plane `program/exec/<phase>`
        phases = [r for r in trace.phase_records() if r[0] == "exec"]
        assert phases and not any(r[1] == "loop_plan" for r in phases)
        for name in ("host_prep", "dispatch", "fetch"):
            assert sum(r[3] for r in phases if r[1] == name) \
                == pytest.approx(timed[name + "_s"], abs=2e-6), name

    @pytest.mark.parametrize("where", ["Executor", "ParallelExecutor",
                                       "build_loop_fn"])
    def test_the_default_body_is_one_step(self, where):
        """A second step in the scan's body is the caller's to ask for,
        or the estimate's to grant where the backend states its memory:
        here (the CPU states none) a loop built without `unroll` holds one
        step. At the 1.3B train cell a second made the compiler recompute
        the head's logits every step (tests/test_chip_compile.py)."""
        import inspect
        from paddle_tpu.obs import trace
        from paddle_tpu.parallel import ParallelExecutor, make_mesh
        if where == "build_loop_fn":
            assert inspect.signature(lowering.build_loop_fn) \
                .parameters["unroll"].default == 1
            return
        main, startup, loss = _mlp_program()
        feed = _feed(np.random.RandomState(0))
        scope = pt.Scope()
        with pt.scope_guard(scope):
            pt.Executor().run(startup)
            trace.reset()
            if where == "Executor":
                assert inspect.signature(pt.Executor.run_loop) \
                    .parameters["unroll"].default is None
                pt.Executor().run_loop(main, feed=feed, fetch_list=[loss],
                                       n_steps=2)
            else:
                assert inspect.signature(ParallelExecutor.run_loop) \
                    .parameters["unroll"].default is None
                ParallelExecutor(loss_name=loss.name, main_program=main,
                                 mesh=make_mesh({"dp": -1}), scope=scope) \
                    .run_loop([loss], feed=feed, n_steps=2)
        plans = [e["args"] for e in trace.events()
                 if (e["cat"], e["name"]) == ("program", "loop_plan")]
        assert plans == [dict(n_steps=2, unroll=1, per_step_feeds=False)]

    def test_a_second_step_is_taken_where_the_estimate_leaves_room(self):
        """`loop_body_steps`: state + TWICE a step's temporaries against
        the device's limit; one step where the backend gives none."""
        from paddle_tpu.analysis.memory import (estimate_memory,
                                                loop_body_steps)
        main, _, _ = _mlp_program()
        est = estimate_memory(main, batch=8)
        need = est.state_bytes + 2 * est.temp_bytes
        assert est.temp_bytes > 0
        assert loop_body_steps(main, batch=8) == 1           # the CPU
        assert loop_body_steps(main, batch=8, bytes_limit=need + 1) == 2
        assert loop_body_steps(main, batch=8, bytes_limit=need) == 1
        assert loop_body_steps(
            main, batch=8, bytes_limit=est.state_bytes + est.temp_bytes) == 1


def test_the_loop_unroll_sweep_rehearses(tmp_path, capsys):
    """`tools/loop_unroll_sweep.py --rehearse`: the sweep that weighs a
    second step in the scan's body, at a tiny size: it builds a
    transformer and the MLP, runs both bodies, compiles each again from shapes alone for
    the compiler's figures and prints a row a body; no time under a
    device's name."""
    import importlib.util
    import json
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "loop_unroll_sweep.py")
    spec = importlib.util.spec_from_file_location("loop_unroll_sweep", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    out = tmp_path / "sweep.jsonl"
    assert tool.main(["--rehearse", "--program", "cell,mlp",
                      "--out", str(out)]) == 0
    lines = [json.loads(line) for line in out.read_text().splitlines()]
    assert lines[0]["what"] == "device" and lines[0]["rehearsal"]
    assert [(r["program"], r["unroll"]) for r in lines
            if r["what"] == "default_body"] == [("cell", 1), ("mlp", 1)]
    rows = [r for r in lines if r["what"] == "body"]
    assert [(r["program"], r["unroll"]) for r in rows] == [
        (p, u) for p in ("cell", "mlp") for u in (1, 2)]
    assert all(r["unit"].endswith("_rehearsal") and r["median"] > 0
               and r["temp_gib"] > 0 and r["argument_gib"] > 0
               and r["remat_instructions"] == 0 for r in rows)
    assert ".remat" in capsys.readouterr().out      # the table's heading
    # without a chip and without --rehearse it times nothing
    assert tool.main(["--program", "mlp"]) == 2


class TestPerStepSequenceFeeds:
    def test_seq_len_synthesis_and_ragged_rejection(self):
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            w = layers.data("words", [1], dtype="int64", lod_level=1)
            emb = layers.embedding(w, size=[50, 8])
            layers.sequence_pool(emb, "last")
        exe = pt.Executor()
        seq_len_name = main.global_block.var("words").seq_len_var
        # padded per-step feed [n_steps=3, B=4, T=5] -> lens [3, 4] all 5
        arr = np.zeros((3, 4, 5), dtype="int64")
        fa = exe._prep_feed(main, {"words": arr}, per_step=True)
        assert fa[seq_len_name].shape == (3, 4)
        assert int(np.asarray(fa[seq_len_name]).max()) == 5
        # ragged list feeds are rejected in per-step mode
        with pytest.raises(ValueError, match="per-step feed"):
            exe._prep_feed(main, {"words": [np.zeros((2, 1), "int64")]},
                           per_step=True)


class TestAmp:
    def test_amp_f32_masters_train(self):
        main, startup, loss = _mlp_program()
        main.amp_dtype = "bfloat16"
        rng = np.random.RandomState(0)
        feed = _feed(rng)  # f32 feeds, cast to bf16 by the lowering
        scope = pt.Scope()
        with pt.scope_guard(scope):
            exe = pt.Executor()
            exe.run(startup)
            (losses,) = exe.run_loop(main, feed=feed, fetch_list=[loss],
                                     n_steps=8)
            assert losses[-1] < losses[0]
            for p in main.all_parameters():
                v = scope.find_var(p.name)
                assert str(np.asarray(v).dtype) == "float32", p.name

    def test_master_weights_for_bf16_activations(self):
        main, startup, loss = _mlp_program(dtype="bfloat16")
        for p in main.all_parameters():
            assert p.dtype == "float32", p.name
        rng = np.random.RandomState(0)
        feed = _feed(rng, dtype="bfloat16")
        scope = pt.Scope()
        with pt.scope_guard(scope):
            exe = pt.Executor()
            exe.run(startup)
            (losses,) = exe.run_loop(main, feed=feed, fetch_list=[loss],
                                     n_steps=8)
            assert losses[-1] < losses[0]
            for p in main.all_parameters():
                v = scope.find_var(p.name)
                assert str(np.asarray(v).dtype) == "float32", p.name

    def test_amp_masters_accumulate_sub_resolution_updates(self):
        """The optimizer must update the f32 masters, not the bf16-cast
        copy: per-step deltas below bf16 resolution still accumulate."""
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            x = layers.data("x", [1])
            y = layers.data("y", [1])
            pred = layers.fc(input=x, size=1,
                             param_attr=pt.ParamAttr(
                                 initializer=pt.initializer.ConstantInitializer(1.0)),
                             bias_attr=False)
            loss = layers.mean(layers.square_error_cost(input=pred, label=y))
            pt.optimizer.SGDOptimizer(learning_rate=5e-5).minimize(loss)
        main.amp_dtype = "bfloat16"
        scope = pt.Scope()
        with pt.scope_guard(scope):
            exe = pt.Executor()
            exe.run(startup)
            feed = {"x": np.ones((4, 1), np.float32),
                    "y": np.full((4, 1), 2.0, np.float32)}
            for _ in range(20):
                exe.run(main, feed=feed, fetch_list=[loss])
            w = float(np.ravel(np.asarray(
                scope.find_var(main.all_parameters()[0].name)))[0])
        # grad = 2*(w-2) ≈ -2, delta ≈ 1e-4/step « bf16 resolution at 1.0
        # (0.0078); 20 steps must accumulate ≈ 2e-3 in the f32 master
        assert w > 1.0 + 1e-3, w

    def test_amp_dtype_survives_clone_and_json(self):
        main, _, _ = _mlp_program()
        main.amp_dtype = "bfloat16"
        assert main.clone().amp_dtype == "bfloat16"
        assert main.clone(for_test=True).amp_dtype == "bfloat16"
        assert pt.Program.from_json(main.to_json()).amp_dtype == "bfloat16"

    def test_fingerprint_tracks_amp_and_mutation(self):
        main, _, _ = _mlp_program()
        fp0 = main.fingerprint()
        assert main.fingerprint() == fp0  # memoized, stable
        main.amp_dtype = "bfloat16"
        fp1 = main.fingerprint()
        assert fp1 != fp0
        main.global_block.create_var("x2", shape=(8, 4), dtype="float32")
        main.global_block.append_op("scale", {"X": ["x"]}, {"Out": ["x2"]},
                                    {"scale": 2.0})
        assert main.fingerprint() != fp1
