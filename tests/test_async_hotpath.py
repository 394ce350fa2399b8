"""Async hot-path tests: lazy fetch handles, per-phase step timing,
device-resident state round-trips, the persistent compile cache, the
two-stage prefetch pipeline, and the device-coercion audit contract.

The load-bearing asserts: (1) dispatching step N+1 never blocks on step
N (counted via a monkeypatched jax.block_until_ready); (2) params stay
jax.Arrays between steps and still checkpoint/restore bit-exactly through
the PR-2 manifest + preemption machinery; (3) a fresh Executor warm-starts
from the persistent compile cache (same program = cache hit, changed
program = miss).
"""

import os
import signal

import numpy as np
import pytest

import jax

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.core import compile_cache as cc
from paddle_tpu.core.async_fetch import LazyFetch, PhaseTimer, materialize
from paddle_tpu.reader.prefetch import double_buffer


def _sgd_program(size=4):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [size], dtype="float32")
        y = layers.fc(x, size=size)
        loss = layers.mean(y)
        pt.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def _feed(size=4, batch=2):
    return {"x": np.ones((batch, size), np.float32)}


# ---------------------------------------------------------------------------
# lazy fetch / async dispatch
# ---------------------------------------------------------------------------

class TestLazyFetch:
    def test_dispatch_of_next_step_does_not_block(self, monkeypatch):
        """THE async regression test: with lazy fetches, step N+1 is
        dispatched while step N executes — no block_until_ready happens
        until a handle is actually read."""
        main, startup, loss = _sgd_program()
        scope = pt.Scope()
        with pt.scope_guard(scope):
            exe = pt.Executor()
            exe.run(startup)
            # warm the compile cache first: a cold first call may block
            # internally for compilation, which is not what we count
            exe.run(main, feed=_feed(), fetch_list=[loss],
                    lazy=True)[0].numpy()

            blocks = []
            real = jax.block_until_ready
            monkeypatch.setattr(
                jax, "block_until_ready",
                lambda tree: (blocks.append(1), real(tree))[1])

            (h1,) = exe.run(main, feed=_feed(), fetch_list=[loss],
                            lazy=True)
            (h2,) = exe.run(main, feed=_feed(), fetch_list=[loss],
                            lazy=True)  # step N+1: dispatched, N unread
            assert blocks == [], \
                "dispatching step N+1 blocked on step N's results"
            v1, v2 = float(h1), float(h2)
            assert blocks, "reading a handle must be the only sync point"
            assert np.isfinite(v1) and np.isfinite(v2)

    def test_lazy_values_match_sync_execution(self):
        """Same seeds, same run counters: the lazy path computes the
        exact floats the sync path does."""
        vals = {}
        for mode in ("sync", "lazy"):
            pt.core.program.reset_unique_names()
            main, startup, loss = _sgd_program()
            scope = pt.Scope()
            with pt.scope_guard(scope):
                exe = pt.Executor()
                exe.run(startup)
                outs = []
                for _ in range(3):
                    (o,) = exe.run(main, feed=_feed(), fetch_list=[loss],
                                   lazy=(mode == "lazy"))
                    outs.append(np.asarray(o))
                vals[mode] = np.stack(outs)
        np.testing.assert_array_equal(vals["sync"], vals["lazy"])

    def test_handle_surface(self):
        main, startup, loss = _sgd_program()
        scope = pt.Scope()
        with pt.scope_guard(scope):
            exe = pt.Executor()
            exe.run(startup)
            (h,) = exe.run(main, feed=_feed(), fetch_list=[loss], lazy=True)
        assert isinstance(h, LazyFetch)
        assert h.shape == (1,) and h.dtype == np.dtype("float32")
        assert h.value() is not None          # raw device value, no sync
        a = np.asarray(h)
        assert a.shape == (1,)
        assert float(h) == float(a[0])
        assert "{:.3f}".format(h) == "%.3f" % float(a[0])
        assert h.block_until_ready() is h
        # materialize() recurses containers
        m = materialize({"k": [h]})
        assert isinstance(m["k"][0], np.ndarray)

    def test_run_loop_lazy(self):
        main, startup, loss = _sgd_program()
        scope = pt.Scope()
        with pt.scope_guard(scope):
            exe = pt.Executor()
            exe.run(startup)
            (h,) = exe.run_loop(main, feed=_feed(), fetch_list=[loss],
                                n_steps=4, lazy=True)
            assert isinstance(h, LazyFetch)
            assert np.asarray(h).shape[0] == 4  # stacked [n_steps, ...]


class TestPhaseTimings:
    def test_phases_recorded_and_reset(self):
        main, startup, loss = _sgd_program()
        scope = pt.Scope()
        with pt.scope_guard(scope):
            exe = pt.Executor()
            exe.run(startup)
            exe.step_timings(reset=True)
            exe.run(main, feed=_feed(), fetch_list=[loss])     # compile
            exe.run(main, feed=_feed(), fetch_list=[loss])     # cached
            tm = exe.step_timings()
        assert tm["runs"] == 2
        for phase in ("host_prep", "dispatch", "device", "fetch"):
            assert tm[f"{phase}_s"] >= 0.0
        assert tm["host_prep_s"] > 0.0
        # the cold (compiling) dispatch is charged to compile_s, not to
        # the per-step dispatch phase
        assert tm["compile_s"] > 0.0
        assert tm["dispatch_s"] < tm["compile_s"]
        assert 0.0 <= tm["host_overhead_pct"] <= 100.0
        tm2 = exe.step_timings(reset=True)
        assert exe.step_timings()["runs"] == 0
        assert exe.step_timings()["compile_s"] == 0.0

    def test_parallel_executor_timings_and_lazy(self):
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            x = layers.data("x", [4], dtype="float32")
            loss = layers.mean(layers.fc(x, size=4))
            pt.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
        scope = pt.Scope()
        with pt.scope_guard(scope):
            pt.Executor().run(startup, scope=scope)
            pe = pt.ParallelExecutor(loss_name=loss.name, main_program=main,
                                     scope=scope)
            (h,) = pe.run(fetch_list=[loss],
                          feed={"x": np.ones((8, 4), np.float32)}, lazy=True)
            assert isinstance(h, LazyFetch)
            assert np.isfinite(float(h))
            tm = pe.step_timings()
        assert tm["runs"] == 1 and tm["compile_s"] > 0.0


# ---------------------------------------------------------------------------
# device-resident state
# ---------------------------------------------------------------------------

class TestDeviceResidentState:
    def test_state_stays_on_device_between_steps(self):
        main, startup, loss = _sgd_program()
        scope = pt.Scope()
        with pt.scope_guard(scope):
            exe = pt.Executor()
            exe.run(startup)
            for _ in range(3):
                exe.run(main, feed=_feed(), fetch_list=[loss], lazy=True)
            params = [v.name for v in
                      main.global_block.all_parameters()]
            assert params
            for name in params:
                assert isinstance(scope.find_var(name), jax.Array), \
                    f"{name} left the device between steps"
            # explicit scope read materializes (and blocks) on demand
            assert isinstance(scope.get_numpy(params[0]), np.ndarray)

    def test_checkpoint_roundtrip_from_device_state(self, tmp_path):
        """Device-resident jax.Array state -> save_checkpoint (manifest
        verified) -> load into a fresh scope: bit-exact, and a re-save of
        untouched state produces byte-identical var files (stable bytes —
        what the resilience manifests digest)."""
        main, startup, loss = _sgd_program()
        scope = pt.Scope()
        with pt.scope_guard(scope):
            exe = pt.Executor()
            exe.run(startup)
            for _ in range(2):
                exe.run(main, feed=_feed(), fetch_list=[loss], lazy=True)
            want = {v.name: np.asarray(scope.find_var(v.name))
                    for v in main.global_block.all_parameters()}
            pt.io.save_checkpoint(exe, str(tmp_path / "ck"),
                                  main_program=main, scope=scope)
            pt.io.save_checkpoint(exe, str(tmp_path / "ck"),
                                  main_program=main, scope=scope)
        # stable bytes: two saves of the SAME device state byte-match
        name = next(iter(want)).replace("/", "__") + ".npy"
        b0 = (tmp_path / "ck" / "checkpoint_0" / name).read_bytes()
        b1 = (tmp_path / "ck" / "checkpoint_1" / name).read_bytes()
        assert b0 == b1
        # verified load into a fresh scope restores the exact floats
        fresh = pt.Scope()
        assert pt.io.get_latest_checkpoint_serial(str(tmp_path / "ck")) == 1
        pt.io.load_checkpoint(None, str(tmp_path / "ck"), serial=1,
                              main_program=main, scope=fresh)
        for n, w in want.items():
            np.testing.assert_array_equal(np.asarray(fresh.find_var(n)), w)

    def test_preempt_resume_bit_exact_under_lazy_metrics(self, tmp_path):
        """The PR-2 preemption path composed with the async trainer
        (log_every>1, lazy metrics): SIGTERM at a step boundary ->
        checkpoint -> fresh-trainer resume matches the uninterrupted
        run's params bit-exactly."""
        rs = np.random.RandomState(7)
        data = [(rs.randn(4).astype(np.float32),
                 rs.randn(1).astype(np.float32)) for _ in range(32)]

        def make_trainer(d):
            pt.core.program.reset_unique_names()

            def train_func():
                x = layers.data("x", [4])
                y = layers.data("y", [1])
                pred = layers.fc(x, size=1)
                return [layers.mean(layers.square_error_cost(pred, y))]

            cfg = pt.CheckpointConfig(d, step_interval=3)
            return pt.Trainer(train_func,
                              lambda: pt.optimizer.SGDOptimizer(0.05),
                              checkpoint_config=cfg)

        def run(trainer, handler=None):
            trainer.train(num_epochs=1,
                          event_handler=handler or (lambda e: None),
                          reader=pt.reader.batch(lambda: iter(data), 4),
                          log_every=4)

        def params(t):
            with pt.scope_guard(t.scope):
                return {v.name: np.asarray(t.scope.find_var(v.name))
                        for v in t.train_program.global_block
                        .all_parameters()}

        a = make_trainer(str(tmp_path / "a"))
        run(a)
        want = params(a)

        kill_after = 4

        def handler(event):
            if isinstance(event, pt.EndStepEvent):
                # non-log steps carry lazy handles; reading one works
                if event.metrics:
                    assert np.isfinite(np.ravel(event.metrics[0])[0])
                if event.step == kill_after:
                    os.kill(os.getpid(), signal.SIGTERM)

        b = make_trainer(str(tmp_path / "b"))
        run(b, handler)
        assert b.preempted
        c = make_trainer(str(tmp_path / "b"))
        run(c)
        got = params(c)
        for n in want:
            np.testing.assert_array_equal(got[n], want[n])


# ---------------------------------------------------------------------------
# persistent compile cache
# ---------------------------------------------------------------------------

class TestCompileCache:
    """The compile-cache rule (core/compile_cache.py): where
    JAX_COMPILATION_CACHE_DIR is set, JAX's own reading of it stands and
    no code sets a directory; where it is not, the chip entry points get
    one fixed directory inside the checkout."""

    @pytest.fixture
    def restore_cache_config(self):
        from jax.experimental.compilation_cache import (
            compilation_cache as jcc)
        names = ("jax_compilation_cache_dir",
                 "jax_persistent_cache_min_compile_time_secs",
                 "jax_persistent_cache_min_entry_size_bytes")
        was = {n: getattr(jax.config, n) for n in names}
        yield
        # jax.config is process-global: un-point the cache so later tests
        # don't write entries into a deleted tmpdir
        for n, v in was.items():
            jax.config.update(n, v)
        jcc.reset_cache()

    @pytest.fixture
    def cache_dir(self, tmp_path, monkeypatch, restore_cache_config):
        """A cache placed from outside: the variable is set and JAX has
        read it (it does so at import; the test stands in for that)."""
        d = str(tmp_path / "xla_cache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
        jax.config.update("jax_compilation_cache_dir", d)
        assert cc.enable_compile_cache() == d
        return d

    def test_env_dir_stands_and_no_code_sets_one(self, tmp_path,
                                                 monkeypatch,
                                                 restore_cache_config):
        from jax.experimental.compilation_cache import (
            compilation_cache as jcc)
        d = str(tmp_path / "from_outside")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
        jax.config.update("jax_compilation_cache_dir", d)
        monkeypatch.setattr(jcc, "set_cache_dir", lambda path: pytest.fail(
            f"code set the cache dir to {path!r} although "
            "JAX_COMPILATION_CACHE_DIR is set"))
        assert cc.enable_compile_cache() == d
        assert cc.active_cache_dir() == d

    def test_unset_env_uses_the_fixed_checkout_dir(self, tmp_path,
                                                   monkeypatch,
                                                   restore_cache_config):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        # neither $HOME nor $TMPDIR has a say in where it is
        monkeypatch.setenv("HOME", str(tmp_path / "home"))
        monkeypatch.setenv("TMPDIR", str(tmp_path / "tmp"))
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert cc.enable_compile_cache() == os.path.join(repo, ".xla_cache")
        assert cc.active_cache_dir() == cc.CHECKOUT_CACHE_DIR
        with open(os.path.join(repo, ".gitignore")) as f:
            assert ".xla_cache/" in f.read().split()

    def test_library_leaves_the_cache_alone(self, monkeypatch):
        """Constructing executors neither turns the cache on nor moves
        it: only chip_smoke.py and benchmark/run.py call
        enable_compile_cache."""
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = cc.active_cache_dir()
        exe = pt.Executor()
        main, startup, loss = _sgd_program(size=3)
        with pt.scope_guard(pt.Scope()):
            exe.run(startup)
            exe.run(main, feed=_feed(size=3), fetch_list=[loss])
        assert cc.active_cache_dir() == before

    def test_warm_start_hits_and_changed_program_misses(self, cache_dir,
                                                        monkeypatch):
        """Same program fingerprint in a FRESH Executor compiles from the
        persistent cache (observed disk reads, no new entries); a changed
        program misses (writes new entries). Sizes 5/9 are unique to this
        test so an identical HLO compiled by ANOTHER test cannot satisfy
        the warm start from JAX's in-memory caches."""
        from jax._src import compilation_cache as jcc
        reads = []
        real_get = jcc.get_executable_and_time
        monkeypatch.setattr(
            jcc, "get_executable_and_time",
            lambda *a, **k: (lambda r: (reads.append(r[0] is not None),
                                        r)[1])(real_get(*a, **k)))

        def run_once(size):
            pt.core.program.reset_unique_names()
            main, startup, loss = _sgd_program(size=size)
            scope = pt.Scope()
            with pt.scope_guard(scope):
                exe = pt.Executor()   # fresh: empty in-process jit cache
                exe.run(startup)
                exe.run(main, feed=_feed(size=size), fetch_list=[loss])

        run_once(5)
        n_cold = cc.cache_entry_count(cache_dir)
        assert n_cold > 0, "cold compile wrote no persistent entries"
        assert not any(reads), "cold compile claimed a cache hit"

        reads.clear()
        run_once(5)   # identical program, fresh Executor: pure cache hit
        assert any(reads), \
            "warm re-compile of an identical program never read the cache"
        assert cc.cache_entry_count(cache_dir) == n_cold, \
            "warm re-compile of an identical program wrote new entries"

        reads.clear()
        run_once(9)   # different shapes = different HLO: must miss
        assert cc.cache_entry_count(cache_dir) > n_cold, \
            "changed program did not produce a cache miss"


# ---------------------------------------------------------------------------
# two-stage prefetch
# ---------------------------------------------------------------------------

class TestTwoStagePrefetch:
    def test_order_preserved_and_values_on_device(self):
        def reader():
            for i in range(8):
                yield {"x": np.full((2, 2), i, np.float32)}

        seen = list(double_buffer(reader, capacity=2)())
        assert len(seen) == 8
        for i, batch in enumerate(seen):
            assert isinstance(batch["x"], jax.Array)
            assert float(batch["x"][0, 0]) == i

    def test_error_propagates_after_delivered_batches(self):
        def reader():
            yield {"x": np.zeros(2, np.float32)}
            yield {"x": np.ones(2, np.float32)}
            raise RuntimeError("decode exploded")

        it = double_buffer(reader)()
        assert float(np.asarray(next(it)["x"])[0]) == 0.0
        assert float(np.asarray(next(it)["x"])[0]) == 1.0
        with pytest.raises(RuntimeError, match="decode exploded"):
            for _ in it:
                pass

    def test_early_exit_does_not_hang(self):
        def reader():
            for i in range(1000):
                yield {"x": np.zeros(4, np.float32)}

        it = double_buffer(reader, capacity=2)()
        next(it)
        it.close()  # generator finalizer sets the stop event; no hang


# ---------------------------------------------------------------------------
# trainer log_every materialization contract
# ---------------------------------------------------------------------------

class TestTrainerLogEvery:
    def test_metrics_materialize_only_on_log_steps(self):
        rs = np.random.RandomState(3)
        data = [(rs.randn(4).astype(np.float32),
                 rs.randn(1).astype(np.float32)) for _ in range(16)]

        def train_func():
            x = layers.data("x", [4])
            y = layers.data("y", [1])
            pred = layers.fc(x, size=1)
            return [layers.mean(layers.square_error_cost(pred, y))]

        pt.core.program.reset_unique_names()
        trainer = pt.Trainer(train_func,
                             lambda: pt.optimizer.SGDOptimizer(0.05))
        kinds = {}

        def handler(event):
            if isinstance(event, pt.EndStepEvent) and event.metrics:
                kinds[event.step] = type(event.metrics[0])

        trainer.train(num_epochs=1, event_handler=handler,
                      reader=pt.reader.batch(lambda: iter(data), 4),
                      log_every=2)
        assert kinds[0] is np.ndarray and kinds[2] is np.ndarray
        assert kinds[1] is LazyFetch and kinds[3] is LazyFetch


# ---------------------------------------------------------------------------
# PhaseTimer unit
# ---------------------------------------------------------------------------

class TestPhaseTimer:
    def test_accumulation_and_overhead(self):
        t = PhaseTimer()
        t.add("host_prep", 0.2)
        t.add("dispatch", 0.1)
        t.add("device", 0.6)
        t.add("fetch", 0.1)
        t.count_run()
        s = t.snapshot()
        assert s["runs"] == 1
        assert s["host_overhead_pct"] == pytest.approx(40.0)
        s = t.snapshot(reset=True)
        assert t.snapshot()["runs"] == 0
        assert t.snapshot()["host_overhead_pct"] is None
