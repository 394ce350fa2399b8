"""Chaos tests: fault injection, retry, verified checkpoints, preemption.

The recovery path is tested CODE here, not hope: every scenario drives a
real failure through the PT_FAULT_INJECT plan (resilience/faults.py) —
or corrupts committed bytes directly — and asserts the system restores a
consistent, verifiable state. scripts/ci.sh chaos replays this file
under two fixed PT_CHAOS_SEED values.
"""

import json
import os
import signal

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.resilience import (FaultInjected, RetryPolicy, faults,
                                   manifest, resilient_reader, retry_call)

CHAOS_SEED = int(os.environ.get("PT_CHAOS_SEED", "0"))


@pytest.fixture(autouse=True)
def fresh_fault_plan(monkeypatch):
    """Each test starts with no armed plan and fresh hit counters."""
    monkeypatch.delenv("PT_FAULT_INJECT", raising=False)
    faults.reset()
    yield
    faults.reset()


def _arm(monkeypatch, spec):
    monkeypatch.setenv("PT_FAULT_INJECT", spec)
    faults.reset()


# ---------------------------------------------------------------------------
# fault plan grammar + determinism
# ---------------------------------------------------------------------------

class TestFaultPlan:
    def test_nth_trigger_is_one_shot(self, monkeypatch):
        _arm(monkeypatch, "step_crash@3")
        assert faults.fire("step_crash") is None
        assert faults.fire("step_crash") is None
        assert faults.fire("step_crash") == 3
        assert faults.fire("step_crash") is None

    def test_every_and_repeated_specs(self, monkeypatch):
        _arm(monkeypatch, "io_crash@*")
        assert faults.fire("io_crash") == 1
        assert faults.fire("io_crash") == 2
        _arm(monkeypatch, "reader_raise@2,reader_raise@4")
        fired = [faults.fire("reader_raise") for _ in range(5)]
        assert fired == [None, 2, None, 4, None]

    def test_probabilistic_trigger_is_seed_deterministic(self):
        a = faults.FaultPlan.parse(f"reader_raise@p0.5:seed={CHAOS_SEED}")
        b = faults.FaultPlan.parse(f"reader_raise@p0.5:seed={CHAOS_SEED}")
        seq_a = [a.fire("reader_raise") for _ in range(64)]
        seq_b = [b.fire("reader_raise") for _ in range(64)]
        assert seq_a == seq_b
        assert any(h is not None for h in seq_a)  # p=.5 over 64 draws
        other = faults.FaultPlan.parse(
            f"reader_raise@p0.5:seed={CHAOS_SEED + 1}")
        assert [other.fire("reader_raise") for _ in range(64)] != seq_a

    def test_unknown_site_and_malformed_specs_raise(self):
        with pytest.raises(ValueError, match="unknown site"):
            faults.FaultPlan.parse("not_a_site@1")
        with pytest.raises(ValueError, match="malformed"):
            faults.FaultPlan.parse("io_crash")
        with pytest.raises(ValueError, match="1-based"):
            faults.FaultPlan.parse("io_crash@0")
        with pytest.raises(ValueError, match="probability"):
            faults.FaultPlan.parse("io_crash@p1.5")

    def test_unarmed_crash_point_is_a_noop(self):
        faults.crash_point("step_crash")  # no plan: must not raise


# ---------------------------------------------------------------------------
# retry primitive + reader restarts
# ---------------------------------------------------------------------------

class TestRetry:
    def test_succeeds_after_transient_failures(self):
        sleeps = []
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise OSError("transient")
            return "ok"

        policy = RetryPolicy(retries=4, base_delay=0.01, jitter=0.5,
                             seed=CHAOS_SEED, sleep=sleeps.append)
        assert retry_call(flaky, policy=policy) == "ok"
        assert calls["n"] == 3 and len(sleeps) == 2
        # exponential envelope: base*2^k <= delay <= base*2^k*(1+jitter)
        for k, d in enumerate(sleeps):
            assert 0.01 * 2 ** k <= d <= 0.01 * 2 ** k * 1.5 + 1e-12

    def test_exhaustion_reraises_the_original_error(self):
        err = ValueError("root cause")

        def always():
            raise err

        with pytest.raises(ValueError) as ei:
            retry_call(always, policy=RetryPolicy(
                retries=2, base_delay=0, sleep=lambda _d: None))
        assert ei.value is err

    def test_non_matching_errors_are_not_retried(self):
        calls = {"n": 0}

        def boom():
            calls["n"] += 1
            raise KeyError("nope")

        with pytest.raises(KeyError):
            retry_call(boom, policy=RetryPolicy(
                retries=5, retry_on=OSError, sleep=lambda _d: None))
        assert calls["n"] == 1

    def test_deadline_stops_retrying(self):
        clock = {"t": 0.0}

        def sleep(d):
            clock["t"] += d

        def always():
            raise OSError("down")

        policy = RetryPolicy(retries=50, base_delay=1.0, max_delay=1.0,
                             jitter=0.0, deadline=3.5, sleep=sleep,
                             clock=lambda: clock["t"])
        with pytest.raises(OSError):
            retry_call(always, policy=policy)
        assert clock["t"] <= 3.5

    def test_reader_restart_is_exactly_once_in_order(self):
        calls = {"n": 0}

        def reader():
            calls["n"] += 1
            first = calls["n"] == 1
            for i in range(10):
                if first and i == 4:
                    raise IOError("stream died")
                yield i

        wrapped = resilient_reader(
            reader, policy=RetryPolicy(retries=2, base_delay=0,
                                       sleep=lambda _d: None))
        assert list(wrapped()) == list(range(10))
        assert calls["n"] == 2  # one restart, fast-forwarded past 0..3

    def test_reader_retry_exhaustion_raises_original(self):
        calls = {"n": 0}
        err = IOError("persistently down")

        def reader():
            calls["n"] += 1
            yield 0
            raise err

        wrapped = resilient_reader(
            reader, policy=RetryPolicy(retries=2, base_delay=0,
                                       sleep=lambda _d: None))
        with pytest.raises(IOError) as ei:
            list(wrapped())
        assert ei.value is err
        assert calls["n"] == 3  # first attempt + 2 bounded retries

    def test_reader_restart_honors_the_deadline(self):
        clock = {"t": 0.0}

        def sleep(d):
            clock["t"] += d

        def reader():
            yield 0
            raise OSError("down")

        wrapped = resilient_reader(reader, policy=RetryPolicy(
            retries=50, base_delay=1.0, max_delay=1.0, jitter=0.0,
            deadline=3.5, sleep=sleep, clock=lambda: clock["t"]))
        with pytest.raises(OSError):
            list(wrapped())
        assert clock["t"] <= 3.5  # stall budget capped, attempts left over

    def test_injected_reader_fault_is_retried(self, monkeypatch):
        _arm(monkeypatch, "reader_raise@3")
        wrapped = resilient_reader(
            lambda: iter(range(6)),
            policy=RetryPolicy(retries=1, base_delay=0,
                               sleep=lambda _d: None))
        assert list(wrapped()) == list(range(6))

    def test_injected_reader_fault_without_policy_propagates(
            self, monkeypatch):
        _arm(monkeypatch, "reader_raise@3")
        with pytest.raises(FaultInjected):
            list(resilient_reader(lambda: iter(range(6)))())

    def test_probabilistic_faults_with_deep_retries_deliver_everything(
            self, monkeypatch):
        # the CI chaos leg varies PT_CHAOS_SEED: whatever failure schedule
        # p=0.3 draws, bounded restarts must still deliver exactly-once
        _arm(monkeypatch, f"reader_raise@p0.3:seed={CHAOS_SEED}")
        wrapped = resilient_reader(
            lambda: iter(range(20)),
            policy=RetryPolicy(retries=200, base_delay=0,
                               sleep=lambda _d: None))
        assert list(wrapped()) == list(range(20))


# ---------------------------------------------------------------------------
# manifests
# ---------------------------------------------------------------------------

class TestManifest:
    def _dir(self, tmp_path):
        d = str(tmp_path / "m")
        os.makedirs(d)
        for name, payload in (("a.npy", b"alpha" * 100),
                              ("b.npy", b"bravo" * 37)):
            with open(os.path.join(d, name), "wb") as f:
                f.write(payload)
        return d

    def test_roundtrip_ok(self, tmp_path):
        d = self._dir(tmp_path)
        man = manifest.write_manifest(d)
        assert set(man["files"]) == {"a.npy", "b.npy"}
        assert manifest.verify_dir(d) == ("ok", [])

    def test_content_flip_size_change_and_deletion_are_corrupt(
            self, tmp_path):
        d = self._dir(tmp_path)
        manifest.write_manifest(d)
        path = os.path.join(d, "a.npy")
        data = bytearray(open(path, "rb").read())
        data[10] ^= 0xFF  # same size, different bytes: crc must catch it
        with open(path, "wb") as f:
            f.write(data)
        status, problems = manifest.verify_dir(d)
        assert status == "corrupt" and "crc32" in problems[0]

        manifest.write_manifest(d)
        with open(path, "ab") as f:
            f.write(b"junk")
        assert manifest.verify_dir(d)[0] == "corrupt"

        manifest.write_manifest(d)
        os.remove(path)
        status, problems = manifest.verify_dir(d)
        assert status == "corrupt" and "absent" in problems[0]

    def test_single_file_check_and_legacy_dirs(self, tmp_path):
        d = self._dir(tmp_path)
        assert manifest.verify_dir(d) == ("legacy", [])  # no manifest yet
        assert manifest.verify_file(d, "a.npy") is None
        manifest.write_manifest(d)
        assert manifest.verify_file(d, "a.npy") is None
        with open(os.path.join(d, "a.npy"), "ab") as f:
            f.write(b"x")
        assert "size" in manifest.verify_file(d, "a.npy")

    def test_tmp_skip_rule_spares_bn_running_stat_files(self):
        # batch_norm running stats persist as batch_norm_N.tmp_0.npy —
        # they MUST be digested; only real in-flight temps are skipped
        assert not manifest._skip("batch_norm_0.tmp_0.npy")
        assert not manifest._skip("batch_norm_0.tmp_1.npy")
        assert manifest._skip("fc_0.w_0.npy.tmp12345")
        assert manifest._skip("__host_table__.t.rank0.npz.tmp")
        assert manifest._skip("manifest.json")
        assert manifest._skip("_SUCCESS")

    def test_bn_running_stats_are_manifested_and_verified(self, tmp_path):
        from paddle_tpu.models import resnet
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            img = layers.data("img", [4, 8, 8])
            resnet.conv_bn_layer(img, 4, 3, 1, 1, is_test=False)
        exe = pt.Executor()
        exe.run(startup)
        ckpt = str(tmp_path / "ckpt")
        pt.io.save_checkpoint(exe, ckpt, main_program=main)
        cur = os.path.join(ckpt, "checkpoint_0")
        man = manifest.read_manifest(cur)
        stats = [n for n in man["files"] if ".tmp_0.npy" in n]
        assert stats, "running mean file missing from the manifest"
        # bit-rot the running mean: verification must catch it
        victim = os.path.join(cur, stats[0])
        blob = bytearray(open(victim, "rb").read())
        blob[-1] ^= 0xFF
        with open(victim, "wb") as f:
            f.write(blob)
        with pytest.warns(UserWarning, match="quarantined"):
            assert pt.io.get_latest_checkpoint_serial(ckpt) == -1

    def test_quarantine_renames_and_never_collides(self, tmp_path):
        for want in ("m.corrupt", "m.corrupt-1"):
            d = self._dir(tmp_path) if not os.path.exists(
                str(tmp_path / "m")) else str(tmp_path / "m")
            os.makedirs(d, exist_ok=True)
            dest = manifest.quarantine(d)
            assert os.path.basename(dest) == want and os.path.isdir(dest)


# ---------------------------------------------------------------------------
# verified checkpoints under injected faults
# ---------------------------------------------------------------------------

def _linreg():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [4])
        y = layers.data("y", [1])
        pred = layers.fc(x, size=1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        pt.optimizer.SGDOptimizer(0.1).minimize(loss)
    return main, startup, loss


class TestCheckpointChaos:
    def _save_one(self, exe, main, ckpt, epoch):
        return pt.io.save_checkpoint(
            exe, ckpt, trainer_args={"epoch_id": epoch, "step_id": 0},
            main_program=main)

    def _setup(self, tmp_path):
        main, startup, loss = _linreg()
        exe = pt.Executor()
        exe.run(startup)
        return main, exe, str(tmp_path / "ckpt")

    def test_crash_mid_save_leaves_previous_serial_loadable(
            self, tmp_path, monkeypatch):
        main, exe, ckpt = self._setup(tmp_path)
        assert self._save_one(exe, main, ckpt, epoch=0) == 0
        _arm(monkeypatch, "io_crash@2")  # second var write of the next save
        with pytest.raises(FaultInjected):
            self._save_one(exe, main, ckpt, epoch=1)
        # the torn attempt is not committed...
        assert not os.path.exists(
            os.path.join(ckpt, "checkpoint_1", "_SUCCESS"))
        _arm(monkeypatch, "")  # disarm
        assert pt.io.get_latest_checkpoint_serial(ckpt) == 0
        args = pt.io.load_checkpoint(exe, ckpt, main_program=main)
        assert args["epoch_id"] == 0

    def test_torn_write_never_yields_verifiable_success(
            self, tmp_path, monkeypatch):
        main, exe, ckpt = self._setup(tmp_path)
        assert self._save_one(exe, main, ckpt, epoch=0) == 0
        _arm(monkeypatch, "io_write_truncate@1")
        with pytest.raises(FaultInjected):
            self._save_one(exe, main, ckpt, epoch=1)
        _arm(monkeypatch, "")
        # truncated bytes DID reach a final filename — but no _SUCCESS,
        # so the serial is invisible and the previous one loads
        assert not os.path.exists(
            os.path.join(ckpt, "checkpoint_1", "_SUCCESS"))
        assert pt.io.get_latest_checkpoint_serial(ckpt) == 0
        # and the next save clears the leftovers, reusing the serial
        assert self._save_one(exe, main, ckpt, epoch=2) == 1
        assert pt.io.load_checkpoint(
            exe, ckpt, main_program=main)["epoch_id"] == 2

    def test_commit_crash_before_success_marker(self, tmp_path, monkeypatch):
        main, exe, ckpt = self._setup(tmp_path)
        assert self._save_one(exe, main, ckpt, epoch=0) == 0
        _arm(monkeypatch, "commit_crash@1")
        with pytest.raises(FaultInjected):
            self._save_one(exe, main, ckpt, epoch=1)
        _arm(monkeypatch, "")
        cur = os.path.join(ckpt, "checkpoint_1")
        assert os.path.exists(os.path.join(cur, "manifest.json"))
        assert not os.path.exists(os.path.join(cur, "_SUCCESS"))
        assert pt.io.get_latest_checkpoint_serial(ckpt) == 0

    def test_corrupt_committed_serial_quarantined_with_fallback(
            self, tmp_path):
        main, exe, ckpt = self._setup(tmp_path)
        self._save_one(exe, main, ckpt, epoch=0)
        self._save_one(exe, main, ckpt, epoch=1)
        # bit-rot one committed .npy of the NEWEST serial (size preserved)
        cur = os.path.join(ckpt, "checkpoint_1")
        victim = os.path.join(cur, sorted(
            n for n in os.listdir(cur) if n.endswith(".npy"))[0])
        blob = bytearray(open(victim, "rb").read())
        blob[-1] ^= 0xFF
        with open(victim, "wb") as f:
            f.write(blob)
        # auto-selection: warn, quarantine, fall back to serial 0
        with pytest.warns(UserWarning, match="quarantined"):
            args = pt.io.load_checkpoint(exe, ckpt, main_program=main)
        assert args["epoch_id"] == 0
        assert not os.path.isdir(cur)
        assert os.path.isdir(cur + ".corrupt")
        # an EXPLICIT serial never silently falls back
        self._save_one(exe, main, ckpt, epoch=2)  # serial 1 again
        victim2 = os.path.join(ckpt, "checkpoint_1", "manifest.json")
        with open(victim2, "a") as f:
            f.write(" ")
        with pytest.raises(pt.io.CheckpointCorruptError):
            pt.io.load_checkpoint(exe, ckpt, serial=1, main_program=main)

    def test_legacy_checkpoint_without_manifest_still_loads(self, tmp_path):
        main, exe, ckpt = self._setup(tmp_path)
        self._save_one(exe, main, ckpt, epoch=0)
        cur = os.path.join(ckpt, "checkpoint_0")
        os.remove(os.path.join(cur, "manifest.json"))
        with open(os.path.join(cur, "_SUCCESS"), "w") as f:
            f.write("")  # pre-manifest marker: empty
        assert pt.io.get_latest_checkpoint_serial(ckpt) == 0
        assert pt.io.load_checkpoint(
            exe, ckpt, main_program=main)["epoch_id"] == 0

    def test_success_marker_binds_the_manifest(self, tmp_path):
        main, exe, ckpt = self._setup(tmp_path)
        self._save_one(exe, main, ckpt, epoch=0)
        cur = os.path.join(ckpt, "checkpoint_0")
        marker = json.loads(open(os.path.join(cur, "_SUCCESS")).read())
        assert {"manifest_size", "manifest_crc32"} <= set(marker)
        # a rewritten manifest (hiding data tampering) breaks the binding
        manifest.write_manifest(cur)
        with open(os.path.join(cur, "manifest.json"), "a") as f:
            f.write("\n")
        status, problems = manifest.verify_dir(cur)
        assert status == "corrupt" and "binding" in problems[0]


# ---------------------------------------------------------------------------
# trainer: step_crash + resume parity, preemption
# ---------------------------------------------------------------------------

N_STEPS = 12
STEP_INTERVAL = 4


def _det_reader():
    rs = np.random.RandomState(1234 + CHAOS_SEED)
    data = [(rs.randn(4).astype(np.float32),
             rs.randn(1).astype(np.float32)) for _ in range(N_STEPS * 4)]

    def reader():
        yield from data
    return reader


def _make_trainer(ckpt_dir):
    pt.core.program.reset_unique_names()

    def train_func():
        x = layers.data("x", [4])
        y = layers.data("y", [1])
        pred = layers.fc(x, size=1)
        return [layers.mean(layers.square_error_cost(pred, y))]

    cfg = pt.CheckpointConfig(ckpt_dir, step_interval=STEP_INTERVAL)
    return pt.Trainer(train_func, lambda: pt.optimizer.SGDOptimizer(0.05),
                      checkpoint_config=cfg)


def _final_params(trainer):
    with pt.scope_guard(trainer.scope):
        return {v.name: np.array(trainer.scope.find_var(v.name))
                for v in trainer.train_program.global_block.all_parameters()}


def _run(trainer, reader, steps_seen=None):
    def handler(event):
        if steps_seen is not None and isinstance(event, pt.EndStepEvent):
            steps_seen.append((event.epoch, event.step))
    trainer.train(num_epochs=1, event_handler=handler,
                  reader=pt.reader.batch(reader, 4))


class TestCrashResumeParity:
    def test_step_crash_resume_is_bit_exact(self, tmp_path, monkeypatch):
        raw = _det_reader()
        # A: uninterrupted
        a = _make_trainer(str(tmp_path / "a"))
        _run(a, raw)
        want = _final_params(a)

        # B: killed mid-epoch by an injected crash before step index 6
        b = _make_trainer(str(tmp_path / "b"))
        _arm(monkeypatch, "step_crash@7")
        with pytest.raises(FaultInjected):
            _run(b, raw)
        _arm(monkeypatch, "")
        # steps 0..3 were checkpointed (interval 4): resume point = step 4
        assert pt.io.load_checkpoint(
            None, str(tmp_path / "b"),
            main_program=b.train_program, scope=pt.Scope()) is not None

        # C: fresh process resumes from B's checkpoint
        steps = []
        c = _make_trainer(str(tmp_path / "b"))
        assert c.checkpoint_cfg.step_id == STEP_INTERVAL
        _run(c, raw, steps_seen=steps)
        # replay starts at the checkpointed step, not at 0
        assert steps[0] == (0, STEP_INTERVAL)
        assert steps[-1] == (0, N_STEPS - 1)

        got = _final_params(c)
        assert set(got) == set(want)
        for name in want:
            np.testing.assert_array_equal(
                got[name], want[name],
                err_msg=f"{name}: resumed params diverge from "
                        "uninterrupted run")

    def test_preemption_checkpoints_at_step_boundary_and_resumes(
            self, tmp_path):
        raw = _det_reader()
        a = _make_trainer(str(tmp_path / "a"))
        _run(a, raw)
        want = _final_params(a)

        kill_after = 5

        def handler(event):
            if isinstance(event, pt.EndStepEvent) \
                    and event.step == kill_after:
                os.kill(os.getpid(), signal.SIGTERM)

        b = _make_trainer(str(tmp_path / "b"))
        b.train(num_epochs=1, event_handler=handler,
                reader=pt.reader.batch(raw, 4))
        assert b.preempted
        # the preemption checkpoint records the NEXT step
        args = pt.io.load_checkpoint(
            None, str(tmp_path / "b"), main_program=b.train_program,
            scope=pt.Scope())
        assert (args["epoch_id"], args["step_id"]) == (0, kill_after + 1)

        steps = []
        c = _make_trainer(str(tmp_path / "b"))
        _run(c, raw, steps_seen=steps)
        assert steps[0] == (0, kill_after + 1)
        got = _final_params(c)
        for name in want:
            np.testing.assert_array_equal(got[name], want[name])

    def test_reader_retry_through_trainer(self, tmp_path, monkeypatch):
        raw = _det_reader()
        a = _make_trainer(str(tmp_path / "a"))
        _run(a, raw)
        want = _final_params(a)

        # one injected reader fault mid-epoch: bounded retries restart
        # and fast-forward the reader; training output is unchanged
        _arm(monkeypatch, "reader_raise@5")
        b = _make_trainer(str(tmp_path / "b"))

        def handler(event):
            pass
        b.train(num_epochs=1, event_handler=handler,
                reader=pt.reader.batch(raw, 4), reader_retry=2)
        _arm(monkeypatch, "")
        got = _final_params(b)
        for name in want:
            np.testing.assert_array_equal(got[name], want[name])

    def test_reader_retry_exhaustion_raises_original(
            self, tmp_path, monkeypatch):
        _arm(monkeypatch, "reader_raise@*")
        b = _make_trainer(str(tmp_path / "b"))
        with pytest.raises(FaultInjected):
            b.train(num_epochs=1, event_handler=lambda e: None,
                    reader=pt.reader.batch(_det_reader(), 4),
                    reader_retry=3)

    def test_sigint_without_checkpoint_config_raises_keyboardinterrupt(
            self):
        # a clean return here would look like a COMPLETED run and let
        # caller code ship a half-trained model
        pt.core.program.reset_unique_names()

        def train_func():
            x = layers.data("x", [4])
            y = layers.data("y", [1])
            pred = layers.fc(x, size=1)
            return [layers.mean(layers.square_error_cost(pred, y))]

        tr = pt.Trainer(train_func,
                        lambda: pt.optimizer.SGDOptimizer(0.05))

        def handler(event):
            if isinstance(event, pt.EndStepEvent) and event.step == 2:
                os.kill(os.getpid(), signal.SIGINT)

        with pytest.raises(KeyboardInterrupt):
            tr.train(num_epochs=1, event_handler=handler,
                     reader=pt.reader.batch(_det_reader(), 4))
