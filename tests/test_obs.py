"""Unified observability plane (paddle_tpu/obs/): structured tracing,
the consolidated metrics registry + Prometheus exposition, and the
predicted-vs-measured drift monitor.

Test planes:
  * span core — nesting/parent ids, thread-local correctness (spans on
    serving dispatcher threads and map_batches workers never interleave
    into the wrong trace), bounded ring buffer, near-zero disabled path;
  * drift monitor — EWMA math, one-shot step recorders, LRU bounds;
  * exposition — conformance of the one renderer over every family
    (pt_serve_/pt_decode_/pt_data_/pt_train_/pt_model_), label escaping,
    no duplicate series;
  * end-to-end — a 3-step Trainer run and one served HTTP request each
    produce a Chrome-trace JSON where executor phases, pipeline stages,
    and the request's queue→device→scatter spans share one timeline and
    parent ids; pt_train_* and pt_model_drift_ratio ride the same
    /v1/metrics?format=prometheus scrape as the existing families.
"""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu import io as pio
from paddle_tpu.obs import drift as obs_drift
from paddle_tpu.obs import trace
from paddle_tpu.obs.metrics import (REGISTRY, MetricsRegistry,
                                    TrainMetrics, render_prometheus,
                                    validate_exposition)
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.admission import AdmissionController
from paddle_tpu.serving.batcher import MicroBatcher
from paddle_tpu.serving.metrics import ModelMetrics, ServingMetrics


@pytest.fixture(autouse=True)
def clean_trace(monkeypatch):
    """Fresh ring buffer per test; PT_TRACE governed via monkeypatch."""
    monkeypatch.delenv("PT_TRACE", raising=False)
    monkeypatch.delenv("PT_TRACE_BUF", raising=False)
    monkeypatch.delenv("PT_TRACE_DIR", raising=False)
    trace.reset()
    yield
    trace.reset()


def _arm(monkeypatch):
    monkeypatch.setenv("PT_TRACE", "1")


# ---------------------------------------------------------------------------
# span core
# ---------------------------------------------------------------------------

class TestSpanCore:
    def test_nesting_parent_and_trace_ids(self, monkeypatch):
        _arm(monkeypatch)
        with trace.span("outer", cat="t", epoch=3):
            with trace.span("inner", cat="t"):
                pass
            trace.instant("mark", cat="t", k=1)
        evs = trace.events()
        by_name = {e["name"]: e for e in evs}
        outer, inner = by_name["outer"], by_name["inner"]
        assert inner["args"]["parent_id"] == outer["args"]["span_id"]
        assert inner["args"]["trace_id"] == outer["args"]["trace_id"]
        assert by_name["mark"]["args"]["parent_id"] \
            == outer["args"]["span_id"]
        assert outer["args"]["epoch"] == 3
        assert outer["ph"] == "X" and outer["dur"] >= inner["dur"]
        # events share one monotonic timeline
        assert inner["ts"] >= outer["ts"]

    def test_disabled_emits_nothing_and_returns_noop(self):
        assert trace.span("x") is trace.NOOP
        with trace.span("x", cat="t", a=1):
            trace.instant("y")
        trace.complete("z", 0.5)
        assert trace.events() == []
        assert trace.current_context() is None

    def test_complete_emits_backdated_interval(self, monkeypatch):
        _arm(monkeypatch)
        trace.complete("measured", 0.25, cat="t")
        (ev,) = trace.events()
        assert ev["dur"] == pytest.approx(0.25e6, rel=0.01)

    def test_ring_buffer_bounded(self, monkeypatch):
        _arm(monkeypatch)
        monkeypatch.setenv("PT_TRACE_BUF", "64")
        trace.reset()
        for i in range(500):
            trace.instant("e", cat="t", i=i)
        evs = trace.events()
        assert len(evs) == 64
        # the NEWEST window survives
        assert [e["args"]["i"] for e in evs] == list(range(436, 500))

    def test_drain_empties_the_ring(self, monkeypatch):
        _arm(monkeypatch)
        trace.instant("a")
        assert len(trace.drain()) == 1
        assert trace.events() == []

    def test_use_context_adopts_parent_across_threads(self, monkeypatch):
        _arm(monkeypatch)
        with trace.span("root", cat="t") as root:
            ctx = trace.current_context()
        done = threading.Event()

        def worker():
            with trace.use_context(ctx):
                with trace.span("work", cat="t"):
                    pass
            done.set()

        threading.Thread(target=worker, daemon=True).start()
        assert done.wait(5.0)
        work = next(e for e in trace.events() if e["name"] == "work")
        assert work["args"]["trace_id"] == root.trace_id
        assert work["args"]["parent_id"] == root.span_id

    def test_threads_never_inherit_each_others_stack(self, monkeypatch):
        """Two threads, each under its OWN root: every child span must
        land in its own thread's trace — never the sibling's."""
        _arm(monkeypatch)
        roots = {}
        barrier = threading.Barrier(2, timeout=10)

        def worker(tag):
            with trace.span(f"root-{tag}", cat="t") as r:
                roots[tag] = r.trace_id
                barrier.wait()          # both stacks open concurrently
                for i in range(20):
                    with trace.span(f"child-{tag}", cat="t", i=i):
                        pass

        ts = [threading.Thread(target=worker, args=(t,), daemon=True)
              for t in ("a", "b")]
        for t in ts:
            t.start()
        for t in ts:
            t.join(10.0)
        for e in trace.events():
            if e["name"].startswith("child-"):
                tag = e["name"].split("-", 1)[1]
                assert e["args"]["trace_id"] == roots[tag], e

    def test_active_stack_snapshot(self, monkeypatch):
        _arm(monkeypatch)
        with trace.span("a", cat="train", epoch=1):
            with trace.span("b", cat="exec"):
                stack = trace.active_stack()
        assert [s["name"] for s in stack] == ["a", "b"]
        assert stack[0]["attrs"] == {"epoch": 1}
        assert trace.active_stack() == []

    def test_disabled_path_budget(self):
        """The documented <= 1% disabled-path budget, pinned as an
        absolute per-call bound (generous for CI co-tenancy): a
        disabled span must cost microseconds, not milliseconds."""
        n = 50_000
        with trace.span("warm"):
            pass
        t0 = time.perf_counter()
        for _ in range(n):
            with trace.span("off", cat="t", k=1):
                pass
        per_call = (time.perf_counter() - t0) / n
        assert per_call < 20e-6, f"disabled span costs {per_call * 1e6:.2f}us"


# ---------------------------------------------------------------------------
# phase records: one timing source, three views
# ---------------------------------------------------------------------------

class _FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: logs (event, name,
    thread) so a test can read the profiler's view without a session."""

    log: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        type(self).log.append(("enter", self.name, threading.get_ident()))
        return self

    def __exit__(self, *exc):
        type(self).log.append(("exit", self.name, threading.get_ident()))
        return False


@pytest.fixture
def fake_annotations(monkeypatch):
    monkeypatch.setattr(_FakeAnnotation, "log", [])
    monkeypatch.setattr(trace, "_annotation_factory", _FakeAnnotation)
    return _FakeAnnotation.log


class TestPhaseRecords:
    def test_phase_lands_in_the_ring_without_being_asked(self):
        from paddle_tpu.core.async_fetch import PhaseTimer
        timer = PhaseTimer()
        t0 = time.perf_counter()
        with timer.span("dispatch"):
            time.sleep(0.002)
        timer.add("fetch", 0.25)
        t1 = time.perf_counter()
        recs = trace.phase_records()
        assert [(c, n) for c, n, _, _ in recs] \
            == [("exec", "dispatch"), ("exec", "fetch")]
        (_, _, end_a, dur_a), (_, _, end_b, dur_b) = recs
        assert t0 <= end_a <= end_b <= t1        # perf_counter, the
        assert 0.002 <= dur_a < 0.5 and dur_b == 0.25   # ring's clock
        # the cumulative view read the same numbers
        snap = timer.snapshot()
        assert snap["dispatch_s"] == pytest.approx(dur_a, abs=1e-6)
        assert snap["fetch_s"] == 0.25
        # rendered to Chrome events only when the ring is read
        evs = trace.events()
        assert [(e["cat"], e["name"], e["ph"]) for e in evs] \
            == [("exec", "dispatch", "X"), ("exec", "fetch", "X")]
        assert evs[0]["dur"] == pytest.approx(dur_a * 1e6, abs=0.1)
        assert evs[0]["args"] == {}              # no ids: PT_TRACE is off
        assert evs[0]["tid"] == threading.get_ident()
        assert evs[1]["ts"] + evs[1]["dur"] >= evs[0]["ts"]
        json.dumps(evs)
        # and PT_TRACE keeps governing everything else
        assert trace.span("x") is trace.NOOP
        trace.instant("y")
        trace.complete("z", 0.1)
        assert len(trace.events()) == 2
        assert len(trace.drain()) == 2 and trace.phase_records() == []

    def test_phase_records_share_the_bounded_ring(self, monkeypatch):
        from paddle_tpu.core.async_fetch import PhaseTimer
        monkeypatch.setenv("PT_TRACE_BUF", "32")
        trace.reset()
        timer = PhaseTimer()
        for _ in range(100):
            timer.add("dispatch", 0.001)
        assert len(trace.phase_records()) == 32
        assert timer.snapshot()["dispatch_s"] == pytest.approx(0.1)

    def test_armed_phase_spans_nest_and_carry_attrs(self, monkeypatch):
        from paddle_tpu.core.async_fetch import PhaseTimer
        _arm(monkeypatch)
        timer = PhaseTimer()
        with trace.span("step", cat="train") as step:
            with timer.span("device", k=1) as sp:
                sp.annotate(n=2)
                timer.add("fetch", 0.001)       # a hand-timed site
        by_name = {e["name"]: e for e in trace.events()}
        dev, fetch = by_name["device"], by_name["fetch"]
        assert dev["args"]["parent_id"] == step.span_id
        assert dev["args"]["trace_id"] == step.trace_id
        assert dev["args"]["k"] == 1 and dev["args"]["n"] == 2
        assert fetch["args"]["parent_id"] == dev["args"]["span_id"]
        assert trace.active_stack() == []
        assert len(trace.events()) == 3          # nothing doubled

    def test_cancelled_span_leaves_no_record(self, monkeypatch,
                                             fake_annotations):
        from paddle_tpu.core.async_fetch import PhaseTimer
        timer = PhaseTimer()
        for armed in (False, True):
            if armed:
                _arm(monkeypatch)
            with timer.span("device") as sp:
                sp.cancel()
            assert trace.events() == [] and trace.active_stack() == []
            assert timer.snapshot()["device_s"] == 0.0
        assert [e for e, _, _ in fake_annotations] \
            == ["enter", "exit"] * 2

    def test_span_is_a_profiler_annotation(self, fake_annotations):
        """The profiler's view, by substituting the annotation factory
        (never a real profiler session in tier-1): every PhaseTimer
        span is `program/<cat>/<phase>` while it is open, nested as the
        `with` blocks are; a hand-timed add() is not one."""
        from paddle_tpu.core.async_fetch import PhaseTimer
        from paddle_tpu.serving.metrics import ServingPhaseTimer
        exe, serve = PhaseTimer(), ServingPhaseTimer()
        with exe.span("device"):
            with serve.span("pad"):
                pass
            exe.add("fetch", 0.001)
        assert [(e, n) for e, n, _ in fake_annotations] == [
            ("enter", "program/exec/device"),
            ("enter", "program/serve/pad"),
            ("exit", "program/serve/pad"),
            ("exit", "program/exec/device")]

    def test_real_annotation_factory_is_jax_profilers(self):
        import jax
        trace._annotation_factory = None
        try:
            with trace.annotation("program/test/x") as a:
                assert isinstance(a, jax.profiler.TraceAnnotation)
            assert trace._annotation_factory \
                is jax.profiler.TraceAnnotation
        finally:
            trace._annotation_factory = None

    def test_phase_record_budget(self):
        """What PT_TRACE off still pays: one record and one (no-op)
        profiler annotation per phase, pinned at a loose 10 us (the
        decode step makes about eight of them in 40 ms). On the wall's
        clock, the stall sentinel waking meanwhile. The best of forty
        short batches: beside twelve busy processes on eight cores the
        best of five batches of 4,000 read 7-8 us for a span that
        costs 3.7, the best of forty of 500 read 3.5-3.8 (PR 54)."""
        from paddle_tpu.core.async_fetch import PhaseTimer
        timer = PhaseTimer()
        n = 500
        batches = []
        for _ in range(41):     # the first warms up; the best of the
            t0 = time.perf_counter()   # rest is the cost, whatever
            for _ in range(n):         # else the machine was doing
                with timer.span("dispatch"):
                    pass
            batches.append((time.perf_counter() - t0) / n)
        per_phase = min(batches[1:])
        assert per_phase < 10e-6, f"{per_phase * 1e6:.2f} us a phase"

    def test_backend_compiles_are_counted_and_on_the_timeline(self):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.obs.metrics import XLA_COMPILES, global_snapshot
        before = XLA_COMPILES.count

        def fresh(x):                 # a new function: a new executable
            return x * 3.0 + 1.0

        jax.jit(fresh)(jnp.ones(7)).block_until_ready()
        assert XLA_COMPILES.count > before
        compiles = [r for r in trace.phase_records()
                    if r[:2] == ("xla", "compile")]
        assert compiles and all(s > 0 for _, _, _, s in compiles)
        text = render_prometheus(global_snapshot())
        assert validate_exposition(text) == []
        assert f"pt_xla_compiles_total{{}} {XLA_COMPILES.count}" in text


def _load_reader(name):
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "readers",
        name + ".py")
    spec = importlib.util.spec_from_file_location("_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_phase_ms():
    return _load_reader("phase_ms")


class TestPhaseMsReader:
    """benchmark/readers/phase_ms.py on a synthetic ring."""

    @pytest.fixture
    def window(self, monkeypatch):
        """A run whose process started at T_START = 100 s on the ring's
        clock, set up for 10 s and measured for 5: the window is
        [110, 115]."""
        import sys
        monkeypatch.setattr(sys.modules["__main__"], "T_START", 100.0,
                            raising=False)
        return {"obs": {"setup_s": 10.0, "window_s": 5.0,
                        "decode_steps": 4, "prefills": 2}}

    def _fill(self):
        for t_end, name, s in [(105.0, "step_wait", 9.0),    # warm-up
                               (110.5, "step_wait", 0.010),
                               (111.0, "step_fetch", 0.004),
                               (112.0, "step_wait", 0.030),
                               (113.0, "step_prep", 0.001),
                               (114.0, "step_emit", 0.003),
                               (114.5, "seed_kv", 0.5),
                               (116.0, "step_wait", 7.0)]:   # traced
            trace.phase("decode", name, s, t_end=t_end)
        trace.phase("exec", "step_wait", 1.0, t_end=112.0)   # other cat

    def test_sums_what_ended_inside_the_window(self, window):
        read = _load_phase_ms().read
        self._fill()
        assert read(window, ["step_wait"], "decode_steps") \
            == pytest.approx(10.0)
        assert read(window, ["step_prep", "step_emit"], "decode_steps") \
            == pytest.approx(1.0)
        assert read(window, ["seed_kv"], "prefills") \
            == pytest.approx(250.0)
        assert read(window, ["step_wait"], "decode_steps", cat="exec") \
            == pytest.approx(250.0)
        assert read(window, ["prefill_pad"], "prefills") == 0.0

    def test_none_on_an_overflowed_ring(self, window):
        """The ring's oldest phase record is younger than the window's
        opening: part of the window may be gone, so no number."""
        read = _load_phase_ms().read
        trace.reset(buf=4)
        self._fill()
        assert trace.phase_records()[0][2] > 110.0
        assert read(window, ["step_wait"], "decode_steps") is None

    def test_none_without_a_count_or_a_window(self, window, monkeypatch):
        import sys
        read = _load_phase_ms().read
        self._fill()
        assert read(window, ["step_wait"], "evictions") is None
        window["obs"]["decode_steps"] = 0
        assert read(window, ["step_wait"], "decode_steps") is None
        window["obs"]["decode_steps"] = 4
        del window["obs"]["window_s"]
        assert read(window, ["step_wait"], "decode_steps") is None
        window["obs"]["window_s"] = 5.0
        monkeypatch.delattr(sys.modules["__main__"], "T_START")
        assert read(window, ["step_wait"], "decode_steps") is None

    def test_none_when_the_program_has_no_phase_records(self, window,
                                                        monkeypatch):
        """The parent commit's `obs.trace`: the metric is left out."""
        read = _load_phase_ms().read
        self._fill()
        monkeypatch.delattr(trace, "phase_records")
        assert read(window, ["step_wait"], "decode_steps") is None


class TestPhaseOverlapReader:
    """benchmark/readers/phase_overlap.py on a synthetic ring: two
    decode steps inside the window [110, 115], the device idle from
    each step's wait to the next launch, one admission between them."""

    PARTS = (["step_dispatch"], ["step_fetch"],
             ["step_emit", "step_prep"], ["admit"], [])

    @pytest.fixture
    def window(self, monkeypatch):
        import sys
        monkeypatch.setattr(sys.modules["__main__"], "T_START", 100.0,
                            raising=False)
        return {"obs": {"setup_s": 10.0, "window_s": 5.0,
                        "decode_steps": 2}}

    def _fill(self):
        ms = 1e-3
        for t_end, name, s in [
                (105.0, "device_idle", 2.0),              # warm-up
                (105.0, "step_wait", 1.0),
                # step 1: launched at 111 + 3 ms, waited to 111.020
                (111.0 + 3 * ms, "device_idle", 8 * ms),
                (111.0 + 4 * ms, "step_dispatch", 2 * ms),
                (111.020, "step_wait", 16 * ms),
                (111.021, "step_fetch", 1 * ms),
                (111.023, "step_emit", 2 * ms),
                # 111.023 .. 111.024: the loop, in no phase
                (111.030, "prefill_device", 3 * ms),      # inside admit
                (111.030, "device_idle", 10 * ms),        # its launch
                (111.034, "admit", 10 * ms),
                # the admission's wait is on an older dispatch: busy
                # until step 2's wait returns at 111.060
                (111.036, "step_prep", 1 * ms),
                (111.038, "step_dispatch", 2 * ms),
                (111.060, "step_wait", 22 * ms),
                (111.061, "step_fetch", 1 * ms),
                (111.0625, "step_emit", 1.5 * ms),
                (111.064, "step_prep", 1 * ms),
                # launched 1 ms into this dispatch, which closes after
                (111.065, "device_idle", 5 * ms),
                (111.066, "step_dispatch", 2 * ms),
                (116.0, "device_idle", 0.5),              # traced
                (116.0, "step_dispatch", 0.6)]:
            trace.phase("decode", name, s, t_end=t_end)
        trace.phase("exec", "device_idle", 1.0, t_end=112.0)  # other cat

    def _read(self, window, under=None):
        read = _load_reader("phase_overlap").read
        kw = {} if under is None else {"under": under}
        return read(window, ["device_idle"], "decode_steps", scale=1000.0,
                    **kw)

    def test_the_whole(self, window):
        self._fill()
        read = _load_reader("phase_overlap").read
        # 8 + 10 + 5 ms ended inside the window, of 5 s
        assert read(window, ["device_idle"], "window_s", scale=100.0) \
            == pytest.approx(100.0 * 0.023 / 5.0)
        assert self._read(window) == pytest.approx(11.5)

    def test_under_one_phase_and_under_several(self, window):
        self._fill()
        # the first interval [110.995, 111.003] meets step_dispatch
        # [111.002, 111.004] for 1 ms, the last [111.060, 111.065] the
        # dispatch [111.064, 111.066] for 1 ms: 2 ms over 2 steps
        assert self._read(window, ["step_dispatch"]) \
            == pytest.approx(1.0)
        assert self._read(window, ["step_fetch"]) == pytest.approx(1.0)
        # emit [111.021, 111.023] whole; emit [111.061, 111.0625] and
        # prep [111.063, 111.064] of the last
        assert self._read(window, ["step_emit", "step_prep"]) \
            == pytest.approx((2 + 1.5 + 1) / 2)
        # admit [111.024, 111.034] holds the interval up to the launch
        assert self._read(window, ["admit"]) == pytest.approx(6 / 2)
        assert self._read(window, ["prefill_device"]) \
            == pytest.approx(3 / 2)

    def test_under_none_and_the_parts_add_to_the_whole(self, window):
        self._fill()
        # 7 ms before the first dispatch opened, 1 ms of loop before the
        # admission, 0.5 ms between the last emit and prep
        assert self._read(window, []) == pytest.approx((7 + 1 + 0.5) / 2)
        assert sum(self._read(window, u) for u in self.PARTS) \
            == pytest.approx(self._read(window))

    def test_none_on_a_dropped_ring_or_without_the_phase(self, window,
                                                         monkeypatch):
        import sys
        read = _load_reader("phase_overlap").read
        trace.phase("decode", "step_wait", 0.01, t_end=105.0)
        trace.phase("decode", "step_wait", 0.01, t_end=112.0)
        assert _load_phase_ms().read(window, ["step_wait"],
                                     "decode_steps") == pytest.approx(5.0)
        # the parent: phase records, none of them `device_idle`
        assert self._read(window) is None
        assert self._read(window, []) is None
        trace.reset()
        self._fill()
        assert read(window, ["device_idle"], "evictions") is None
        monkeypatch.delattr(sys.modules["__main__"], "T_START")
        assert self._read(window) is None
        monkeypatch.setattr(sys.modules["__main__"], "T_START", 100.0,
                            raising=False)
        assert self._read(window) is not None
        monkeypatch.delattr(trace, "phase_records")
        assert self._read(window) is None
        monkeypatch.undo()
        monkeypatch.setattr(sys.modules["__main__"], "T_START", 100.0,
                            raising=False)
        trace.reset(buf=6)              # the window's start has gone
        self._fill()
        assert trace.phase_records()[0][2] > 110.0
        assert self._read(window) is None
        assert self._read(window, ["admit"]) is None

    def test_overlapping_records_are_counted_once(self):
        covered = _load_reader("phase_overlap")._covered
        got = covered([(0.0, 4.0), (1.0, 2.0), (3.0, 5.0), (7.0, 8.0)],
                      np.array([-1.0, 4.5, 6.0, 2.5]),
                      np.array([1.5, 7.5, 6.5, 9.0]))
        assert got == pytest.approx([1.5, 1.0, 0.0, 3.5])
        assert covered([], np.array([0.0]), np.array([1.0])) \
            == pytest.approx([0.0])


# ---------------------------------------------------------------------------
# cross-thread correctness under the real concurrency sources
# ---------------------------------------------------------------------------

class _StubModel:
    batch_size = 4

    def bucket_of(self, feeds):
        return None

    def execute_batch(self, bucket, examples, timer=None):
        if timer is not None:
            timer.add("pad", 0.001)
            timer.add("device", 0.002)
            timer.add("scatter", 0.0005)
        return ([{"y": np.asarray(e["x"]) * 2.0} for e in examples],
                {"pad": 0.001, "device": 0.002, "scatter": 0.0005})


class TestServingTraceThreading:
    def test_request_spans_follow_their_submitters(self, monkeypatch):
        """Requests submitted from different threads (each under its
        own ingress-like root span) get queue spans parented under
        THEIR root — the dispatcher thread never crosses them."""
        _arm(monkeypatch)
        model = _StubModel()
        batcher = MicroBatcher(
            model, max_wait_ms=1.0,
            admission=AdmissionController(queue_depth=64,
                                          max_batch_size=4),
            metrics=ModelMetrics("stub"), name="stub")
        roots = {}
        futs = {}

        def submitter(tag):
            with trace.span(f"ingress-{tag}", cat="serve") as r:
                roots[tag] = r.trace_id
                futs[tag] = batcher.submit({"x": np.float32(1)})

        try:
            threads = [threading.Thread(target=submitter, args=(t,),
                                        daemon=True)
                       for t in ("a", "b", "c")]
            for t in threads:
                t.start()
            for t in threads:
                t.join(10.0)
            for f in futs.values():
                f.result(timeout=10.0)
        finally:
            batcher.close(drain=True, timeout=10.0)
        queue_spans = [e for e in trace.events()
                       if e["name"] == "queue" and e["cat"] == "serve"]
        assert len(queue_spans) == 3
        assert ({e["args"]["trace_id"] for e in queue_spans}
                == set(roots.values()))
        rids = [e["args"]["rid"] for e in queue_spans]
        assert len(set(rids)) == 3
        # batch-level spans emitted from the dispatcher thread exist
        names = {e["name"] for e in trace.events()}
        assert "batch" in names and "device" in names

    def test_map_batches_workers_emit_decode_spans(self, monkeypatch):
        _arm(monkeypatch)
        from paddle_tpu.data.pipeline import Dataset
        ds = (Dataset.from_samples([np.full((2,), i, np.float32)
                                    for i in range(8)])
              .map_batches(lambda b: b * 2.0, workers=3)
              .named("obs-mb"))
        out = list(ds())
        assert len(out) == 8
        decode = [e for e in trace.events() if e["name"] == "decode"]
        assert len(decode) == 8
        # every span carries the batch cursor and none parented under a
        # foreign trace (worker threads start with an empty stack)
        assert sorted(e["args"]["cursor"] for e in decode) \
            == list(range(8))
        assert all("parent_id" not in e["args"] for e in decode)
        assert {e["args"]["pipeline"] for e in decode} == {"obs-mb"}

    def test_long_pipeline_run_stays_bounded(self, monkeypatch):
        _arm(monkeypatch)
        monkeypatch.setenv("PT_TRACE_BUF", "128")
        trace.reset()
        from paddle_tpu.data.pipeline import Dataset
        ds = (Dataset.from_samples([np.zeros(2, np.float32)] * 300)
              .map_batches(lambda b: b + 1.0, workers=2))
        assert len(list(ds())) == 300
        assert len(trace.events()) <= 128


# ---------------------------------------------------------------------------
# drift monitor
# ---------------------------------------------------------------------------

class TestDriftMonitor:
    def test_ewma_math_is_exact(self):
        reg = MetricsRegistry()
        mon = obs_drift.DriftMonitor(registry=reg)
        e = mon.entry("fp-ewma")
        e.set_prediction(2.0, "compute", predicted_mfu=0.5)
        e.observe_step(100.0)
        assert e.ewma_ms == 100.0                    # first sample seeds
        e.observe_step(50.0)
        assert e.ewma_ms == pytest.approx(0.2 * 50 + 0.8 * 100)
        e.observe_step(10.0)
        assert e.ewma_ms == pytest.approx(0.2 * 10 + 0.8 * 90)
        snap = e.snapshot()
        assert snap["measured_step_ms"] == pytest.approx(74.0)
        assert snap["drift_ratio"] == pytest.approx(37.0)
        assert snap["bound"] == "compute" and snap["steps"] == 3
        # and the entry is live on the injected registry
        assert "fp-ewma"[:12] in reg.snapshot()["model"]

    def test_step_recorder_is_one_shot(self):
        rec1 = obs_drift.step_recorder("fp-oneshot", n_steps=4)
        rec1()                               # first settle seeds only
        rec2 = obs_drift.step_recorder("fp-oneshot", n_steps=4)
        rec2()
        rec2()
        rec2()                               # deduped: one fold total
        e = obs_drift.MONITOR.entry("fp-oneshot")
        assert e.steps == 1

    def test_settle_to_settle_measurement(self, monkeypatch):
        """Measured step time is the gap between consecutive settles
        over the steps between them — a handle materialized LATE (the
        guard health handle drained log_every windows later) cannot
        inflate the series, and stale settles never fold backwards."""
        e = obs_drift.DriftMonitor(registry=MetricsRegistry()) \
            .entry("fp-s2s")
        t = [100.0]
        monkeypatch.setattr(obs_drift.time, "perf_counter",
                            lambda: t[0])
        c1 = e.begin_run(4)
        e.settle(c1)                         # seeds at t=100, cum=4
        assert e.steps == 0 and e.ewma_ms is None
        t[0] = 100.2
        c2 = e.begin_run(4)
        e.settle(c2)                         # (200 ms) / 4 steps
        assert e.ewma_ms == pytest.approx(50.0)
        t[0] = 105.0
        e.settle(c1)                         # stale: never folds back
        assert e.steps == 1
        # a compile resets the baseline: the next settle seeds, the
        # compile's wall time never folds
        e.reset_baseline()
        t[0] = 200.0
        c3 = e.begin_run(2)
        e.settle(c3)
        assert e.steps == 1
        t[0] = 200.1
        c4 = e.begin_run(2)
        e.settle(c4)                         # (100 ms) / 2 steps
        assert e.steps == 2
        assert e.ewma_ms == pytest.approx(0.2 * 50.0 + 0.8 * 50.0)

    def test_lru_bound(self):
        reg = MetricsRegistry()
        mon = obs_drift.DriftMonitor(registry=reg, max_programs=5)
        for i in range(12):
            mon.entry(f"fp-{i:04d}")
        snap = mon.snapshot()
        assert len(snap) == 5
        assert "fp-0011" in snap and "fp-0000" not in snap

    def test_interleaved_program_never_poisons_another_entry(
            self, monkeypatch):
        """A second program's compile/run between program A's settles
        must not fold into A's measured EWMA (the periodic-eval false
        drift alarm): the dispatch switch invalidates A's baseline, so
        A's next settle only re-seeds."""
        t = [0.0]
        monkeypatch.setattr(obs_drift.time, "perf_counter",
                            lambda: t[0])
        obs_drift.step_recorder("fp-ilv-A", 1)()     # seeds A
        t[0] = 1.0
        obs_drift.step_recorder("fp-ilv-A", 1)()     # folds 1000 ms
        eA = obs_drift.MONITOR.entry("fp-ilv-A")
        assert eA.steps == 1
        assert eA.ewma_ms == pytest.approx(1000.0)
        # program B dispatches (a compile or a cached run)
        obs_drift.MONITOR.note_dispatch("fp-ilv-B")
        t[0] = 50.0                                  # 49 s of B's work
        obs_drift.step_recorder("fp-ilv-A", 1)()     # re-seeds only
        assert eA.steps == 1                         # no 49 s sample
        t[0] = 51.0
        obs_drift.step_recorder("fp-ilv-A", 1)()     # honest again
        assert eA.steps == 2
        assert eA.ewma_ms == pytest.approx(1000.0)

    def test_executor_records_prediction_and_measurement(self):
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            x = layers.data("x", [4])
            y = layers.data("y", [1])
            pred = layers.fc(x, size=1)
            loss = layers.mean(layers.square_error_cost(pred, y))
            pt.optimizer.SGDOptimizer(0.05).minimize(loss)
        # fingerprints are structural: an identical program built by an
        # earlier test shares this entry (same program = same timeline,
        # by design) — assert the DELTA this test contributes
        steps0 = obs_drift.MONITOR.entry(main.fingerprint()).steps
        scope = pt.Scope()
        with pt.scope_guard(scope):
            exe = pt.Executor()
            exe.run(startup)
            feed = {"x": np.ones((4, 4), np.float32),
                    "y": np.ones((4, 1), np.float32)}
            for _ in range(3):
                exe.run(main, feed=feed, fetch_list=[loss])
        snap = obs_drift.MONITOR.entry(main.fingerprint()).snapshot()
        assert snap["predicted_step_ms"] is not None
        assert snap["bound"] in ("compute", "bandwidth", "comm", "host")
        # run 1 compiles (baseline reset), run 2's settle seeds it,
        # run 3's settle folds the one measured gap
        assert snap["steps"] == steps0 + 1
        assert snap["measured_step_ms"] > 0
        assert snap["drift_ratio"] is not None
        assert snap["host_share_pct"] is not None


# ---------------------------------------------------------------------------
# exposition conformance
# ---------------------------------------------------------------------------

class TestExposition:
    def _snapshot_with_every_family(self):
        sm = ServingMetrics()
        mm = sm.model('we"ird\\mo\ndel')          # escaping-hostile name
        mm.on_received(2)
        mm.on_batch(3, 4)
        mm.on_done(True, 1, phase_s={"pad": 0.01}, total_s=0.02)
        dm = sm.decode("dec")
        dm.on_received()
        dm.on_step(2, 4, 0.01, 2)
        from paddle_tpu.data.metrics import PipelineMetrics, register
        pm = PipelineMetrics("expo-pipe")
        pm.add("decode", 0.5, 3)
        pm.on_delivered(8)
        register(pm)
        tm = TrainMetrics("expo-train")
        tm.observe_step(12.5, n=2, examples=16)
        tm.observe_loss(0.25)
        tm.on_anomaly()
        REGISTRY.register("train", tm.name, tm)
        mon = obs_drift.MONITOR
        e = mon.entry("fp-expo")
        e.set_prediction(1.5, "bandwidth")
        e.observe_step(3.0)
        # keep providers alive through render (weakref registry)
        return sm, (pm, tm, e)

    def test_all_families_render_and_conform(self):
        sm, keep = self._snapshot_with_every_family()
        snap = sm.snapshot()
        # snapshot-merge semantics: every section on one pane
        for section in ("models", "decode", "data", "train", "model"):
            assert section in snap, section
        text = render_prometheus(snap)
        problems = validate_exposition(text)
        assert problems == [], problems
        for needle in ("pt_serve_received_total", "pt_decode_received",
                       "pt_data_batches_total", "pt_train_steps_total",
                       "pt_train_step_time_ms", "pt_train_loss",
                       "pt_train_anomalies_total",
                       "pt_model_drift_ratio", "pt_model_bound"):
            assert needle in text, needle
        # label escaping of the hostile model name survives round-trip
        assert 'we\\"ird\\\\mo\\ndel' in text

    def test_validator_flags_malformed_text(self):
        bad = "\n".join([
            "pt_x_total{model=\"a\"} 1",             # no TYPE
            "# TYPE pt_y gauge",
            "pt_y{m=\"a\"} 1",
            "pt_y{m=\"a\"} 2",                       # duplicate series
            "# TYPE pt_z gauge",
            "pt_z{m=\"a\"} notanumber",              # bad value
            'pt_y{m="un\\escaped"} 3',               # bad escape
        ]) + "\n"
        problems = validate_exposition(bad)
        assert any("no preceding # TYPE" in p for p in problems)
        assert any("duplicate series" in p for p in problems)
        assert any("non-numeric" in p for p in problems)
        assert any("malformed" in p for p in problems)

    def test_train_metrics_snapshot_fields(self):
        tm = TrainMetrics("t")
        tm.observe_step(10.0, n=2, examples=8)
        tm.observe_step(20.0, n=2, examples=8)
        tm.observe_step(None, n=2, examples=8)       # count-only window
        tm.observe_compiles(3)
        tm.observe_compiles(2)                       # monotonic
        tm.on_epoch()
        tm.on_checkpoint()
        tm.on_rollback()
        snap = tm.snapshot()
        assert snap["steps"] == 6 and snap["examples"] == 24
        assert len(tm._step_ms) == 2                 # None didn't sample
        assert snap["compile_events"] == 3
        assert snap["epochs"] == snap["checkpoints"] \
            == snap["rollbacks"] == 1
        assert snap["step_time"]["p50_ms"] is not None


# ---------------------------------------------------------------------------
# Chrome-trace JSON schema (tools/trace_dump.py)
# ---------------------------------------------------------------------------

class TestTraceDump:
    def test_dump_schema(self, monkeypatch, tmp_path):
        _arm(monkeypatch)
        with trace.span("a", cat="t", epoch=1):
            trace.instant("m", cat="t")
        trace.complete("c", 0.01, cat="t")
        from tools.trace_dump import dump
        path = dump(str(tmp_path / "t.json"))
        with open(path) as f:
            doc = json.load(f)
        evs = doc["traceEvents"]
        assert len(evs) == 3
        for ev in evs:
            assert set(ev) >= {"name", "cat", "ph", "ts", "pid", "tid",
                               "args"}
            assert ev["ph"] in ("X", "i")
            if ev["ph"] == "X":
                assert ev["dur"] >= 0
            else:
                assert ev["s"] == "t"
            assert isinstance(ev["ts"], (int, float))
        # dump() drained the ring
        assert trace.events() == []

    def test_dump_honors_trace_dir(self, monkeypatch, tmp_path):
        _arm(monkeypatch)
        monkeypatch.setenv("PT_TRACE_DIR", str(tmp_path / "td"))
        trace.instant("x")
        from tools.trace_dump import dump
        path = dump()
        assert path.startswith(str(tmp_path / "td"))
        with open(path) as f:
            assert len(json.load(f)["traceEvents"]) == 1


# ---------------------------------------------------------------------------
# end-to-end: the trainer demo trace + the served-request demo trace
# ---------------------------------------------------------------------------

def _trainer():
    pt.core.program.reset_unique_names()

    def train_func():
        x = layers.data("x", [4])
        y = layers.data("y", [1])
        pred = layers.fc(x, size=1)
        return [layers.mean(layers.square_error_cost(pred, y))]

    return pt.Trainer(train_func,
                      lambda: pt.optimizer.SGDOptimizer(0.05))


def _pipeline_reader(n=3):
    """A real data-pipeline (data/pipeline.py) reader: its decode /
    queue_wait spans must land on the same timeline as the trainer's."""
    from paddle_tpu.data.pipeline import Dataset
    rng = np.random.RandomState(0)
    samples = [{"x": rng.rand(4, 4).astype(np.float32),
                "y": rng.rand(4, 1).astype(np.float32)}
               for _ in range(n)]
    return (Dataset.from_samples(samples)
            .map_batches(lambda b: b, workers=2)
            .named("obs-e2e"))


class TestEndToEndTraces:
    def test_three_step_trainer_run_one_timeline(self, monkeypatch,
                                                 tmp_path):
        _arm(monkeypatch)
        tr = _trainer()
        tr.train(num_epochs=1, event_handler=lambda ev: None,
                 reader=_pipeline_reader(3), double_buffer=False)
        from tools.trace_dump import dump
        path = dump(str(tmp_path / "train.json"))
        with open(path) as f:
            evs = json.load(f)["traceEvents"]
        steps = [e for e in evs
                 if e["name"] == "step" and e["cat"] == "train"]
        assert len(steps) == 3
        assert [e["args"]["step"] for e in steps] == [0, 1, 2]
        # executor phases parent under the step spans — one causal
        # timeline, shared trace ids
        step_ids = {e["args"]["span_id"]: e["args"]["trace_id"]
                    for e in steps}
        execs = [e for e in evs if e["cat"] == "exec"
                 and e["args"].get("parent_id") in step_ids]
        assert {e["name"] for e in execs} >= {"host_prep", "dispatch"}
        for e in execs:
            assert e["args"]["trace_id"] \
                == step_ids[e["args"]["parent_id"]]
        # pipeline stages rode the same dump
        data_spans = {e["name"] for e in evs if e["cat"] == "data"}
        assert "decode" in data_spans and "queue_wait" in data_spans
        # epoch edges + guard-free run
        names = {e["name"] for e in evs}
        assert "epoch_begin" in names and "epoch_end" in names

        # the train-plane family populated from the same run, and the
        # drift monitor measured the program — both on ONE pane.
        # COUNTS cover every window (incl. the compile-absorbing first)
        snap = ServingMetrics().snapshot()
        assert snap["train"]["trainer"]["steps"] == 3
        assert snap["train"]["trainer"]["examples"] == 12
        assert snap["train"]["trainer"]["loss"] is not None
        text = render_prometheus(snap)
        assert validate_exposition(text) == []
        assert "pt_train_steps_total" in text
        fp = tr.train_program.fingerprint()[:12]
        assert f'pt_model_measured_step_ms{{program="{fp}"}}' in text

    def test_train_counters_vs_boundary_sampling(self):
        """Counts record EVERY window; step-time samples only at
        materialize boundaries (under log_every > 1 the in-between
        gaps measure host dispatch only — dispatch-vs-settle), and
        compile events count only THIS run's compiles (the startup
        compile predates train())."""
        tr = _trainer()
        tr.train(num_epochs=1, event_handler=lambda ev: None,
                 reader=_pipeline_reader(4), double_buffer=False,
                 log_every=2)
        tm = tr.train_metrics
        snap = tm.snapshot()
        assert snap["steps"] == 4 and snap["examples"] == 16
        # boundaries at steps 0 and 2: the first seeds, the second
        # folds ONE honest sample covering 2 steps
        assert len(tm._step_ms) == 1
        assert snap["compile_events"] == 1

    def test_trainer_step_span_context_rides_provenance(self,
                                                        monkeypatch):
        """Satellite: with tracing armed, LazyFetch provenance carries
        the step span's context (epoch/step) captured at the executor —
        the trainer's manual annotate plumbing is not engaged."""
        _arm(monkeypatch)
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            x = layers.data("x", [4])
            pred = layers.fc(x, size=1)
            loss = layers.mean(pred)
        scope = pt.Scope()
        with pt.scope_guard(scope):
            exe = pt.Executor()
            exe.run(startup)
            with trace.span("step", cat="train", epoch=7, step=42):
                (out,) = exe.run(main,
                                 feed={"x": np.ones((2, 4), np.float32)},
                                 fetch_list=[loss], lazy=True)
        prov = out.provenance
        assert prov["epoch"] == 7 and prov["step"] == 42
        assert prov["fetch"] == loss.name
        assert "span" in prov

    def test_watchdog_dump_names_active_spans(self, monkeypatch):
        """Satellite: StepHungError dumps attach the active span stack
        — which phase/stage was in flight when the step hung."""
        from paddle_tpu.resilience import faults, watchdog
        monkeypatch.setenv("PT_STEP_DEADLINE_S", "0.2")
        monkeypatch.setenv("PT_FAULT_INJECT", "step_hang@1")
        faults.reset()
        _arm(monkeypatch)
        try:
            with trace.span("step", cat="train", epoch=2, step=9):
                with pytest.raises(watchdog.StepHungError) as ei:
                    watchdog.wait_until_ready(np.float32(1.0))
            msg = str(ei.value)
            assert "active spans" in msg
            assert "train:step" in msg
            assert "'epoch': 2" in msg
        finally:
            faults.reset()

    @pytest.fixture(scope="class")
    def serving_dir(self, tmp_path_factory):
        pt.core.program.reset_unique_names()
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            x = layers.data("x", [6])
            probs = layers.fc(input=x, size=3, act="softmax")
        scope = pt.Scope()
        with pt.scope_guard(scope):
            pt.Executor().run(startup)
            d = str(tmp_path_factory.mktemp("obs") / "serve")
            pio.export_serving_model(d, ["x"], [probs],
                                     main_program=main, scope=scope,
                                     batch_size=4)
        return d

    def test_served_request_one_timeline_and_unified_scrape(
            self, monkeypatch, serving_dir, tmp_path):
        from paddle_tpu.serving.http import start_http_server
        engine = ServingEngine(max_wait_ms=2.0)
        engine.load_model("clf", serving_dir)
        _arm(monkeypatch)
        trace.reset()
        server, _thread = start_http_server(engine)
        base = f"http://127.0.0.1:{server.server_address[1]}"
        try:
            req = urllib.request.Request(
                f"{base}/v1/models/clf:predict",
                data=json.dumps(
                    {"feeds": {"x": [0.1] * 6}}).encode(),
                headers={"Content-Type": "application/json"},
                method="POST")
            with urllib.request.urlopen(req, timeout=60) as r:
                assert r.status == 200
            # the ingress span closes on the handler thread AFTER the
            # response is written: wait for it, don't race it
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline and not any(
                    e["name"] == "http_request" for e in trace.events()):
                time.sleep(0.01)

            from tools.trace_dump import dump
            path = dump(str(tmp_path / "serve.json"), drain=False)
            with open(path) as f:
                evs = json.load(f)["traceEvents"]
            by_name = {}
            for e in evs:
                by_name.setdefault(e["name"], []).append(e)
            (http,) = by_name["http_request"]
            (queue,) = by_name["queue"]
            (batch,) = by_name["batch"]
            tid = http["args"]["trace_id"]
            # the request id minted at ingress threads the whole chain:
            # queue + the (single-request) batch share the http span's
            # trace; pad/device/scatter parent under the batch span
            assert queue["args"]["trace_id"] == tid
            assert queue["args"]["parent_id"] == http["args"]["span_id"]
            assert queue["args"]["rid"] is not None
            assert batch["args"]["trace_id"] == tid
            assert batch["args"]["rids"] == [queue["args"]["rid"]]
            for phase in ("pad", "device", "fetch", "scatter"):
                spans = [e for e in by_name[phase]
                         if e["cat"] == "serve"]
                assert spans, phase
                assert any(e["args"].get("parent_id")
                           == batch["args"]["span_id"] for e in spans)

            # the unified scrape: pt_serve_* + pt_train_* +
            # pt_model_drift_ratio on ONE exposition
            tm = TrainMetrics("scrape-train")
            tm.observe_step(5.0, n=1, examples=4)
            REGISTRY.register("train", tm.name, tm)
            e = obs_drift.MONITOR.entry("fp-scrape")
            e.set_prediction(1.0, "compute")
            e.observe_step(2.0)
            with urllib.request.urlopen(
                    f"{base}/v1/metrics?format=prometheus",
                    timeout=60) as r:
                text = r.read().decode()
            assert validate_exposition(text) == []
            assert "pt_serve_completed_total" in text
            assert "pt_train_steps_total" in text
            assert 'pt_model_drift_ratio{program="fp-scrape"} 2' in text
        finally:
            server.shutdown()
            engine.shutdown()
