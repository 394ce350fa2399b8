"""The stall sentinel (obs/trace.py): a `PhaseTimer` span open far
beyond its phase's usual length leaves ONE `stall` record that says
what its thread was doing, collections are spans of the same plane, and
`benchmark/readers/overrun.py` reads both. Every case has a time limit
of its own (`limit`): a sentinel that hangs must fail one case, not the
run."""

import functools
import gc
import importlib.util
import json
import os
import signal
import sys
import threading
import time

import pytest

from paddle_tpu.core.async_fetch import PhaseTimer
from paddle_tpu.obs import trace
from paddle_tpu.obs.metrics import (global_snapshot, render_prometheus,
                                    validate_exposition)
from paddle_tpu.serving.metrics import DecodeMetrics, DecodePhaseTimer

HERE = os.path.basename(__file__)


def limit(seconds):
    """The case's own time limit: SIGALRM on the main thread (where
    pytest and its xdist workers run a test)."""
    def wrap(test):
        @functools.wraps(test)
        def run(*args, **kwargs):
            if threading.current_thread() is not threading.main_thread():
                return test(*args, **kwargs)

            def late(*_):
                raise TimeoutError(f"{test.__name__}: over {seconds} s")

            before = signal.signal(signal.SIGALRM, late)
            signal.setitimer(signal.ITIMER_REAL, seconds)
            try:
                return test(*args, **kwargs)
            finally:
                signal.setitimer(signal.ITIMER_REAL, 0)
                signal.signal(signal.SIGALRM, before)
        return run
    return wrap


@pytest.fixture(autouse=True)
def watched():
    """The sentinel running (another module's test may have stopped
    it) and an empty ring."""
    trace._sentinel_start()
    trace.reset()
    yield
    trace._sentinel_start()
    trace.reset()


def _timer(cls=PhaseTimer, usual=0.001):
    """A timer whose every phase has a usual length."""
    timer = cls()
    for phase in timer.PHASES:
        for _ in range(4):
            timer.add(phase, usual)
    trace.reset()
    return timer


def _stalls(name=None):
    return [r for r in trace.attr_records()
            if r[0] == "stall" and (name is None or r[1] == name)]


def cause(attrs, seconds):
    """docs/observability.md's table, "what the thread was doing"."""
    if attrs["gc_s"] >= 0.5 * seconds:
        return "collecting"
    sampled = attrs["sampled_s"] or 0.0
    cpu, delay = attrs["cpu_s"] or 0.0, attrs["run_delay_s"] or 0.0
    # on a shared core the two split the time: their sum near the
    # whole is a thread that never waited for anything but the core
    if sampled and cpu + delay >= 0.5 * sampled:
        return "computing" if cpu >= delay else "descheduled"
    return "waiting"          # stack_last says where, others who ran


def napping(seconds):
    time.sleep(seconds)       # the sleeping line


@limit(60)
def test_a_sleeping_span_leaves_one_record_that_says_so():
    timer = _timer()
    with timer.span("dispatch"):
        napping(0.35)
    (_, name, t_end, seconds, attrs), = _stalls()
    assert name == "exec/dispatch" and 0.35 <= seconds < 5.0
    assert attrs["waits_on"] is None
    assert attrs["usual_s"] == pytest.approx(0.001)
    assert attrs["cpu_s"] < 0.05 and attrs["gc_s"] == 0
    assert attrs["sampled_s"] >= 0.25      # seen soon after it opened
    assert cause(attrs, seconds) == "waiting"
    assert attrs["stack_last"][0].endswith(" napping")
    assert attrs["stack_last"][0].startswith(f"tests/{HERE}:")
    assert any("test_a_sleeping_span" in f for f in attrs["stack_last"])
    assert len(attrs["stack_last"]) <= trace.STACK_FRAMES
    # the phase record itself is there as ever, after the stall's
    phases = [r for r in trace.phase_records() if r[0] == "exec"]
    assert [r[1] for r in phases] == ["dispatch"]
    assert phases[0][3] == pytest.approx(seconds, abs=1e-3)
    assert timer.overrun_snapshot()["phase_overruns"] == 1
    assert timer.overrun_snapshot()["last_overrun"]["phase"] \
        == "exec/dispatch"


@limit(60)
def test_a_spinning_span_reads_cpu_near_its_length():
    timer = _timer()
    with timer.span("fetch"):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < 0.4:
            pass
    (_, name, _, seconds, attrs), = _stalls()
    assert name == "exec/fetch"
    # on a machine with a core to spare the two are equal; a shared one
    # may keep the thread off the core, and says so in run_delay_s
    assert attrs["cpu_s"] > 0.15 * seconds
    assert attrs["cpu_s"] + (attrs["run_delay_s"] or 0.0) \
        >= 0.7 * attrs["sampled_s"]
    assert cause(attrs, seconds) in ("computing", "descheduled")
    assert attrs["gc_s"] == 0


class _Recorded:
    """Stands where `jax.profiler.TraceAnnotation` does."""

    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("open", self.name))

    def __exit__(self, *exc):
        self.log.append(("close", self.name))


@limit(120)
def test_a_collection_is_a_span_and_a_record_and_the_stalls_gc_s():
    timer = _timer()
    gc.disable()        # the cycles are for the collection in the span
    try:
        junk = []
        for _ in range(400_000):           # a large cycle set
            a = []
            a.append([a])
            junk.append(a)
        del junk, a
        _Recorded.log = []
        trace._annotation_factory = _Recorded
        with timer.span("host_prep"):
            gc.collect()
    finally:
        trace._annotation_factory = None
        gc.enable()
    (_, name, t_end, seconds, attrs), = _stalls()
    assert name == "exec/host_prep" and seconds > trace.OVERRUN_FLOOR_S
    assert attrs["gc_s"] > 0.5 * seconds
    assert cause(attrs, seconds) == "collecting"
    # the interpreter was held: the sentinel was kept out meanwhile
    assert attrs["late_s"] > 0.05
    collections = [r for r in trace.attr_records()
                   if r[:2] == ("host", "gc")]
    big = max(collections, key=lambda r: r[3])
    assert big[4]["generation"] == 2 and big[4]["collected"] >= 400_000
    assert t_end - seconds <= big[2] - big[3] and big[2] <= t_end
    opened = [n for what, n in _Recorded.log if what == "open"]
    assert trace.GC_SPAN in opened
    assert opened.count(trace.GC_SPAN) == sum(
        1 for what, n in _Recorded.log
        if (what, n) == ("close", trace.GC_SPAN))
    counts = trace.stall_counters()["collections"]
    assert counts[2][0] >= 1 and counts[2][1] >= big[3]


@limit(60)
def test_a_collection_under_a_millisecond_leaves_no_record():
    gc.collect()
    trace.reset()
    before = trace.stall_counters()["collections"].get(0, (0, 0.0))
    for _ in range(20):
        gc.collect(0)
    after = trace.stall_counters()["collections"][0]
    assert after[0] >= before[0] + 20 and after[1] > before[1]
    assert [r for r in trace.phase_records() if r[0] == "host"] == []


@limit(60)
def test_a_span_behind_anothers_lock_names_who_held_it():
    timer = _timer()
    lock, held = threading.Lock(), threading.Event()

    def holder():
        with lock:
            held.set()
            napping(0.4)

    other = threading.Thread(target=holder, name="the-holder")
    other.start()
    held.wait()
    with timer.span("dispatch"):
        lock.acquire()             # blocked here
        lock.release()
    other.join()
    (_, _, _, seconds, attrs), = _stalls()
    assert cause(attrs, seconds) == "waiting" and attrs["cpu_s"] < 0.05
    assert attrs["stack_last"][0].endswith(
        " test_a_span_behind_anothers_lock_names_who_held_it")
    held_by = [o for o in attrs["others"] if o.startswith("the-holder: ")]
    assert len(held_by) == 1 and held_by[0].endswith(" napping")
    assert not any("pt-stall-sentinel" in o for o in attrs["others"])


@limit(60)
def test_waiting_for_work_is_never_judged_and_a_device_wait_says_so():
    timer = _timer(DecodePhaseTimer)
    with timer.span("sched_idle"):
        napping(0.3)
    assert _stalls() == []
    with timer.span("step_wait"):
        napping(0.3)
    (_, name, _, _, attrs), = _stalls()
    assert name == "decode/step_wait" and attrs["waits_on"] == "device"
    with timer.span("step_prep"):
        napping(0.3)
    assert _stalls("decode/step_prep")[0][4]["waits_on"] is None
    # a stall record is no phase of `decode`: a reader that sums the
    # category's phases (phase_ms, phase_overlap) never sees one
    assert sorted(r[1] for r in trace.phase_records()
                  if r[0] == "decode") \
        == ["sched_idle", "step_prep", "step_wait"]


@limit(60)
def test_the_rule_has_a_floor_and_follows_the_phases_own_length():
    assert trace.overrun_after(0.001) == trace.OVERRUN_FLOOR_S
    assert trace.overrun_after(2.1) == pytest.approx(4.2)
    timer = PhaseTimer()
    # PR 50's run_loop calls: 2.0 to 2.1 s, then one of 5 s
    for s in (2.0, 2.1, 2.05, 2.0, 2.08, 2.0):
        timer.add("device", s)
    assert 2.0 <= timer._usual["device"] <= 2.1
    assert trace.overrun_after(timer._usual["device"]) < 5.0
    before = timer._usual["device"]
    timer.add("device", 5.0)           # an overrun moves it a sixteenth
    assert timer._usual["device"] == pytest.approx(
        before + (5.0 - before) / 16)
    # an admission near twice the last one's length is no overrun: the
    # peak is the usual length at once, and sinks slowly
    timer.add("fetch", 0.1)
    timer.add("fetch", 0.18)
    assert timer._usual["fetch"] == 0.18
    timer.add("fetch", 0.06)
    assert 0.17 < timer._usual["fetch"] < 0.18
    # a phase nobody has timed yet is not judged: no usual length
    fresh = PhaseTimer()
    with fresh.span("dispatch"):
        napping(0.25)
    assert _stalls() == [] and fresh._usual["dispatch"] >= 0.25
    timer.reset()                      # the learned lengths stay
    assert timer._usual["fetch"] > 0.17 and timer.overruns == 0


@limit(120)
def test_ten_thousand_normal_spans_flag_nothing_inside_the_budget():
    timer = _timer(DecodePhaseTimer)
    n, batches = 10_000, []
    for _ in range(20):         # on the wall's clock, the sentinel
        t0 = time.perf_counter()    # waking meanwhile; the best batch
        for _ in range(n // 20):    # is the cost, whatever else the
            with timer.span("step_prep"):       # machine was doing
                pass
        batches.append((time.perf_counter() - t0) / (n // 20))
    assert trace._sentinel.is_alive()
    assert _stalls() == [] and timer.overruns == 0
    # test_obs.py test_phase_record_budget's bound for a phase record
    assert min(batches) < 10e-6, f"{min(batches) * 1e6:.2f} us a span"
    assert timer._open[threading.get_ident()] is None


@limit(60)
def test_the_counters_and_the_snapshots_carry_the_count():
    metrics = DecodeMetrics("m")
    for phase in metrics.timer.PHASES:
        metrics.timer.add(phase, 0.002)
    trace.reset()
    was = trace.stall_counters()["overruns"].get("decode/step_emit",
                                                 (0, 0.0))
    with metrics.timer.span("step_emit"):
        napping(0.3)
    snap = metrics.snapshot()
    assert snap["phase_overruns"] == 1
    last = snap["last_overrun"]
    assert last["phase"] == "decode/step_emit" and last["seconds"] >= 0.3
    assert last["stack_last"][0].endswith(" napping")
    json.dumps(snap["last_overrun"])          # a scrape can carry it
    n, seconds = trace.stall_counters()["overruns"]["decode/step_emit"]
    assert n == was[0] + 1 and seconds >= was[1] + 0.3
    text = render_prometheus(global_snapshot())
    assert validate_exposition(text) == []
    assert ('pt_phase_overruns_total{cat="decode",phase="step_emit"} '
            f"{n}") in text
    assert 'pt_phase_overrun_seconds_total{cat="decode",' in text
    assert 'pt_gc_collections_total{generation="0"}' in text
    assert 'pt_gc_pause_seconds_total{generation="0"}' in text
    metrics.reset()         # the count is the window's, the record stays
    assert metrics.snapshot()["phase_overruns"] == 0
    assert metrics.snapshot()["last_overrun"]["phase"] == last["phase"]


@limit(60)
def test_the_executors_timings_carry_the_count():
    import paddle_tpu as pt
    exe = pt.Executor()
    timings = exe.step_timings()
    assert timings["phase_overruns"] == 0
    assert timings["last_overrun"] is None and "device_s" in timings
    exe._timings.add("fetch", 0.001)
    with exe._timings.span("fetch"):
        napping(0.3)
    assert exe.step_timings(reset=True)["phase_overruns"] == 1
    assert exe.step_timings()["phase_overruns"] == 0


@limit(120)
def test_every_phase_of_the_executors_step_is_an_open_span(monkeypatch):
    """`host_prep` and a cached `dispatch` are spans, as `device` and
    `fetch` are: a feed preparation or a jitted call that hangs leaves
    its record; a cold call compiles and is no dispatch."""
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu import layers
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [4], dtype="float32")
        loss = layers.mean(layers.fc(x, size=4))
    feed = {"x": np.ones((2, 4), np.float32)}
    with pt.scope_guard(pt.Scope()):
        exe = pt.Executor()
        exe.run(startup)

        def slow_once(fn):
            seen = []

            def run(*args, **kwargs):
                if not seen:
                    seen.append(napping(0.3))
                return fn(*args, **kwargs)
            return run

        # the cold call: 0.3 s of "compile", no dispatch and no record
        jit = pt.core.executor.jax.jit
        monkeypatch.setattr(pt.core.executor.jax, "jit",
                            lambda f, **kw: slow_once(jit(f, **kw)))
        exe.step_timings(reset=True)
        exe.run(main, feed=feed, fetch_list=[loss])
        monkeypatch.undo()
        timings = exe.step_timings()
        assert timings["compile_s"] >= 0.3 and timings["dispatch_s"] == 0
        assert _stalls() == []
        for _ in range(3):
            exe.run(main, feed=feed, fetch_list=[loss])
        assert 0 < exe._timings._usual["dispatch"] < 0.1
        assert 0 < exe._timings._usual["host_prep"] < 0.1
        assert _stalls() == []
        # a cached call that hangs, then a feed preparation that does
        (compiled,) = [c for c in exe._cache.values()
                       if c.fetch_names == [loss.name]]
        compiled.fn = slow_once(compiled.fn)
        exe.run(main, feed=feed, fetch_list=[loss])
        monkeypatch.setattr(exe, "_prep_feed", slow_once(exe._prep_feed))
        exe.run(main, feed=feed, fetch_list=[loss])
    first, second = _stalls()
    assert (first[1], second[1]) == ("exec/dispatch", "exec/host_prep")
    for _, _, _, seconds, attrs in (first, second):
        assert seconds >= 0.3 and attrs["waits_on"] is None
        assert attrs["stack_first"][0].endswith(" napping")
        assert cause(attrs, seconds) == "waiting"
    assert exe.step_timings()["phase_overruns"] == 2


@limit(60)
def test_one_daemon_thread_a_process_and_the_stop_is_sticky():
    def sentinels():
        return [t for t in threading.enumerate()
                if t.name == "pt-stall-sentinel"]

    timers = [PhaseTimer() for _ in range(3)]
    (thread,) = sentinels()
    assert thread.daemon and thread is trace._sentinel
    assert gc.callbacks.count(trace._on_collection) == 1
    trace._sentinel_stop()
    assert sentinels() == [] and trace._on_collection not in gc.callbacks
    timers.append(PhaseTimer())         # registers, starts nothing
    assert sentinels() == []
    with _timer().span("dispatch"):
        napping(0.25)
    assert _stalls() == []              # nobody watched
    trace._sentinel_start()
    trace._sentinel_start()
    assert len(sentinels()) == 1
    assert gc.callbacks.count(trace._on_collection) == 1


@limit(60)
def test_the_span_around_is_open_again_when_the_inner_one_closes():
    timer = _timer(DecodePhaseTimer)
    me = threading.get_ident()
    with timer.span("admit") as outer:
        assert timer._open[me][0] == "admit"
        with timer.span("prefill_fetch"):
            assert timer._open[me][0] == "prefill_fetch"
        assert timer._open[me] == ("admit", outer._t0)
        with timer.span("seed_kv") as inner:
            inner.cancel()
            napping(0.25)       # not this phase after all: no record
        assert timer._open[me] == ("admit", outer._t0)
        napping(0.3)            # the admission's own code stalls
    assert timer._open[me] is None
    assert [r[1] for r in _stalls()] == ["decode/admit"]


@limit(60)
def test_an_overrun_nobody_watched_is_judged_when_it_closes(monkeypatch):
    """The interpreter held from a span's opening to its close keeps
    the sentinel out: the span's own thread applies the rule."""
    timer = _timer()
    time.sleep(3 * trace.SENTINEL_PERIOD_S)
    monkeypatch.setattr(trace, "_tick", lambda now, late: None)  # blind
    with timer.span("dispatch"):
        napping(0.3)
    with timer.span("dispatch"):
        pass
    (_, name, _, seconds, attrs), = _stalls()
    assert name == "exec/dispatch" and seconds >= 0.3
    assert attrs["stack_first"] is None
    assert attrs["stack_last"][0].endswith(
        " test_an_overrun_nobody_watched_is_judged_when_it_closes")
    assert attrs["gc_s"] == 0 and attrs["sampled_s"] is None
    assert isinstance(attrs["others"], list)
    assert timer.overruns == 1


@limit(60)
def test_the_record_keeps_its_attrs_with_tracing_on(monkeypatch):
    monkeypatch.setenv(trace.ENABLE_ENV, "1")
    timer = _timer()
    with timer.span("dispatch", step=7):
        napping(0.3)
    (stall,) = [e for e in trace.events()
                if e["cat"] == "stall" and e["name"] == "exec/dispatch"]
    assert stall["args"]["stack_last"][0].endswith(" napping")
    assert stall["args"]["usual_s"] == pytest.approx(0.001)
    assert stall["dur"] >= 0.3e6 and "span_id" in stall["args"]
    (span,) = [e for e in trace.events()
               if e["cat"] == "exec" and e["name"] == "dispatch"]
    assert span["args"]["step"] == 7
    assert timer.span("dispatch").kept() is True
    monkeypatch.setenv(trace.ENABLE_ENV, "0")
    assert timer.span("dispatch").kept() is False


@limit(60)
def test_the_sentinel_says_since_when_it_watches_and_leaves_the_ring_alone():
    trace._sentinel_stop()
    trace.reset()
    assert trace.sentinel_since() is None
    assert trace.stall_counters()["watching_since"] is None
    t0 = time.perf_counter()
    trace._sentinel_start()
    time.sleep(3 * trace.SENTINEL_PERIOD_S)
    assert t0 <= trace.sentinel_since() <= time.perf_counter()
    counters = trace.stall_counters()
    assert counters["watching_since"] == trace.sentinel_since()
    assert counters["tick_errors"] == 0
    assert 0.02 <= trace.SENTINEL_PERIOD_S <= 0.05
    # it writes nothing of its own: every reader reads as before
    assert trace.phase_records() == [] and trace.attr_records() == []
    assert trace.events() == []
    trace.phase("decode", "step_wait", 0.5, 9.0)
    trace.phase("kernel", "flash_plan", 0.0, 9.5, attrs={"block_q": 128})
    assert trace.phase_records() == [("decode", "step_wait", 9.0, 0.5),
                                     ("kernel", "flash_plan", 9.5, 0.0)]
    assert trace.attr_records() == [
        ("kernel", "flash_plan", 9.5, 0.0, {"block_q": 128})]


# -- benchmark/readers/overrun.py on hand-made rings --------------------------

def _reader():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "readers", "overrun.py")
    spec = importlib.util.spec_from_file_location("_overrun", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


@pytest.fixture
def window(monkeypatch):
    """T_START = 100 s on the ring's clock, 10 s of set-up, 5 s
    measured: the window is [110, 115]. The sentinel is stopped, so
    that the ring holds what the case puts there, and said to have
    watched since 101 s."""
    trace._sentinel_stop()
    trace.reset()
    monkeypatch.setattr(sys.modules["__main__"], "T_START", 100.0,
                        raising=False)
    monkeypatch.setattr(trace, "sentinel_since", lambda: 101.0)
    return {"obs": {"setup_s": 10.0, "window_s": 5.0}}


def _ring(*records):
    for cat, name, t_end, seconds, attrs in records:
        trace._append((cat, name, t_end, seconds, 1, attrs))


WARM = ("decode", "step_wait", 105.0, 9.0, None)


@limit(60)
def test_reader_zero_where_the_sentinel_watched_and_flagged_nothing(
        window, capsys):
    read = _reader()
    _ring(WARM, ("decode", "step_wait", 113.0, 0.5, None))
    assert read(window, "stall", "decode/*") == 0.0
    assert read(window, "host", "gc") == 0.0
    assert capsys.readouterr().out == ""


@limit(60)
def test_reader_none_where_nobody_watched_or_the_ring_is_too_young(
        window, monkeypatch):
    read = _reader()
    stall = ("stall", "decode/step_prep", 113.0, 1.0, {"cpu_s": 0.9})
    _ring(WARM, stall)
    assert read(window, "stall", "decode/*") == pytest.approx(20.0)
    assert read({"obs": {"setup_s": 10.0}}, "stall", "decode/*") is None
    monkeypatch.setattr(trace, "sentinel_since", lambda: None)
    assert read(window, "stall", "decode/*") is None    # nobody watched
    monkeypatch.setattr(trace, "sentinel_since", lambda: 110.2)
    assert read(window, "stall", "decode/*") is None    # started inside
    monkeypatch.setattr(trace, "sentinel_since", lambda: 101.0)
    trace.reset()
    _ring(("decode", "step_wait", 110.5, 0.01, None), stall)
    assert read(window, "stall", "decode/*") is None    # ring too young
    trace.reset()
    _ring(WARM, stall)
    # the parent has neither accessor: no metric
    monkeypatch.delattr(trace, "attr_records")
    assert read(window, "stall", "decode/*") is None
    monkeypatch.undo()
    assert read(window, "stall", "decode/*") is None    # no T_START


@limit(60)
def test_reader_prints_each_record_and_sums_the_windows_alone(
        window, capsys):
    read = _reader()
    _ring(WARM,
          ("stall", "decode/admit", 108.0, 2.0, {"cpu_s": 0.1}),  # warm-up
          # a stalled admission holds a stalled wait: one stretch
          ("stall", "decode/prefill_fetch", 112.0, 0.5, {"cpu_s": 0.0}),
          ("stall", "decode/admit", 112.1, 0.7, {"cpu_s": 0.0}),
          ("stall", "exec/device", 113.0, 1.0, {"waits_on": "device"}),
          ("host", "gc", 113.5, 0.25, {"generation": 2, "collected": 9}),
          ("stall", "decode/step_prep", 114.0, 0.25, {"cpu_s": 0.2}),
          # the traced seconds: printed, not summed
          ("stall", "decode/step_prep", 116.5, 1.2,
           {"stack_last": ["profiler.py:1 start_trace"]}),
          ("host", "gc", 117.0, 0.3, {"generation": 2, "collected": 1}))
    assert read(window, "stall", "decode/*") == pytest.approx(
        (0.7 + 0.25) / 5.0 * 100)
    lines = [json.loads(line)
             for line in capsys.readouterr().out.splitlines()]
    assert [(rec["record"][1], rec["counted"]) for rec in lines] == [
        ("decode/prefill_fetch", True), ("decode/admit", True),
        ("decode/step_prep", True), ("decode/step_prep", False)]
    assert lines[-1]["attrs"]["stack_last"] == ["profiler.py:1 start_trace"]
    assert lines[-1]["ended_s_into_window"] == pytest.approx(6.5)
    assert read(window, "stall", "exec/*") == pytest.approx(20.0)
    capsys.readouterr()
    assert read(window, "host", "gc") == pytest.approx(5.0)
    lines = [json.loads(line)
             for line in capsys.readouterr().out.splitlines()]
    assert [(rec["attrs"]["collected"], rec["counted"])
            for rec in lines] == [(9, True), (1, False)]
