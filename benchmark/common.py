"""What every run shares: the manifest and its data files, the device
check, the compile cache, the traced window, the result line."""

from __future__ import annotations

import importlib
import json
import os
import shutil
import time
from typing import Dict, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORK_DIR = os.path.join(ROOT, ".bench_work")   # ignored by git


class ManifestError(ValueError):
    pass


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of `workloads`, with the data files its names lead to
    and the metrics the manifest says it reports."""

    def __init__(self, manifest_path: str, name: str):
        manifest = load_json(manifest_path)
        base = os.path.dirname(os.path.abspath(manifest_path))
        # data files sit beside the manifest's own benchmark directory
        # (a rehearsal's manifest brings its own under /root/scratch);
        # readers and kinds are always this directory's
        data = os.path.join(base, manifest["paths"][0])
        cells = {w["name"]: w for w in manifest["workloads"]}
        if name not in cells:
            raise ManifestError(f"no workload {name!r} in {manifest_path}; "
                                f"it has {sorted(cells)}")
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        self.run_seconds = manifest["run_seconds"]
        configs = {c["name"]: c for c in manifest["configs"]}
        self.config = load_json(os.path.join(
            base, configs[self.entry["config"]]["file"]))
        self.traffic = load_json(os.path.join(
            data, "traffic", self.entry["traffic"] + ".json"))

        def mine(metric):
            return name in metric.get("workloads", [name])

        self.end_to_end = {
            m["name"]: load_json(os.path.join(data, "end_to_end",
                                              m["name"] + ".json"))
            for m in manifest["end_to_end"] if mine(m)}
        self.per_layer = {}
        for m in manifest["per_layer"]:
            if not mine(m):
                continue
            if m["moves"] not in self.end_to_end:
                raise ManifestError(
                    f"cell {name!r} reports the layer metric "
                    f"{m['name']!r} but not {m['moves']!r}, the "
                    "end-to-end metric it moves")
            spec = load_json(os.path.join(data, "layer_metrics",
                                          m["name"] + ".json"))
            self.per_layer[m["name"]] = dict(spec, unit=m["unit"])
        for m in manifest["end_to_end"]:
            if m["name"] in self.end_to_end:
                self.end_to_end[m["name"]]["unit"] = m["unit"]


def read_metrics(specs: Dict[str, Dict], ctx: Dict) -> Dict[str, Dict]:
    """Run each metric's reader (one module under `readers/`, named in
    the metric's own file). A reader that finds nothing to read returns
    None and the metric is left out of the line."""
    out = {}
    for name, spec in specs.items():
        reader = importlib.import_module("readers." + spec["reader"])
        value = reader.read(ctx, **spec.get("params", {}))
        if value is not None:
            out[name] = {"value": float(value), "unit": spec["unit"]}
    return out


# -- the device ---------------------------------------------------------------

def require_chips(chips: int, rehearsal: bool) -> Dict:
    """JAX's own report of the device; anything but a TPU with enough
    chips ends the run with no result (a rehearsal takes what is there
    and never prints a metric)."""
    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if not rehearsal and device["platform"] != "tpu":
        raise SystemExit(f"benchmark: no TPU; JAX found {devices}")
    if len(devices) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chips, JAX "
                         f"found {len(devices)}")
    return device


def memory_peak_bytes(chips: int) -> Optional[int]:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def fresh_work_dir(name: str) -> str:
    """A directory inside the checkout, emptied: bundles and traces."""
    path = os.path.join(WORK_DIR, name)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


# -- compiles inside the window ----------------------------------------------

class CompileCounter:
    """Counts the executables XLA builds, through `jax.monitoring`: the
    first call of any shape builds one (compiled, or read from the
    persistent cache), whoever made it: the Executor, the serving plane,
    an eager op of the K/V seeding path."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.count = 0
        self.at = []    # perf_counter of each, for the run's notes
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.at.append(time.perf_counter())


# -- the traced window --------------------------------------------------------

class Tracer:
    """Profiles a short stretch right after the measured window of a
    `--trace 1` run and reduces it. One per run."""

    WINDOW_SPAN = "bench/traced_window"

    def __init__(self, enabled: bool, work_name: str,
                 rehearsal: bool = False):
        self.enabled = enabled
        self.rehearsal = rehearsal   # off the chip no device plane exists
        self.reduced: Optional[Dict] = None
        self._dir = fresh_work_dir("trace_" + work_name) if enabled \
            else None
        self._span = None

    def start(self):
        if not self.enabled:
            return
        import jax
        jax.profiler.start_trace(self._dir)
        self._span = jax.profiler.TraceAnnotation(self.WINDOW_SPAN)
        self._span.__enter__()

    def stop(self):
        if not self.enabled or self._span is None:
            return
        import jax
        self._span.__exit__(None, None, None)
        self._span = None
        jax.profiler.stop_trace()

    def reduce(self):
        """After the window: parse what was written."""
        if not self.enabled:
            return None
        import trace_reduce
        xplane = trace_reduce.find_xplane(self._dir)
        trace = trace_reduce.load_xplane(xplane)
        keep = os.environ.get("BENCH_KEEP_TRACE")
        if keep:   # the builder's way to look at a trace and record one
            with open(keep, "w") as f:
                json.dump(trace, f)
            with open(keep + ".outline", "w") as f:
                json.dump(trace_reduce.outline(xplane), f, indent=1)
        shutil.rmtree(self._dir, ignore_errors=True)
        if self.rehearsal and not trace_reduce.device_ops(trace):
            return None
        self.reduced = trace_reduce.reduce(trace, self.WINDOW_SPAN)
        return self.reduced


# -- the result line ----------------------------------------------------------

def note(**fields):
    """An earlier line of standard output: worth keeping, not the
    contract's."""
    print(json.dumps(fields), flush=True)


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: Dict, device: Dict, chips: int,
                reduced: Optional[Dict]) -> str:
    dev = dict(device)
    dev["memory_peak_bytes"] = memory_peak_bytes(chips)
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": dev}
    if reduced is not None:
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    return json.dumps(line)
