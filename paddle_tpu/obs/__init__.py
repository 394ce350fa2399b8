"""paddle_tpu.obs — the unified observability plane.

Three surfaces, one timeline:

  trace     structured spans (`trace.span(name, **attrs)`) with
            thread-local context propagation, a bounded ring buffer,
            and Chrome-trace-event output (tools/trace_dump.py writes a
            Perfetto-loadable file). Armed by PT_TRACE; near-zero cost
            off. Every plane — executor phases, trainer events,
            data-pipeline stages, the serving request lifecycle —
            emits onto it.
  metrics   the process-wide MetricsRegistry + the ONE Prometheus text
            renderer for every family (pt_serve_* / pt_decode_* /
            pt_data_* / pt_train_* / pt_model_*), plus TrainMetrics —
            the train-plane family the Trainer records into — and
            pt_xla_compiles_total, counted by the one `jax.monitoring`
            listener this package registers at import.
  drift     continuous predicted-vs-measured monitoring: the roofline
            `predict_step` recorded at compile time, measured step time
            folded into an EWMA per step, exported as
            pt_model_predicted_step_ms / pt_model_measured_step_ms /
            pt_model_drift_ratio on the same scrape.
  opprof    the per-op performance observatory: measured device time
            per program segment (the lowering's own run boundaries),
            distributed across ops by predicted cost share and JOINED
            to analysis/cost — the ranked laggard ledger behind
            tools/op_report.py and the pt_op_* family. Opt-in
            profiling, never a hot-path hook.

See docs/observability.md.
"""

import jax.monitoring

from . import opprof, trace
from .drift import MONITOR, DriftMonitor, observe_prediction, step_recorder
from .metrics import (REGISTRY, XLA_COMPILES, MetricsRegistry,
                      TrainMetrics, build_info_labels, global_snapshot,
                      render_prometheus, validate_exposition)

# the process's one compile listener: every backend compile becomes a
# phase record `xla`/`compile` in the trace ring and a count on the
# scrape (obs/metrics.py XlaCompiles)
jax.monitoring.register_event_duration_secs_listener(
    XLA_COMPILES.on_event)

__all__ = ["trace", "opprof", "REGISTRY", "MetricsRegistry",
           "TrainMetrics", "render_prometheus", "validate_exposition",
           "global_snapshot", "build_info_labels", "MONITOR",
           "DriftMonitor", "observe_prediction", "step_recorder"]
