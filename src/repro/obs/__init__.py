"""repro.obs — unified tracing, metrics & profiling across the runtime.

One zero-dependency observability layer threaded through every
subsystem: the compile pass (``core.plan``), kernel dispatch
(``kernels.dispatch``), the program front door (``Options(trace=)``)
and the serving runtime (``repro.serve``). See docs/observability.md
for the span taxonomy and metric name registry.

    from repro import obs

    trace = obs.enable()                  # install a collector
    ...                                   # compile / run / serve
    trace.export("out.json")              # open in chrome://tracing
    print(obs.prometheus_text())          # metrics exposition dump

The on-demand :class:`Trace` collector is **off by default**: with no
collector installed and no flight recorder, ``obs.span``/``obs.event``
return a shared no-op immediately (<2% end-to-end overhead on the
3-stage imaging chain, gated by ``benchmarks/bench_obs.py`` through
``scripts/check_bench.py``), and recording never perturbs numerics —
hooks observe, they do not touch arrays.

The **flight recorder** (``obs.flight``) is the exception: it installs
at import time (disable with ``REPRO_FLIGHT=off``) and keeps the last
N spans/events per thread in preallocated ring buffers regardless of
the trace tri-state, so ``FlightRecorder.dump()`` can reconstruct the
moments before an incident (<5% overhead under serving load, same
bench gate). Per-program :class:`SLO` objectives (``obs.slo``) and the
structured JSON-lines log (``obs.log``) build on it: a breach or a
worker failure auto-triggers a dump inside ``repro.serve``.
"""

from repro.obs.export import (export_metrics, prometheus_text, write_jsonl)
from repro.obs.metrics import (Counter, Gauge, Histogram, RATIO_BUCKETS,
                               REGISTRY, Registry, counter, gauge, histogram)
from repro.obs.trace import (TRACE_MODES, Trace, current_trace_id, disable,
                             enable, enabled, event, get_trace, now_ns,
                             recording, set_thread_device, span, span_at,
                             span_ns, trace_mode, use_mode)
from repro.obs.flight import (FlightRecorder, get_flight, install,
                              install_default, uninstall)
from repro.obs.log import StructuredLog
from repro.obs.slo import SLO, SLOMonitor

__all__ = [
    "Counter", "FlightRecorder", "Gauge", "Histogram", "RATIO_BUCKETS",
    "REGISTRY", "Registry", "SLO", "SLOMonitor", "StructuredLog",
    "TRACE_MODES", "Trace", "counter", "current_trace_id", "disable",
    "enable", "enabled", "event", "export_metrics", "gauge", "get_flight",
    "get_trace", "histogram", "install", "install_default", "now_ns",
    "prometheus_text", "recording", "set_thread_device", "span", "span_at",
    "span_ns", "trace_mode", "uninstall", "use_mode", "write_jsonl",
]

# the always-on black box: installed unless REPRO_FLIGHT=off
install_default()
