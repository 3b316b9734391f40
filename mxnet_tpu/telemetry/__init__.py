"""Runtime telemetry: structured events, counters, and exporters.

The reference MXNet answers "why is this step slow?" with an
engine-integrated profiler (``src/profiler/``): every op lands in a chrome
trace plus an aggregate table.  On TPU the per-op story belongs to
``jax.profiler`` (XPlane traces of the fused executables — see
``mxnet_tpu/profiler.py``); what the XPlane trace *cannot* show is the
framework-level cause of a slow step: a silent CachedOp recompile, an eager
jit-cache miss storm, KVStore push volume, or an input pipeline stall.  This
subsystem records exactly those.

Usage::

    import mxnet_tpu as mx
    mx.telemetry.enable()            # or MXNET_TELEMETRY=1 in the env
    ... train ...
    mx.telemetry.snapshot()          # dict: counters/gauges/span aggregates
    mx.telemetry.dump_trace("t.json")   # chrome://tracing / perfetto
    print(mx.telemetry.dump_metrics())  # Prometheus text exposition

Instrumented subsystems (event-name prefix = subsystem):

- ``dispatch.*``  — eager op calls, per-op jit-cache hits/misses/compiles
  (``ndarray/ndarray.py``)
- ``cachedop.*``  — hybridized-block recompiles with the
  shape/dtype/training-flag key that triggered them (``gluon/block.py``)
- ``trainer.*``   — per-step spans, donated-buffer bytes, collective
  payload bytes from the lowered HLO (``parallel/trainer.py``,
  ``gluon/trainer.py``)
- ``kvstore.*``   — push/pull call counts and payload bytes
- ``optimizer.*`` — aggregated-update group spans, dispatch counts,
  group-signature compile misses, state bytes (``optimizer/aggregate.py``)
- ``checkpoint.*``— save/restore spans with bytes and serialize-vs-IO
  split (``gluon/trainer.py``, ``parallel/checkpoint.py``)
- ``io.*``        — prefetch producer/consumer wait (host-bound shows up
  as a number) and ``ImageRecordIter``'s internal decode-pool waits
- ``serving.*``   — inference runtime: request queue waits, micro-batch
  runs, padding waste, compile misses, rejections
  (``mxnet_tpu/serving/``)
- ``engine.*``    — ``engine.bulk`` scopes (reference bulking intent)
- ``jax.*``       — backend compilations via ``jax.monitoring``

Three observability layers ride on the bus (PR 15):

- ``telemetry.trace`` — request/step-scoped trace contexts propagated
  across threads and (simulated-)host processes; ``chrome_trace()`` is
  the merged multi-lane timeline with parent→child flow links.
- ``telemetry.flight`` — always-on fixed-size flight recorder, dumped to
  a post-mortem file when a sanitizer violation / nan rollback / SIGTERM
  preemption fires.
- ``telemetry.http`` — opt-in ``/metrics`` + ``/healthz`` + ``/trace``
  endpoint (``MXNET_METRICS_PORT`` or ``start_server()``).

Everything is off by default (flight recording excepted — it exists for
the crash nobody armed telemetry for); when disabled each site costs one
module attribute read.
"""
from . import bus  # noqa: F401
from . import exporters  # noqa: F401
from . import flight  # noqa: F401
from . import jax_hooks  # noqa: F401
from . import sampler  # noqa: F401

# trace imports bus+exporters and lazily touches analysis.divergence;
# keep it after the core modules so import order stays cycle-free.
from . import trace  # noqa: F401
from . import http  # noqa: F401
from .bus import (  # noqa: F401
    count,
    counter_sample,
    counter_value,
    disable,
    enable,
    gauge,
    histogram_quantile,
    histograms,
    instant,
    is_enabled,
    observe,
    record_span,
    reset,
    snapshot,
    span,
    span_aggregates,
)
from .exporters import dump_metrics, dump_trace, trace_events  # noqa: F401
from .http import (  # noqa: F401
    register_health,
    server_port,
    start_server,
    stop_server,
    unregister_health,
)
from .jax_hooks import collective_stats, record_collectives  # noqa: F401
from .sampler import (  # noqa: F401
    sampler_running,
    start_counter_sampler,
    stop_counter_sampler,
)
from .trace import TraceContext, chrome_trace  # noqa: F401

__all__ = [
    "enable", "disable", "is_enabled", "reset", "snapshot",
    "span", "count", "gauge", "instant", "counter_sample", "counter_value",
    "record_span", "observe", "histogram_quantile", "histograms",
    "span_aggregates", "dump_trace", "dump_metrics", "trace_events",
    "TraceContext", "chrome_trace",
    "start_server", "stop_server", "server_port",
    "register_health", "unregister_health",
    "collective_stats", "record_collectives",
    "start_counter_sampler", "stop_counter_sampler", "sampler_running",
    "bus", "exporters", "flight", "trace", "http", "jax_hooks", "sampler",
]
