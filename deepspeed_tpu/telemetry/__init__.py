"""deepspeed_tpu.telemetry — the unified observability spine.

One structured-event model for everything the stack can measure:

* **step traces** (:mod:`.trace`): per-step spans (forward / backward /
  grad-reduce / optimizer / checkpoint) → Chrome-trace JSON + per-step
  JSONL records;
* **comm attribution** (:mod:`.comm_attribution`): wire-truthful bytes from
  ``utils/comms_logging`` joined with span timing → per-``op[variant]``
  latency, effective wire bandwidth, exposed-comm-fraction;
* **live metrics** (:mod:`.metrics`): counters/gauges/histograms with the
  ``monitor/`` backends as sinks plus a Prometheus text endpoint.

One span primitive, :func:`scope`: it ALWAYS writes a ``ds:<name>``
``jax.profiler.TraceAnnotation`` (with its counts as the event's stats), so
any profiler capture shows the program's own spans on the device trace's
clock with no config — under a microsecond a span when no profiler session
is open — and, only when :data:`enabled`, books the same span into the
:class:`TraceRecorder`.  The names are constants in :mod:`.names`.

Everything else stays behind the module-level :data:`enabled` flag, off by
default: no recorder, no file, no registry lookup, no device sync —

    from deepspeed_tpu import telemetry
    if telemetry.enabled:
        telemetry.record_comm_event(...)

one attribute read.  ``configure()`` (called by the engine when the
``telemetry`` config block enables it) flips the flag and builds the
recorder/registry; ``shutdown()`` flushes and flips it back.  This module
must stay import-light: ``comm/comm.py`` imports it at module scope.
"""

from jax.profiler import TraceAnnotation as _Annotation

from . import names  # noqa: F401  (re-export)

from .comm_attribution import (CommAttribution,  # noqa: F401  (re-export)
                               overlap_efficiency)
from .metrics import (MetricsRegistry, MonitorSink,  # noqa: F401
                      PrometheusEndpoint, render_prometheus)
from .trace import (PHASES, SPAN_BACKWARD, SPAN_BUCKET_PREFIX,  # noqa: F401
                    SPAN_CHECKPOINT, SPAN_FORWARD, SPAN_GATHER_PREFIX,
                    SPAN_GRAD_REDUCE, SPAN_OPTIMIZER, STEPS_FILE, TRACE_FILE,
                    TraceRecorder)

#: THE flag every emit site guards on.  Only configure()/shutdown() write it.
enabled = False

_recorder = None
_registry = None
_sinks = []
_endpoint = None
_rank = 0


def get_recorder():
    """The active :class:`TraceRecorder`, or None (metrics-only mode)."""
    return _recorder


def get_registry():
    """The active :class:`MetricsRegistry`, or None when disabled."""
    return _registry


def configure(cfg, monitor=None, rank=0):
    """Enable telemetry from a ``TelemetryConfig``-shaped object (duck-typed:
    ``trace_dir``/``trace_steps``/``fence`` plus a ``metrics`` sub-object).
    Reconfiguring tears the previous instance down first.  Returns
    (recorder, registry)."""
    global enabled, _recorder, _registry, _sinks, _endpoint, _rank
    shutdown()
    _rank = int(rank)
    trace_dir = getattr(cfg, "trace_dir", "") or "telemetry"
    _recorder = TraceRecorder(
        trace_dir,
        fence=getattr(cfg, "fence", False),
        trace_steps=getattr(cfg, "trace_steps", 0),
        rank=_rank)
    _registry = MetricsRegistry()
    _sinks = []
    mc = getattr(cfg, "metrics", None)
    metrics_on = getattr(mc, "enabled", True) if mc is not None else True
    rank0_only = getattr(mc, "rank0_only", True) if mc is not None else True
    exporting = metrics_on and (not rank0_only or _rank == 0)
    if exporting and monitor is not None and \
            getattr(monitor, "enabled", False):
        _sinks.append(MonitorSink(monitor))
    port = getattr(mc, "prometheus_port", 0) if mc is not None else 0
    if exporting and port:
        try:
            _endpoint = PrometheusEndpoint(
                _registry, port, labels={"rank": _rank}).start()
        except OSError as e:
            from ..utils.logging import logger
            logger.warning("telemetry: Prometheus endpoint on port %s "
                           "unavailable (%s); text rendering still works",
                           port, e)
            _endpoint = None
    enabled = True
    return _recorder, _registry


def shutdown():
    """Flush traces, stop the endpoint, drop back to zero-overhead mode."""
    global enabled, _recorder, _registry, _sinks, _endpoint
    enabled = False
    if _endpoint is not None:
        _endpoint.stop()
        _endpoint = None
    if _recorder is not None:
        _recorder.close()
        _recorder = None
    _registry = None
    _sinks = []


# --------------------------------------------------------------- emit helpers
# All assume the caller already checked ``telemetry.enabled`` (the zero-
# overhead contract) but stay safe to call mid-shutdown.

def begin_step(step):
    if _recorder is not None:
        _recorder.begin_step(step)


def end_step(metrics=None):
    """Returns the just-written step record (dict) or None."""
    if _recorder is not None:
        return _recorder.end_step(metrics=metrics)
    return None


def begin_span(name, cat="compute", **args):
    if _recorder is not None:
        _recorder.begin_span(name, cat=cat, **args)


def end_span(name=None):
    if _recorder is not None:
        _recorder.end_span(name)


def span(name, cat="compute", **args):
    """Recorder-only context-manager span (checkpoint engine, tools): no
    profiler annotation.  The hot paths use :func:`scope`."""
    if _recorder is not None:
        return _recorder.span(name, cat=cat, **args)
    import contextlib
    return contextlib.nullcontext()


class Scope(_Annotation):
    """One span of the program: a ``ds:<name>`` profiler annotation (this
    class IS the annotation, so the disabled path adds no wrapper).  A
    context manager; ``begin()`` / ``end()`` are the same for linear call
    sites.  ``set()`` adds counts that are only known once the work is
    under way."""

    __slots__ = ()

    def begin(self):
        self.__enter__()
        return self

    def end(self):
        self.__exit__(None, None, None)

    def set(self, phase=None, **counts):
        self.set_metadata(**counts)


class _RecordedScope(Scope):
    """A :class:`Scope` that also books the span, with the same counts,
    into the :class:`TraceRecorder` (telemetry enabled)."""

    __slots__ = ("_span", )

    def __init__(self, name, phase, cat, counts):
        super().__init__(names.SPAN_PREFIX + name, **counts)
        self._span = _recorder.span(phase or name, cat=cat, **counts)

    def __enter__(self):
        super().__enter__()
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        self._span.__exit__(*exc)
        return super().__exit__(*exc)

    def set(self, phase=None, **counts):
        self.set_metadata(**counts)
        span = self._span
        if phase is not None:
            span.name = phase
        span.args = {**span.args, **counts} if span.args else counts


def scope(name, phase=None, cat="compute", **counts):
    """``with telemetry.scope(names.TRAIN_MICRO, step=3): ...`` — always a
    ``ds:<name>`` ``jax.profiler.TraceAnnotation`` with the counts as its
    stats; only when telemetry is enabled, the same span in the
    :class:`TraceRecorder` too.  ``phase`` is the name the recorder books
    it under (its documented phase columns predate the ``ds:`` names);
    default the scope's own name.  Counts are ints or strings."""
    if enabled and _recorder is not None:
        return _RecordedScope(name, phase, cat, counts)
    return Scope(names.SPAN_PREFIX + name, **counts)


def mark(name, **counts):
    """A span of no length: one request event (``ds:serve.admitted``)."""
    scope(name, cat="event", **counts).begin().end()


def record_comm_event(op, variant, msg_bytes, wire_bytes, latency_s,
                      world_size=1, exposed=True):
    if _recorder is not None:
        _recorder.comm_event(op, variant, msg_bytes, wire_bytes, latency_s,
                             world_size, exposed=exposed)


def record_hbm(stats):
    """Device-memory snapshot (live/peak/limit bytes) into the open step
    window — the ``hbm`` section of the step record (the engine samples
    ``memory_stats()`` on the boundary sync it already pays for)."""
    if _recorder is not None:
        _recorder.hbm_stat(stats)


def record_moe_stats(layer, stats):
    """Per-layer routed-token accounting (drop fraction, overflow, expert
    load imbalance, aux loss) into the open step window — the ``moe``
    section of the step record (``moe/engine.record_routing`` emits)."""
    if _recorder is not None:
        _recorder.moe_stat(layer, stats)


def metadata(name, payload):
    if _recorder is not None:
        _recorder.metadata(name, payload)


def counter(name, help=""):
    return _registry.counter(name, help=help) if _registry is not None \
        else None


def gauge(name, help=""):
    return _registry.gauge(name, help=help) if _registry is not None \
        else None


def observe(name, value, help="", buckets=None):
    """Histogram observation (checkpoint/save durations etc.)."""
    if _registry is None:
        return
    from .metrics import DEFAULT_BUCKETS
    h = _registry.histogram(name, help=help,
                            buckets=buckets or DEFAULT_BUCKETS)
    h.observe(value)


def export_metrics(step=0):
    """Push the registry through the configured sinks (engine calls this at
    its ``steps_per_print`` cadence on the exporting rank)."""
    if _registry is not None and _sinks:
        _registry.export(_sinks, step=step)


def prometheus_text():
    """Render the live registry in Prometheus exposition format."""
    if _registry is None:
        return ""
    return render_prometheus(_registry, labels={"rank": _rank})
