"""Structured step traces: spans → Chrome-trace JSON + per-step JSONL.

The :class:`TraceRecorder` is the event spine every subsystem emits into
(engine phases, collectives, checkpoint engine, watchdog).  Two outputs:

* ``trace.json`` — Chrome trace-event format (load in ``chrome://tracing``
  or https://ui.perfetto.dev): one complete-event (``"ph": "X"``) per span,
  comm ops on their own track, written on :meth:`close` (and at interpreter
  exit as a backstop);
* ``steps.jsonl`` — one compact JSON record per optimizer step, appended as
  the step ends: wall time, per-phase breakdown, per-``op[variant]`` comm
  attribution with the exposed-comm-fraction estimate, and engine metrics
  (loss, grad norm, throughput).  This is what ``tools/trace_report.py``
  and the future autotuner ingest.

Timing is host wall time (``time.perf_counter``).  With ``fence=True`` the
recorder blocks on the accelerator at phase boundaries, so phase times are
CPU-accurate attributions instead of async-dispatch shadows — the same
trade ``comms_logger.sync_timing`` makes, documented in
docs/observability.md.  The recorder's clock is not the device's: for
device-side truth read the ``ds:`` annotations that ``telemetry.scope``
writes into any ``jax.profiler`` capture (they need no recorder and no
config; ``telemetry/names.py``).
"""

import atexit
import json
import os
import sys
import time

from ..utils.logging import logger
from .comm_attribution import (CommAttribution, exposed_fraction,
                               overlap_efficiency)

# canonical phase names — the engine emits exactly these, and
# tools/trace_report.py columns key off them
SPAN_FORWARD = "forward"
SPAN_BACKWARD = "backward"
SPAN_GRAD_REDUCE = "grad_reduce"
SPAN_OPTIMIZER = "optimizer"
SPAN_CHECKPOINT = "checkpoint"

PHASES = (SPAN_FORWARD, SPAN_BACKWARD, SPAN_GRAD_REDUCE, SPAN_OPTIMIZER,
          SPAN_CHECKPOINT)

#: per-bucket reduce spans render as ``bucket_reduce/<index>`` — their own
#: namespace (the ``overlap`` section of the step record), never a phase
#: column (the overlap bench and eager bucket paths emit them; a fully
#: jitted step has none — its buckets live inside the compiled graph and
#: are visible only as trace metadata + HLO structure)
SPAN_BUCKET_PREFIX = "bucket_reduce"
#: forward-direction twin: per-bucket param-gather prefetch spans render
#: as ``param_gather/<index>`` in the same ``overlap`` namespace
SPAN_GATHER_PREFIX = "param_gather"
_BUCKET_SPAN_PREFIXES = (SPAN_BUCKET_PREFIX + "/", SPAN_GATHER_PREFIX + "/")

TRACE_FILE = "trace.json"
STEPS_FILE = "steps.jsonl"

#: chrome-trace keys every complete event must carry (schema contract the
#: unit tests and trace_report validate against)
CHROME_EVENT_KEYS = ("name", "ph", "ts", "dur", "pid", "tid")

_COMM_TID = 1  # comm ops render on their own track under each pid


def _sync_device():
    """Block until the accelerator drains (fence mode)."""
    from ..accelerator import get_accelerator
    get_accelerator().synchronize()


class _SpanHandle:
    """Context manager for one span; also usable via explicit begin/end."""

    __slots__ = ("_rec", "name", "cat", "args", "_t0")

    def __init__(self, rec, name, cat, args):
        self._rec = rec
        self.name = name
        self.cat = cat
        self.args = args
        self._t0 = None

    def __enter__(self):
        self._rec._begin(self)
        return self

    def __exit__(self, *exc):
        self._rec._end(self)
        return False


class TraceRecorder:

    def __init__(self, trace_dir, fence=False, trace_steps=0, rank=0,
                 max_events=200_000, sync_fn=_sync_device):
        self.trace_dir = os.path.abspath(trace_dir)
        self.fence = bool(fence)
        self.trace_steps = int(trace_steps)  # 0 = unbounded
        self.rank = int(rank)
        self.max_events = int(max_events)
        self._sync = sync_fn
        self._epoch = time.perf_counter()
        self._events = []            # chrome complete events
        self._meta = {}              # metadata blobs (zero plan, config, …)
        self._dropped = 0
        self._stack = []             # open _SpanHandle frames
        self._steps_file = None
        self._closed = False
        # per-step state
        self._step = None
        self._step_t0 = None
        self._phase_s = {}
        self._bucket_s = {}
        self._moe_s = {}             # layer → accumulated routing stats
        self._hbm = None             # memory_stats snapshot for the step
        self._step_comm = CommAttribution()
        self.steps_recorded = 0
        os.makedirs(self.trace_dir, exist_ok=True)
        atexit.register(self.close)

    # ------------------------------------------------------------- internals
    def _now_us(self):
        return (time.perf_counter() - self._epoch) * 1e6

    def _emit(self, name, cat, ts_us, dur_us, tid=0, args=None):
        if len(self._events) >= self.max_events:
            self._dropped += 1
            return
        ev = {"name": name, "cat": cat, "ph": "X", "ts": ts_us,
              "dur": dur_us, "pid": self.rank, "tid": tid}
        if args:
            ev["args"] = args
        self._events.append(ev)

    @property
    def recording(self):
        """False once the trace_steps budget is spent — emit sites stay
        cheap because the engine stops opening steps."""
        return not self._closed and (
            self.trace_steps <= 0 or self.steps_recorded < self.trace_steps)

    # ----------------------------------------------------------------- spans
    def span(self, name, cat="compute", **args):
        """``with recorder.span("forward"): ...`` — spans nest; every span
        feeds the per-step phase breakdown by name, so a nested phase
        (``grad_reduce`` inside ``backward``) reports its own time AND is
        contained in its parent's — phase columns are attributions, not a
        partition of the wall time."""
        return _SpanHandle(self, name, cat, args or None)

    def begin_span(self, name, cat="compute", **args):
        """Explicit-begin variant for linear call sites (engine hot path);
        pair with :meth:`end_span`."""
        h = _SpanHandle(self, name, cat, args or None)
        self._begin(h)
        return h

    def end_span(self, name=None):
        """Close the innermost open span (``name`` asserts intent; a
        mismatch is logged, never raised — telemetry must not kill a
        step)."""
        if not self._stack:
            logger.warning("telemetry: end_span(%r) with no open span", name)
            return
        h = self._stack[-1]
        if name is not None and h.name != name:
            logger.warning("telemetry: end_span(%r) closes open span %r",
                           name, h.name)
        self._end(h)

    def _begin(self, h):
        if self.fence:
            self._sync()
        self._stack.append(h)
        h._t0 = time.perf_counter()

    def _end(self, h):
        if self.fence:
            self._sync()
        t1 = time.perf_counter()
        try:
            depth = self._stack.index(h)
        except ValueError:
            return  # already closed
        # close anything left open underneath (exception unwound past it)
        del self._stack[depth:]
        dur = t1 - h._t0
        self._emit(h.name, h.cat, (h._t0 - self._epoch) * 1e6, dur * 1e6,
                   args=h.args)
        if self._step is not None:
            if h.name.startswith(_BUCKET_SPAN_PREFIXES):
                self._bucket_s[h.name] = self._bucket_s.get(h.name, 0.0) \
                    + dur
            else:
                self._phase_s[h.name] = self._phase_s.get(h.name, 0.0) + dur

    # ----------------------------------------------------------------- steps
    def begin_step(self, step):
        """Open the per-step record window.  Idempotent for the same step
        index (forward() calls it once per micro-batch)."""
        if self._step == step or not self.recording:
            return
        if self._step is not None:
            self.end_step()   # unterminated previous window: flush it
        self._step = step
        self._step_t0 = time.perf_counter()
        self._phase_s = {}
        self._bucket_s = {}
        self._moe_s = {}
        self._hbm = None
        self._step_comm.reset()

    def end_step(self, metrics=None):
        """Close the step window: emit the chrome step event and append one
        JSONL record.  ``metrics`` is a flat dict of engine numbers (loss,
        grad_norm, throughput, …) copied into the record verbatim."""
        if self._step is None:
            return
        if self.fence:
            self._sync()
        wall_s = time.perf_counter() - self._step_t0
        step = self._step
        self._step = None
        self._emit(f"step {step}", "step",
                   (self._step_t0 - self._epoch) * 1e6, wall_s * 1e6,
                   tid=2, args={"step": step})
        exposed_s = self._step_comm.total_seconds()
        hidden_s = self._step_comm.hidden_seconds()
        record = {
            "step": step,
            "wall_ms": wall_s * 1e3,
            "phases": {k: v * 1e3 for k, v in sorted(self._phase_s.items())},
            "comm": {
                "total_ms": (exposed_s + hidden_s) * 1e3,
                "exposed_ms": exposed_s * 1e3,
                "hidden_ms": hidden_s * 1e3,
                "exposed_comm_fraction": exposed_fraction(exposed_s, wall_s),
                "overlap_efficiency": overlap_efficiency(
                    hidden_s, exposed_s + hidden_s),
                "ops": self._step_comm.summary(),
            },
        }
        if self._hbm:
            record["hbm"] = self._hbm
        if self._bucket_s:
            record["overlap"] = {
                "buckets": len(self._bucket_s),
                "bucket_ms": {k: v * 1e3
                              for k, v in sorted(self._bucket_s.items())},
            }
        if self._moe_s:
            layers = {}
            for name, acc in sorted(self._moe_s.items()):
                n = max(1, acc.pop("_n", 1))
                vec_n = {k[3:]: max(1, acc.pop(k))
                         for k in [k for k in acc if k.startswith("_n_")]}
                layers[name] = {
                    k: (v if k == "k"
                        else ([x / vec_n.get(k, n) for x in v]
                              if isinstance(v, list) else v / n))
                    for k, v in acc.items()}
            # aggregate defensively: a client may book a partial stats
            # payload, and telemetry must never kill a step over it
            record["moe"] = {
                "layers": layers,
                "drop_fraction_mean": (sum(l.get("drop_fraction", 0.0)
                                           for l in layers.values())
                                       / len(layers)),
                "load_imbalance_max": max(l.get("load_imbalance", 0.0)
                                          for l in layers.values()),
                "aux_loss_total": sum(l.get("aux_loss", 0.0)
                                      for l in layers.values()),
            }
        if metrics:
            metrics = {k: v for k, v in metrics.items() if v is not None}
            # MFU is derived HERE because the recorder owns the step wall
            # clock: achieved per-chip flops/s ÷ per-chip peak.  Both
            # inputs ride the metrics dict (the engine's compiled-cost
            # registry supplies them) so the spine needs no profiler
            # import; absent inputs → no mfu key (refuse, don't guess).
            sf = metrics.get("step_flops_per_chip")
            peak = metrics.get("peak_flops_per_chip")
            if sf and peak and wall_s > 0 and "mfu" not in metrics:
                metrics["mfu"] = sf / wall_s / peak
            record["metrics"] = metrics
        self._append_step_record(record)
        self.steps_recorded += 1
        return record

    def _append_step_record(self, record):
        try:
            if self._steps_file is None:
                self._steps_file = open(
                    os.path.join(self.trace_dir, STEPS_FILE), "a")
            self._steps_file.write(json.dumps(record) + "\n")
            self._steps_file.flush()
        except (OSError, ValueError, TypeError) as e:
            logger.warning("telemetry: step record write failed (%s)", e)

    # ------------------------------------------------------------ comm + meta
    def bucket_span(self, index, kind=SPAN_BUCKET_PREFIX, **args):
        """Span for one bucket's eager collective — ``kind`` picks the
        direction namespace (``bucket_reduce`` for the backward gradient
        reduce, ``param_gather`` for the forward prefetch).  Lands in the
        step record's ``overlap`` section, not the phase columns."""
        return self.span(f"{kind}/{index}", cat="comm", **args)

    def hbm_stat(self, stats):
        """Attach the step-boundary device-memory snapshot to the open step
        window — the ``hbm`` section of the step record (``live_bytes`` /
        ``peak_bytes`` / ``limit_bytes`` from the accelerator's
        ``memory_stats()``, sampled on the boundary sync telemetry already
        pays for)."""
        if self._closed or self._step is None or not stats:
            return
        clean = {}
        for key, val in stats.items():
            try:
                clean[str(key)] = int(val)
            except (TypeError, ValueError):
                continue   # telemetry must never kill a step over a stat
        if clean:
            self._hbm = clean

    def moe_stat(self, layer, stats):
        """Accumulate one MoE layer's routed-token stats into the open step
        window (mean over the gas window's micro-batches at end_step).
        ``stats``: drop_fraction / overflow_tokens / load_imbalance /
        aux_loss floats plus the integer ``k``."""
        if self._closed or self._step is None:
            return
        acc = self._moe_s.setdefault(str(layer), {"_n": 0})
        acc["_n"] += 1
        for key, val in stats.items():
            if key == "k":
                acc["k"] = int(val)
            elif isinstance(val, (list, tuple)):
                # vector stats (per-expert capacity utilization) mean
                # elementwise over the gas window, like the scalars —
                # with their OWN call count (a vector present in only
                # some window calls must not be diluted by _n), and a
                # length change (resized expert group) restarts the sum
                # instead of zip-truncating silently
                vals = [float(v) for v in val]
                prev = acc.get(key)
                if isinstance(prev, list) and len(prev) == len(vals):
                    acc[key] = [a + b for a, b in zip(prev, vals)]
                    acc[f"_n_{key}"] += 1
                else:
                    acc[key] = vals
                    acc[f"_n_{key}"] = 1
            else:
                acc[key] = acc.get(key, 0.0) + float(val)

    def comm_event(self, op, variant, msg_bytes, wire_bytes, latency_s,
                   world_size=1, exposed=True):
        """One eager collective: chrome event on the comm track + join into
        the per-step attribution.  ``exposed=False`` books
        the latency as hidden (overlapped-under-compute) comm time — it
        feeds ``overlap_efficiency`` instead of the exposed fraction."""
        if self._closed:
            return
        name = f"{op}[{variant}]" if variant else op
        t1 = time.perf_counter()
        self._emit(name, "comm", (t1 - latency_s - self._epoch) * 1e6,
                   latency_s * 1e6, tid=_COMM_TID,
                   args={"msg_bytes": int(msg_bytes),
                         "wire_bytes": int(wire_bytes if wire_bytes
                                           is not None else msg_bytes),
                         "exposed": bool(exposed)})
        if self._step is not None:
            self._step_comm.record(op, variant, msg_bytes, wire_bytes,
                                   latency_s, world_size, exposed=exposed)

    def metadata(self, name, payload):
        """Attach a structured metadata blob (zero plan, mesh, config hash);
        lands under ``otherData`` in the chrome trace."""
        try:
            json.dumps(payload)
        except (TypeError, ValueError):
            payload = repr(payload)
        self._meta[str(name)] = payload

    # ---------------------------------------------------------------- output
    def chrome_trace(self):
        other = dict(self._meta)
        other["rank"] = self.rank
        if self._dropped:
            other["dropped_events"] = self._dropped
        return {"traceEvents": list(self._events),
                "displayTimeUnit": "ms",
                "otherData": other}

    def write_chrome_trace(self, path=None):
        path = path or os.path.join(self.trace_dir, TRACE_FILE)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.chrome_trace(), f)
        os.replace(tmp, path)
        return path

    def close(self):
        """Flush both outputs.  Safe to call twice (atexit backstop)."""
        if self._closed:
            return
        if self._step is not None:
            self.end_step()
        self._closed = True
        atexit.unregister(self.close)  # bound-method equality: this entry
        if self._dropped and not sys.is_finalizing():
            logger.warning("telemetry: dropped %d trace events past the "
                           "max_events=%d cap", self._dropped,
                           self.max_events)
        try:
            self.write_chrome_trace()
        except OSError as e:
            logger.warning("telemetry: chrome trace write failed (%s)", e)
        if self._steps_file is not None:
            try:
                self._steps_file.close()
            except OSError:
                pass
            self._steps_file = None
