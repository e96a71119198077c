"""What every job shares: observation of compiles and warnings, named checks,
spans, percentiles, the profiler window and the run itself (``run_cell``).

The compile log and the warning collector are copies of ``chip_smoke.py``'s
(sound there; the benchmark keeps its own so that a later PR cannot change the
yardstick by changing the smoke test)."""

import json
import logging
import math
import os
import shutil
import sys
import time

from . import loader


# ------------------------------------------------------------- observation
class WarningCollector(logging.Handler):
    """WARNING-or-above records of the repo's and jax's loggers."""

    LOGGERS = ("DeepSpeedTPU", "jax")

    def __init__(self):
        super().__init__(level=logging.WARNING)
        self.records = []

    def emit(self, record):
        self.records.append(f"{record.name}: {record.getMessage()}")

    def __enter__(self):
        for name in self.LOGGERS:
            logging.getLogger(name).addHandler(self)
        return self

    def __exit__(self, *exc):
        for name in self.LOGGERS:
            logging.getLogger(name).removeHandler(self)


class CompileLog:
    """Every XLA backend compile of the process, in order, with what the
    persistent cache said about it (``hit`` / ``miss`` / ``off``)."""

    _BACKEND = "/jax/core/compile/backend_compile_duration"
    _VERDICTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}

    def __init__(self):
        self.events = []        # (fun_name, seconds, verdict)
        self._verdict = "off"

    def _on_event(self, event, **kw):
        if event in self._VERDICTS:
            self._verdict = self._VERDICTS[event]

    def _on_duration(self, event, secs, **kw):
        if event == self._BACKEND:
            self.events.append((kw.get("fun_name", "?"), secs,
                                self._verdict))
            self._verdict = "off"

    def __enter__(self):
        from jax import monitoring
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def __exit__(self, *exc):
        from jax import monitoring
        monitoring.unregister_event_listener(self._on_event)
        monitoring.unregister_event_duration_listener(self._on_duration)

    def mark(self):
        return len(self.events)

    def since(self, mark):
        return self.events[mark:]

    def summary(self, mark=0):
        evs = self.since(mark)
        return {"programs": len(evs),
                "hits": sum(v == "hit" for _, _, v in evs),
                "misses": sum(v == "miss" for _, _, v in evs),
                "backend_s": round(sum(s for _, s, _ in evs), 2)}


# ------------------------------------------------------------------ checks
class Checks:
    """Named comparisons that decide ``correct``.  Each prints one line with
    its observed value and its tolerance the moment it is made."""

    def __init__(self):
        self.rows = []

    def at_most(self, name, observed, tolerance, note=""):
        ok = bool(math.isfinite(observed) and observed <= tolerance)
        self._add(name, observed, f"<= {tolerance:g}", ok, note)
        return ok

    def equal(self, name, observed, expected, note=""):
        self._add(name, observed, f"== {expected!r}", observed == expected,
                  note)
        return observed == expected

    def _add(self, name, observed, rule, ok, note):
        self.rows.append({"check": name, "observed": observed, "rule": rule,
                          "pass": ok})
        shown = f"{observed:.6g}" if isinstance(observed, float) else observed
        print(f"CHECK {name}: observed {shown} must be {rule}: "
              f"{'pass' if ok else 'FAIL'}{' (' + note + ')' if note else ''}",
              flush=True)

    @property
    def all_passed(self):
        return bool(self.rows) and all(r["pass"] for r in self.rows)


# ------------------------------------------------------------------- spans
class Spans:
    """Benchmark-side spans on the host clock: name -> [(start, end)], kept
    in every run (two clock reads a span).  With ``annotate`` each span is
    also written into the profiler's trace (as ``pb:<name>``), so that idle
    gaps of the device can be labelled."""

    def __init__(self, annotate=False):
        self.annotate = annotate
        self.data = {}

    def span(self, name):
        return _Span(self, name)

    def slowest(self, since, n=3):
        """The ``n`` longest spans that began at or after ``since``:
        ``[name, seconds, seconds after since]``; says which call a stall of
        the window sat in."""
        rows = [(e - s, name, s - since) for name, v in self.data.items()
                for s, e in v if s >= since]
        return [[name, d, at] for d, name, at in sorted(rows)[::-1][:n]]


class _Span:
    __slots__ = ("spans", "name", "t0", "ann")

    def __init__(self, spans, name):
        self.spans, self.name, self.ann = spans, name, None

    def __enter__(self):
        if self.spans.annotate:
            import jax
            self.ann = jax.profiler.TraceAnnotation("pb:" + self.name)
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.spans.data.setdefault(self.name, []).append((self.t0, t1))
        if self.ann is not None:
            self.ann.__exit__(*exc)


# -------------------------------------------------------------- arithmetic
def percentile(values, q):
    """Linear-interpolated percentile (numpy's default), None when empty;
    after ``tools/serve_bench._pct``."""
    if not values:
        return None
    xs = sorted(float(v) for v in values)
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def fold_seed(seed):
    """A jax PRNG key from any non-negative integer below 2**63."""
    import jax
    seed = int(seed)
    if seed < 0:
        raise ValueError("--seed must be non-negative")
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def weights_seed(ctx):
    """The seed the weights are made from: the configuration's
    ``weights_seed`` where its file states one, else ``--seed``.  A routed
    model's weights decide how many rows its held experts get, and so how much
    work a step is: a configuration whose cells' work moved with the seed
    states one (README.md, "What a seed decides"), and ``--seed`` then makes
    the inputs alone."""
    return int(ctx.config.get("weights_seed", ctx.seed))


# ----------------------------------------------------------------- devices
def device_record(devices):
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


class ProfilerWindow:
    """One ``jax.profiler`` trace of a stretch inside the timed window, kept
    at a fixed path inside the checkout and reduced by ``perfbench.xplane``."""

    def __init__(self, root, cell_name, enabled=True):
        self.dir = os.path.join(root, ".perfbench_trace", cell_name)
        self.enabled = enabled      # off the TPU there is no device to trace
        self.t_start = None
        self.on = False

    def start(self):
        self.t_start = time.perf_counter()
        self.on = True
        if not self.enabled:
            return
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # python frames swamp the trace
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.t_start = time.perf_counter()

    def stop(self):
        self.on = False
        if self.enabled:
            import jax
            jax.profiler.stop_trace()

    def reduce(self, n_devices):
        if not self.enabled:
            return None
        from . import xplane
        path = xplane.find_trace(self.dir)
        if path is None:
            return None
        return xplane.reduce_file(path, n_devices=n_devices)


# --------------------------------------------------------------------- run
class Context:
    """What a job gets: the cell, its two data files, the parts found by
    name, the observers and the clock of the process's start."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def info(self, title, **fields):
        print(f"INFO {title}: " + json.dumps(fields, default=float),
              flush=True)

    def measured_worst(self, check):
        """The worst error measured for ``check`` over this configuration's
        own runs: ``measured_worst[check]["value"]`` of its file.  The jobs
        hold the rule (a factor, a floor); what was measured is data of the
        configuration.  One without the entry for a check its job runs is an
        error that names the key, never a default."""
        where = getattr(self, "config_file", "the configuration")
        try:
            return float(self.config["measured_worst"][check]["value"])
        except KeyError:
            raise KeyError(
                f"{where} has no measured_worst[{check!r}][\"value\"]: run "
                "the check over a dozen seeds, write the worst error there "
                "(perfbench/README.md)") from None


def run_cell(workload, seed, seconds, trace, *, root=loader.ROOT,
             require_tpu=True, t_process_start=None, out=sys.stdout):
    """Run one cell and print the contract's last line.  Returns
    ``(exit_code, result_or_None)``; a non-zero code prints no result line.
    ``require_tpu=False`` is the tests' door to the CPU; the command never
    opens it."""
    if t_process_start is None:
        t_process_start = time.perf_counter()
    manifest = loader.load_manifest(root)
    cell = loader.find(manifest["workloads"], workload, "workload")
    config_entry = loader.find(manifest["configs"], cell["config"], "config")
    config_file = config_entry["file"]
    config = loader.load_json(os.path.join(root, config_file))
    traffic = loader.load_json(
        loader.part_path(root, "traffic", cell["traffic"], "json"))
    peaks_all = loader.load_json(os.path.join(root, "perfbench", "peaks.json"))

    import jax
    if require_tpu:
        # JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache: a
        # fixed path, so that a cell's second run finds every program
        from deepspeed_tpu.utils.compile_cache import enable_compile_cache
        enable_compile_cache()
        # the cache is never evicted, the checkout's own or one that is given:
        # a size cap from the environment (JAX_COMPILATION_CACHE_MAX_SIZE)
        # under the cell's programs makes every run evict what the next needs
        jax.config.update("jax_compilation_cache_max_size", -1)

    devices = jax.devices()
    d0 = devices[0]
    if require_tpu:
        if d0.platform != "tpu":
            print(f"perfbench runs on a TPU only; jax found platform "
                  f"{d0.platform!r} ({d0.device_kind})", file=sys.stderr)
            return 2, None
        if d0.device_kind not in peaks_all:
            print(f"device kind {d0.device_kind!r} is not in "
                  "perfbench/peaks.json", file=sys.stderr)
            return 2, None
        if len(devices) != cell["chips"]:
            print(f"cell {workload} needs {cell['chips']} chip(s), jax found "
                  f"{len(devices)}", file=sys.stderr)
            return 2, None
        devices = devices[:cell["chips"]]
    peaks = peaks_all.get(d0.device_kind)

    job = loader.load_part(root, "jobs", traffic["job"])
    arch = loader.load_part(root, "models", config["arch"])
    reference = loader.load_part(root, "reference", config["arch"])
    print(f"perfbench: cell={workload} config={cell['config']} "
          f"traffic={cell['traffic']} job={traffic['job']} seed={seed} "
          f"seconds={seconds} trace={trace} platform={d0.platform} "
          f"kind={d0.device_kind!r} devices={len(devices)} "
          f"compile_cache={jax.config.jax_compilation_cache_dir} "
          f"cache_max_size={jax.config.jax_compilation_cache_max_size} "
          f"(env {os.environ.get('JAX_COMPILATION_CACHE_MAX_SIZE')})",
          flush=True)

    with WarningCollector() as warnings, CompileLog() as compiles:
        ctx = Context(
            root=root, cell=cell, config=config, config_file=config_file,
            traffic=traffic,
            seed=int(seed), seconds=float(seconds), trace=bool(trace),
            arch=arch, reference=reference, peaks=peaks, devices=devices,
            compiles=compiles, checks=Checks(),
            spans=Spans(annotate=bool(trace) and d0.platform == "tpu"),
            profiler=ProfilerWindow(root, workload,
                                    enabled=d0.platform == "tpu"),
            t_process_start=t_process_start, on_tpu=d0.platform == "tpu")
        record = job.run(ctx)
    for r in warnings.records:
        print(f"WARNING-RECORD {r}")

    record.setdefault("spans", ctx.spans.data)
    record["checks"] = ctx.checks.rows
    record["peaks"] = peaks
    record["device"] = device_record(devices)
    correct = ctx.checks.all_passed and record.get("window_ok", True)

    section = "per_layer" if trace else "end_to_end"
    wanted = loader.metrics_of_cell(manifest, section, workload)
    metrics = {}
    for m in wanted:
        if trace:
            value = loader.load_reader(root, m["name"]).read(record)
        else:
            value = record["end_to_end"].get(m["name"])
        if value is None:
            if trace:
                continue        # a reader that finds nothing returns nothing
            print(f"job {traffic['job']} reported no {m['name']}",
                  file=sys.stderr)
            return 1, None
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    device = dict(record["device"])
    result = {"correct": bool(correct), "attempted": int(record["attempted"]),
              "failed": int(record["failed"]), "metrics": metrics,
              "device": device}
    if trace and record.get("trace"):
        tr = record["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        result["breakdown"] = {"device_ops": tr["top_ops"][:10],
                               "idle_gaps": tr["idle_gaps"][:10]}
    print(json.dumps(result), file=out, flush=True)
    return 0, result
