"""Compiled-cost observability: XLA's own cost model on the telemetry spine.

On TPU the two numbers every training report leads with — model-FLOPs
utilization and HBM headroom — are free: the compiled executable already
knows them.  ``jit(fn).lower(...).compile()`` exposes

* ``cost_analysis()`` — XLA's post-fusion flop and bytes-accessed estimate
  of the optimized per-device program (the number MFU should use, not an
  analytic pre-fusion walk);
* ``memory_analysis()`` — argument / output / temp / generated-code bytes
  of the per-device program, i.e. a **static peak-HBM estimate** available
  at compile time, before the first step can OOM.

This module captures both **once per compile** for every program the stack
owns (training micro-step and its overlap/prefetch/qgZ variants, the
boundary apply-update, serving prefill/decode) into a process-wide
:class:`CostModelRegistry`, with zero steady-state overhead: nothing runs
per step, only per compile.  The engine feeds the registry into the
telemetry spine (``mfu`` on step records, the compiled-programs table in
``tools/trace_report.py``) and a loud once-per-program OOM-margin warning
fires when the static estimate approaches ``total_memory()``.

A program that fails to lower, compile or accept a call raises: the AOT
executable *is* the program that trains, so there is nothing to fall back to
that would not be a second compile hiding a layout bug.

The analytic jaxpr walk below serves the per-module breakdown of
``flops_profiler/`` (a façade over this module since PR 14) — XLA's cost
model has no module tree.
"""

import os
import time
from collections import defaultdict

import numpy as np

from ..utils.logging import logger

# --------------------------------------------------------------- peak FLOPS
#: THE per-chip peak dense bf16 FLOP/s table (Google Cloud TPU documentation,
#: per-generation system-architecture pages), matched against
#: ``jax.devices()[0].device_kind`` by lowercase substring, longest match
#: wins.  A device kind that is not here is an error, not a default.
PEAK_FLOPS_BY_KIND = (
    ("tpu v6", 918e12),      # Trillium / v6e
    ("tpu v5p", 459e12),
    ("tpu v5 lite", 197e12),  # v5e device_kind spelling
    ("tpu v5e", 197e12),
    ("tpu v5", 459e12),
    ("tpu v4", 275e12),
    ("tpu v3", 123e12),
    ("tpu v2", 46e12),
    # nominal host-CPU figure so the tier-1 telemetry tests (which run on
    # the CPU mesh) get a finite MFU.  Not a device number: chip_smoke.py
    # and perfbench/run.py refuse to run on a CPU before they could reach it.
    ("cpu", 1e11),
)

PEAK_FLOPS_ENV = "DS_TPU_PEAK_FLOPS"


def peak_flops_for_kind(device_kind):
    """Table lookup for one ``device_kind`` string; unknown kinds raise."""
    kind = str(device_kind).lower()
    best, best_len = None, -1
    for frag, peak in PEAK_FLOPS_BY_KIND:
        if frag in kind and len(frag) > best_len:
            best, best_len = peak, len(frag)
    if best is None:
        raise KeyError(
            f"no peak-FLOPS entry for device kind {device_kind!r} — add it "
            f"to cost_model.PEAK_FLOPS_BY_KIND with its source (or set "
            f"{PEAK_FLOPS_ENV} for an unlisted part)")
    return best


def peak_flops_per_chip():
    """Per-chip peak FLOP/s of the default device: ``DS_TPU_PEAK_FLOPS``
    (a positive float) when set, else the table."""
    env = os.environ.get(PEAK_FLOPS_ENV)
    if env:
        v = float(env)
        if not v > 0:
            raise ValueError(f"{PEAK_FLOPS_ENV}={env!r} must be positive")
        return v
    import jax
    return peak_flops_for_kind(jax.devices()[0].device_kind)


# ------------------------------------------------------ analytic jaxpr walk
# (the per-scope module breakdown behind flops_profiler/)
_ELEMENTWISE_1 = {
    "add", "sub", "mul", "div", "max", "min", "pow", "and", "or", "xor",
    "neg", "abs", "floor", "ceil", "round", "sign", "select_n",
    "clamp", "rem", "nextafter",
}
_ELEMENTWISE_TRANSCENDENTAL = {
    "exp", "log", "log1p", "expm1", "sin", "cos", "tan", "tanh", "logistic",
    "erf", "erfc", "erf_inv", "rsqrt", "sqrt", "cbrt", "atan2", "sigmoid",
}
_REDUCE = {"reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
           "reduce_and", "reduce_or", "argmax", "argmin", "cumsum",
           "cumlogsumexp", "cummax", "cummin", "cumprod"}


def _out_size(eqn):
    if not eqn.outvars:
        return 0
    v = eqn.outvars[0]
    aval = getattr(v, "aval", None)
    if aval is None or not hasattr(aval, "shape"):
        return 0
    return int(np.prod(aval.shape)) if aval.shape else 1


def _dot_general_flops(eqn):
    a, b = eqn.invars[0].aval, eqn.invars[1].aval
    dnums = eqn.params["dimension_numbers"]
    (lc, rc), (lb, rb) = dnums
    batch = int(np.prod([a.shape[i] for i in lb])) if lb else 1
    contract = int(np.prod([a.shape[i] for i in lc])) if lc else 1
    m = int(np.prod([a.shape[i] for i in range(a.ndim)
                     if i not in set(lc) | set(lb)]))
    n = int(np.prod([b.shape[i] for i in range(b.ndim)
                     if i not in set(rc) | set(rb)]))
    return 2 * batch * m * n * contract


def _conv_flops(eqn):
    rhs = eqn.invars[1].aval
    out = eqn.outvars[0].aval
    # out_elems * (2 * kernel_spatial * in_channels/groups); rhs layout
    # (out_c, in_c/g, *spatial) in dimension_numbers-normalized form
    kernel_elems = int(np.prod(rhs.shape[2:])) if rhs.ndim > 2 else 1
    in_c_per_group = rhs.shape[1] if rhs.ndim > 1 else 1
    return 2 * int(np.prod(out.shape)) * kernel_elems * in_c_per_group


def _eqn_flops(eqn):
    """(flops, macs) for one jaxpr equation."""
    prim = eqn.primitive.name
    if prim == "dot_general":
        f = _dot_general_flops(eqn)
        return f, f // 2
    if prim in ("conv_general_dilated", ):
        f = _conv_flops(eqn)
        return f, f // 2
    if prim in _ELEMENTWISE_1:
        return _out_size(eqn), 0
    if prim in _ELEMENTWISE_TRANSCENDENTAL:
        return 4 * _out_size(eqn), 0  # transcendental ≈ several flops each
    if prim in _REDUCE:
        size = eqn.invars[0].aval
        n = int(np.prod(size.shape)) if hasattr(size, "shape") and size.shape else 1
        return n, 0
    if prim == "integer_pow":
        return _out_size(eqn), 0
    return 0, 0


def _walk_jaxpr(jaxpr, scale=1, scope="", acc=None):
    """Recursively accumulate (flops, macs) per scope from a jaxpr."""
    if acc is None:
        acc = defaultdict(lambda: [0, 0])
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        # nested jaxprs
        if prim == "scan":
            inner = eqn.params["jaxpr"].jaxpr
            _walk_jaxpr(inner, scale * eqn.params.get("length", 1),
                        scope, acc)
            continue
        if prim == "while":
            inner = eqn.params["body_jaxpr"].jaxpr
            _walk_jaxpr(inner, scale, scope, acc)  # trip count unknown: 1×
            continue
        if prim == "cond":
            branches = eqn.params.get("branches", ())
            if branches:  # count the largest branch
                best = defaultdict(lambda: [0, 0])
                for br in branches:
                    tmp = _walk_jaxpr(br.jaxpr, scale, scope,
                                      defaultdict(lambda: [0, 0]))
                    if sum(v[0] for v in tmp.values()) > \
                            sum(v[0] for v in best.values()):
                        best = tmp
                for k, v in best.items():
                    acc[k][0] += v[0]
                    acc[k][1] += v[1]
            continue
        if prim in ("pjit", "closed_call", "custom_jvp_call",
                    "custom_vjp_call", "custom_vjp_call_jaxpr", "remat",
                    "checkpoint", "custom_partitioning", "shard_map"):
            sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr") \
                or eqn.params.get("fun_jaxpr")
            if sub is not None:
                inner = sub.jaxpr if hasattr(sub, "jaxpr") else sub
                name = eqn.params.get("name", "")
                sub_scope = f"{scope}/{name}" if name and name != "<lambda>" \
                    else scope
                _walk_jaxpr(inner, scale, sub_scope, acc)
            continue
        f, m = _eqn_flops(eqn)
        if f:
            # group by name stack when present (flax module scopes)
            st = str(eqn.source_info.name_stack) if hasattr(
                eqn.source_info, "name_stack") else ""
            key = f"{scope}/{st}" if st else (scope or "/")
            acc[key][0] += f * scale
            acc[key][1] += m * scale
    return acc


def jaxpr_flops(fn, *args, **kwargs):
    """(total_flops, total_macs, per_scope dict) for fn(*args) by analytic
    jaxpr walk — the per-module breakdown (XLA's cost model has no module
    tree; flax name stacks do)."""
    import jax
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    acc = _walk_jaxpr(closed.jaxpr)
    total_f = sum(v[0] for v in acc.values())
    total_m = sum(v[1] for v in acc.values())
    return total_f, total_m, {k: tuple(v) for k, v in acc.items()}


# ------------------------------------------------------------ compiled cost
def analyze_compiled(compiled):
    """Extract {flops, bytes_accessed, *_bytes, peak_hbm_bytes} from a
    ``Compiled`` object.  Per-DEVICE numbers (the compiled executable is
    the per-partition SPMD program).  A figure XLA does not report for this
    program comes back None."""
    out = {"flops": None, "bytes_accessed": None, "source": None}
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    ca = ca or {}
    f = ca.get("flops")
    if f is not None and f >= 0:
        out["flops"] = float(f)
        out["source"] = "xla"
    b = ca.get("bytes accessed")
    if b is not None and b >= 0:
        out["bytes_accessed"] = float(b)
    ma = compiled.memory_analysis()
    arg = int(ma.argument_size_in_bytes)
    outb = int(ma.output_size_in_bytes)
    tmp = int(ma.temp_size_in_bytes)
    gen = int(ma.generated_code_size_in_bytes)
    alias = int(ma.alias_size_in_bytes)
    out.update(argument_bytes=arg, output_bytes=outb, temp_bytes=tmp,
               generated_code_bytes=gen, alias_bytes=alias,
               # static peak estimate: everything resident at once, minus
               # donated outputs that alias their argument buffers
               peak_hbm_bytes=max(0, arg + outb + tmp + gen - alias))
    return out


# --------------------------------------------------------------- the registry
class CompiledProgram:
    """One captured program: the executable, its XLA cost/memory analysis
    and a call count.  ``compiled`` is the ``jax.stages.Compiled`` that
    runs (``as_text()`` is its optimized HLO) — chip_smoke.py reads it to
    see which kernels and collectives the program really holds."""

    __slots__ = ("name", "compiled", "analysis", "flops", "peak_hbm_bytes",
                 "calls", "meta", "captured_at")

    def __init__(self, name, analysis, meta=None, compiled=None):
        self.name = name
        self.compiled = compiled
        self.analysis = dict(analysis)
        self.flops = self.analysis.get("flops")
        self.peak_hbm_bytes = self.analysis.get("peak_hbm_bytes")
        self.calls = 0
        self.meta = dict(meta or {})
        self.captured_at = time.time()

    def describe(self):
        d = {"name": self.name, "calls": int(self.calls)}
        d.update({k: self.analysis.get(k) for k in
                  ("flops", "bytes_accessed", "argument_bytes",
                   "output_bytes", "temp_bytes", "generated_code_bytes",
                   "peak_hbm_bytes", "source")})
        if self.meta:
            d["meta"] = self.meta
        return d


class CostModelRegistry:
    """Process-wide table of captured programs.  ``version`` bumps on every
    record so consumers (trace metadata refresh) can diff cheaply."""

    def __init__(self):
        self._programs = {}
        self.version = 0

    def record(self, name, analysis, meta=None, compiled=None):
        entry = CompiledProgram(name, analysis, meta=meta,
                                compiled=compiled)
        self._programs[name] = entry
        self.version += 1
        return entry

    def get(self, name):
        return self._programs.get(name)

    def programs(self):
        return list(self._programs.values())

    def describe(self):
        """JSON-safe list, insertion-ordered — the compiled-programs table
        trace_report renders from the chrome trace's otherData."""
        return [p.describe() for p in self._programs.values()]

    def total_flops_executed(self):
        """Σ flops × calls over programs with a known flop count."""
        total = 0.0
        any_known = False
        for p in self._programs.values():
            if p.flops is not None and p.calls:
                total += p.flops * p.calls
                any_known = True
        return total if any_known else None

    def max_peak_hbm_bytes(self):
        peaks = [p.peak_hbm_bytes for p in self._programs.values()
                 if p.peak_hbm_bytes]
        return max(peaks) if peaks else None

    def reset(self):
        self._programs = {}
        self.version += 1


_registry = CostModelRegistry()


def registry():
    return _registry


def reset():
    """Test hook: clear captured programs + once-per-process warn state."""
    _registry.reset()
    _oom_warned.clear()


# --------------------------------------------------------------- OOM margin
#: static-estimate fraction of total_memory() past which the once-per-
#: program warning fires (override: DS_TPU_OOM_MARGIN, a fraction)
OOM_MARGIN_FRACTION = 0.9
_oom_warned = set()


def check_oom_margin(name, peak_hbm_bytes):
    """Loud once-per-program warning when the static peak-HBM estimate
    approaches the device memory limit — the point of a compile-time
    estimate is hearing about the OOM before the first step hits it."""
    if not peak_hbm_bytes or name in _oom_warned:
        return False
    from ..accelerator import get_accelerator
    total = get_accelerator().total_memory()
    if not total:   # a backend that reports no limit (CPU)
        return False
    frac = float(os.environ.get("DS_TPU_OOM_MARGIN", OOM_MARGIN_FRACTION))
    if peak_hbm_bytes >= frac * total:
        _oom_warned.add(name)
        logger.warning(
            "HBM MARGIN: compiled program %r statically needs ~%.2f GiB of "
            "%.2f GiB device memory (%.0f%% ≥ %.0f%% margin) — this config "
            "is at OOM risk; consider a higher ZeRO stage, smaller "
            "micro-batch, or offload (see python -m "
            "deepspeed_tpu.profiling.mem_estimator)",
            name, peak_hbm_bytes / 2**30, total / 2**30,
            100.0 * peak_hbm_bytes / total, 100.0 * frac)
        return True
    return False


# -------------------------------------------------------------- capture API
#: force-capture switch for tools that want the registry populated without
#: enabling the full telemetry spine (chip_smoke);
#: telemetry.enabled also arms capture at the opt-in call sites (serving) —
#: the training engine captures unconditionally.
_force_capture = False


def enable_capture(on=True):
    global _force_capture
    _force_capture = bool(on)


def capturing():
    """Should the opt-in call sites (serving) capture right now?"""
    if _force_capture:
        return True
    from .. import telemetry
    return telemetry.enabled


def _compile_and_record(name, jitted, args, kwargs, meta):
    compiled = jitted.lower(*args, **(kwargs or {})).compile()
    entry = _registry.record(name, analyze_compiled(compiled), meta=meta,
                             compiled=compiled)
    check_oom_margin(name, entry.peak_hbm_bytes)
    return entry


def capture_jit(name, jitted, args=(), kwargs=None, meta=None):
    """AOT-compile ``jitted`` for ``args`` and record its cost entry.

    Returns ``(compiled, entry)`` — ``compiled`` is the executable itself
    (one compile total, the same one jit would have done lazily).  It
    validates input layouts strictly: a call whose arguments are placed
    differently from ``args`` raises instead of silently recompiling."""
    entry = _compile_and_record(name, jitted, args, kwargs, meta)
    return entry.compiled, entry


def capture_jit_call(name, jitted, args=(), kwargs=None, meta=None):
    """Record the cost entry for a call signature of an existing jitted
    function WITHOUT replacing the callable (the serving engines keep
    jit's own static-argument dispatch; jit and the AOT path share one
    executable cache, so this is not a second compile).  Only done under
    :func:`capturing`.  Always returns the (possibly pre-existing) entry;
    increments its call count."""
    entry = _registry.get(name) or _compile_and_record(
        name, jitted, args, kwargs, meta)
    entry.calls += 1
    return entry


def analyze_fn(fn, *args, **kwargs):
    """One-shot analysis of ``fn(*args, **kwargs)`` (jitted here if not
    already a jit wrapper).  Returns the analysis dict — the flops_profiler
    façade and the bench candidate rows use this."""
    import jax
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    return analyze_compiled(jitted.lower(*args, **kwargs).compile())


def mfu(flops_per_chip_per_second, peak=None):
    """Model-FLOPs utilization: achieved per-chip FLOP/s ÷ per-chip peak.
    None in → None out (refuse, don't fabricate)."""
    if flops_per_chip_per_second is None:
        return None
    peak = peak if peak is not None else peak_flops_per_chip()
    if not peak or peak <= 0:
        return None
    return float(flops_per_chip_per_second) / float(peak)
