"""profiling/cost_model: compiled-cost capture (the AOT executable is the
program that runs — a rejected lower/compile/call raises), the one
peak-FLOPS table (an unknown device kind is an error), OOM margin.

The repo logger writes to its own stdout handler with propagate=False, so
warning asserts attach a test-local handler (the ``warnlog`` fixture)."""

import io
import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.profiling import cost_model


@pytest.fixture(autouse=True)
def _fresh_registry():
    cost_model.reset()
    yield
    cost_model.reset()
    cost_model.enable_capture(False)


@pytest.fixture
def warnlog():
    """StringIO attached to the repo logger for the duration of a test."""
    from deepspeed_tpu.utils.logging import logger
    buf = io.StringIO()
    handler = logging.StreamHandler(buf)
    handler.setLevel(logging.WARNING)
    logger.addHandler(handler)
    yield buf
    logger.removeHandler(handler)


def _mm(x, w):
    return jnp.tanh(x @ w).sum()


ARGS = (jnp.ones((16, 64), jnp.float32), jnp.ones((64, 64), jnp.float32))


def test_analyze_fn_reports_flops_and_peak_on_cpu():
    a = cost_model.analyze_fn(_mm, *ARGS)
    # this jaxlib's CPU backend implements both analyses
    assert a["flops"] and a["flops"] > 0
    assert a["peak_hbm_bytes"] and a["peak_hbm_bytes"] > 0
    assert a["source"] == "xla"
    # arguments dominate the tiny program's static estimate
    assert a["argument_bytes"] >= 16 * 64 * 4


def test_capture_jit_returns_the_executable_itself():
    fn, entry = cost_model.capture_jit("t/mm", jax.jit(_mm), ARGS)
    assert isinstance(fn, jax.stages.Compiled)
    out = fn(*ARGS)
    assert np.isfinite(float(out))
    assert cost_model.registry().get("t/mm") is entry
    assert entry.compiled is fn and "dot" in entry.compiled.as_text()
    assert entry.flops > 0
    d = cost_model.registry().describe()
    assert d[0]["name"] == "t/mm" and d[0]["source"] == "xla"
    assert "compiled" not in d[0]        # describe() stays JSON-safe


def test_aot_rejections_raise_instead_of_redispatching():
    """A lower/compile failure and a mis-placed call both propagate: no
    second compile through jit that would hide a layout bug."""
    class BrokenJit:
        def lower(self, *a, **k):
            raise RuntimeError("no AOT on this backend")

    with pytest.raises(RuntimeError, match="no AOT"):
        cost_model.capture_jit("t/broken", BrokenJit(), ARGS)
    assert cost_model.registry().get("t/broken") is None
    fn, _ = cost_model.capture_jit("t/strict", jax.jit(_mm), ARGS)
    with pytest.raises((TypeError, ValueError)):
        fn(jnp.ones((8, 64), jnp.float32), ARGS[1])   # other shape


def test_capture_jit_call_counts_invocations():
    jitted = jax.jit(_mm)
    e1 = cost_model.capture_jit_call("t/serve", jitted, ARGS)
    e2 = cost_model.capture_jit_call("t/serve", jitted, ARGS)
    assert e1 is e2 and e2.calls == 2
    total = cost_model.registry().total_flops_executed()
    assert total == pytest.approx(2 * e1.flops)


def test_peak_flops_env_override(monkeypatch):
    monkeypatch.setenv(cost_model.PEAK_FLOPS_ENV, "2.5e14")
    assert cost_model.peak_flops_per_chip() == 2.5e14
    monkeypatch.setenv(cost_model.PEAK_FLOPS_ENV, "not-a-float")
    with pytest.raises(ValueError):
        cost_model.peak_flops_per_chip()


def test_peak_flops_table_keys_by_device_kind_and_rejects_unknown():
    assert cost_model.peak_flops_for_kind("TPU v5 lite") == 197e12
    assert cost_model.peak_flops_for_kind("TPU v5") == 459e12   # v5p
    assert cost_model.peak_flops_for_kind("TPU v4") == 275e12
    with pytest.raises(KeyError, match="TPU v9 hyper"):
        cost_model.peak_flops_for_kind("TPU v9 hyper")


def test_mfu_refuses_on_unknown_flops():
    assert cost_model.mfu(None) is None
    assert cost_model.mfu(1e12, peak=2e12) == pytest.approx(0.5)
    assert cost_model.mfu(1e12, peak=0) is None


def test_oom_margin_warns_once_near_limit(monkeypatch, warnlog):
    from deepspeed_tpu import accelerator as acc_mod
    acc = acc_mod.get_accelerator()
    monkeypatch.setattr(type(acc), "total_memory",
                        lambda self, device_index=None: 1000)
    assert cost_model.check_oom_margin("t/big", 950)
    assert not cost_model.check_oom_margin("t/big", 950)  # once per name
    assert not cost_model.check_oom_margin("t/small", 100)
    assert warnlog.getvalue().count("HBM MARGIN") == 1


def test_capturing_follows_force_flag_and_telemetry():
    from deepspeed_tpu import telemetry
    assert not telemetry.enabled
    assert not cost_model.capturing()
    cost_model.enable_capture(True)
    assert cost_model.capturing()
    cost_model.enable_capture(False)
    assert not cost_model.capturing()


def test_flops_profiler_facade_still_reports_xla_numbers():
    # the façade (flops_profiler) rides analyze_fn and keeps its API
    from deepspeed_tpu.profiling.flops_profiler import FlopsProfiler
    prof = FlopsProfiler()
    prof.profile(_mm, *ARGS)
    assert prof.flops == cost_model.jaxpr_flops(_mm, *ARGS)[0]
    assert prof.xla_flops and prof.xla_flops > 0
    assert prof.xla_peak_hbm and prof.xla_peak_hbm > 0
    text = prof.print_model_profile(output_file=None)
    assert "static peak HBM" in text
