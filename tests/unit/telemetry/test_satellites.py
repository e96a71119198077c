"""ISSUE-6 satellite fixes: monitor path/scalar tolerance, timer
``elapsed(reset=False)`` consistency + throughput smoothing window,
comms-logging machine-readable summary without variant double-counting."""

import time

import numpy as np
import pytest


# ------------------------------------------------------------------ monitor
def test_csv_monitor_creates_dirs_on_first_write(tmp_path):
    from deepspeed_tpu.monitor.monitor import csv_monitor
    from deepspeed_tpu.runtime.config import MonitorConfig
    out = tmp_path / "does" / "not" / "exist"
    cfg = MonitorConfig(csv_monitor={"enabled": True,
                                     "output_path": str(out),
                                     "job_name": "job"})
    mon = csv_monitor(cfg.csv_monitor)
    assert not out.exists()      # __init__ no longer touches the fs
    mon.write_events([("Train/loss", 1.0, 1)])
    assert (out / "job" / "Train_loss.csv").exists()


def test_csv_monitor_unwritable_path_degrades(tmp_path):
    from deepspeed_tpu.monitor.monitor import csv_monitor
    from deepspeed_tpu.runtime.config import MonitorConfig
    blocker = tmp_path / "file"
    blocker.write_text("")
    cfg = MonitorConfig(csv_monitor={"enabled": True,
                                     "output_path": str(blocker / "sub"),
                                     "job_name": "job"})
    mon = csv_monitor(cfg.csv_monitor)
    mon.write_events([("Train/loss", 1.0, 1)])   # warns, must not raise
    assert not mon.enabled


def test_monitor_tolerates_non_scalar_values(tmp_path):
    from deepspeed_tpu.monitor.monitor import csv_monitor
    from deepspeed_tpu.runtime.config import MonitorConfig
    cfg = MonitorConfig(csv_monitor={"enabled": True,
                                     "output_path": str(tmp_path),
                                     "job_name": "job"})
    mon = csv_monitor(cfg.csv_monitor)
    mon.write_events([
        ("Train/vec", np.ones((4, )), 1),        # non-scalar: dropped loudly
        ("Train/np_scalar", np.float32(2.5), 1),  # 0-d numpy: fine
        ("Train/str", "nope", 1),                # junk: dropped
        ("Train/loss", 1.25, 1),
    ])
    files = sorted(p.name for p in (tmp_path / "job").iterdir())
    assert files == ["Train_loss.csv", "Train_np_scalar.csv"]


# -------------------------------------------------------------------- timer
def test_timer_elapsed_no_reset_is_pure_read():
    from deepspeed_tpu.utils.timer import SynchronizedWallClockTimer
    t = SynchronizedWallClockTimer()("x")
    t.start()
    time.sleep(0.01)
    e1 = t.elapsed(reset=False)
    e2 = t.elapsed(reset=False)
    assert t.started_          # still running, state untouched
    assert e2 >= e1 > 0
    time.sleep(0.01)
    t.stop()
    # total covers the FULL start→stop window: the reads did not eat time
    assert t.elapsed(reset=False) >= e2 + 0.01
    assert not t.records or len(t.records) == 1  # reads recorded nothing


def test_timer_elapsed_reset_restarts_running_segment():
    from deepspeed_tpu.utils.timer import SynchronizedWallClockTimer
    t = SynchronizedWallClockTimer()("x")
    t.start()
    time.sleep(0.01)
    assert t.elapsed(reset=True) >= 0.01
    assert t.started_
    assert t.elapsed(reset=False) < 0.01  # accumulation restarted at now
    t.stop()


def test_timer_sync_routes_through_accelerator(monkeypatch):
    from deepspeed_tpu import accelerator as acc_mod
    from deepspeed_tpu.utils.timer import SynchronizedWallClockTimer
    synced = []
    real = acc_mod.get_accelerator()

    class Spy:
        def synchronize(self):
            synced.append(1)

        def __getattr__(self, name):
            return getattr(real, name)

    monkeypatch.setattr(acc_mod, "get_accelerator", lambda: Spy())
    t = SynchronizedWallClockTimer()("x")
    t.start(sync=True)
    t.stop(sync=True)
    SynchronizedWallClockTimer.synchronize()
    assert len(synced) == 3


def test_throughput_timer_smoothing_window():
    from deepspeed_tpu.utils.timer import ThroughputTimer
    cfg = type("C", (), {"enabled": True})()
    tt = ThroughputTimer(cfg, batch_size=10, start_step=1,
                         smoothing_window=2)
    durations = [0.1, 0.1, 0.1, 0.01, 0.01]  # slow warmup, then fast
    for d in durations:
        tt.start()
        # simulate a step of length d without sleeping
        tt.start_time = time.perf_counter() - d
        tt.stop(global_step=True)
    # window of 2 sees only the fast steps: ≈ 10 / 0.01 = 1000 samples/s,
    # NOT the whole-run mean (≈ 152) the slow warmup would drag it to
    assert tt.avg_samples_per_sec() == pytest.approx(1000, rel=0.25)
    # no window → historical behavior
    tt2 = ThroughputTimer(cfg, batch_size=10, start_step=1)
    for d in durations:
        tt2.start()
        tt2.start_time = time.perf_counter() - d
        tt2.stop(global_step=True)
    assert tt2.avg_samples_per_sec() < 300


# ----------------------------------------------------------- comms logging
def _append_calls(logger, calls):
    for raw, rec, lat, msg, ws, wire, variant in calls:
        logger.append(raw, rec, lat, msg, ws, wire_size=wire,
                      variant=variant)


def test_get_summary_dict_no_variant_double_count():
    """An op that falls back from a quantized variant to flat mid-run:
    every call lands in exactly one variant row and once in the base-op
    total."""
    from deepspeed_tpu.utils.comms_logging import CommsLogger
    log = CommsLogger(enabled=True)
    _append_calls(log, [
        ("reduce_scatter", "reduce_scatter", 0.002, 4096, 8, 1100, "q_int8"),
        ("reduce_scatter", "reduce_scatter", 0.002, 4096, 8, 1100, "q_int8"),
        # mid-run fallback to flat (e.g. shape stopped dividing)
        ("reduce_scatter", "reduce_scatter", 0.004, 4096, 8, None, None),
    ])
    s = log.get_summary_dict()
    assert set(s["ops"]) == {"reduce_scatter", "reduce_scatter[q_int8]"}
    q = s["ops"]["reduce_scatter[q_int8]"]
    flat = s["ops"]["reduce_scatter"]
    assert q["count"] == 2 and q["total_wire_bytes"] == 2200
    assert flat["count"] == 1 and flat["total_wire_bytes"] == 4096
    t = s["totals"]["reduce_scatter"]
    assert t["count"] == 3                      # each call exactly once
    assert t["total_wire_bytes"] == 2200 + 4096  # no stale-wire inflation
    assert sorted(t["variants"]) == ["flat", "q_int8"]


def test_append_accumulates_wire_bytes_not_overwrites():
    from deepspeed_tpu.utils.comms_logging import CommsLogger
    log = CommsLogger(enabled=True)
    _append_calls(log, [
        ("all_gather", "all_gather", 0.001, 8192, 4, 2100, "q_int8"),
        ("all_gather", "all_gather", 0.001, 8192, 4, 2100, "q_int8"),
    ])
    entry = log.comms_dict["all_gather[q_int8]"][8192]
    assert entry[0] == 2 and entry[4] == 4200   # total, not last-call
    log.log_all(print_log=False)                # table still renders


def test_stale_variant_not_attributed_to_flat_op(monkeypatch):
    """comm._dispatch resets the last-dispatch marker on entry: an engine
    hit recorded by an earlier op must not label a later flat op."""
    from deepspeed_tpu.comm import comm as comm_mod
    comm_mod._last_dispatch = ("q_int8", 1100)  # stale from a previous op
    import deepspeed_tpu.comm as dist
    import jax.numpy as jnp
    dist.init_distributed()
    log = comm_mod.comms_logger
    saved = (log.enabled, dict(log.comms_dict))
    log.enabled, log.comms_dict = True, {}
    try:
        dist.all_reduce(jnp.ones((64, )))
        assert "all_reduce" in log.comms_dict       # flat row
        assert "all_reduce[q_int8]" not in log.comms_dict
    finally:
        log.enabled, log.comms_dict = saved[0], {}


# ------------------------------------------------- ISSUE-14 satellites
def test_see_memory_usage_reports_peak_limit_fragmentation(monkeypatch):
    from deepspeed_tpu import accelerator as acc_mod
    from deepspeed_tpu.runtime.utils import (memory_usage_snapshot,
                                             see_memory_usage)
    acc = acc_mod.get_accelerator()
    monkeypatch.setattr(
        type(acc), "memory_stats",
        lambda self, device_index=None: {
            "bytes_in_use": 600, "peak_bytes_in_use": 800,
            "bytes_limit": 1000, "largest_free_block_bytes": 100})
    snap = memory_usage_snapshot()
    assert snap["live_bytes"] == 600 and snap["peak_bytes"] == 800
    assert snap["limit_bytes"] == 1000 and snap["free_bytes"] == 400
    # largest free block 100 of 400 free → 75% fragmented
    assert snap["fragmentation"] == pytest.approx(0.75)
    # force=False stays a no-op (the hot-path contract)
    assert see_memory_usage("quiet") is None
    assert see_memory_usage("loud", force=True) == snap


def test_see_memory_usage_routes_gauges_through_registry(monkeypatch,
                                                         tmp_path):
    from deepspeed_tpu import accelerator as acc_mod, telemetry
    from deepspeed_tpu.runtime.utils import see_memory_usage
    acc = acc_mod.get_accelerator()
    monkeypatch.setattr(
        type(acc), "memory_stats",
        lambda self, device_index=None: {
            "bytes_in_use": 600, "peak_bytes_in_use": 800,
            "bytes_limit": 1000, "largest_free_block_bytes": 100})
    cfg = type("C", (), {"trace_dir": str(tmp_path), "fence": False,
                         "trace_steps": 0, "metrics": None})()
    try:
        telemetry.configure(cfg)
        see_memory_usage("snap", force=True)
        text = telemetry.prometheus_text()
    finally:
        telemetry.shutdown()
    assert 'hbm_live_bytes{rank="0"} 600.0' in text
    assert 'hbm_peak_bytes{rank="0"} 800.0' in text
    assert 'hbm_fragmentation{rank="0"} 0.75' in text


def test_sequence_length_config_validates():
    from deepspeed_tpu.runtime.config import (DeepSpeedConfig,
                                              DeepSpeedConfigError)
    cfg = DeepSpeedConfig({"train_micro_batch_size_per_gpu": 1,
                           "sequence_length": 128})
    assert cfg.sequence_length == 128
    with pytest.raises(DeepSpeedConfigError):
        DeepSpeedConfig({"train_micro_batch_size_per_gpu": 1,
                         "sequence_length": -5})
    with pytest.raises(DeepSpeedConfigError):
        DeepSpeedConfig({"train_micro_batch_size_per_gpu": 1,
                         "sequence_length": "long"})


def test_token_accounting_validates_loudly(monkeypatch):
    """Engine._count_batch_tokens: config sequence_length wins (mismatch
    warns once); unset + 2-D input assumes axis 1 loudly; nothing
    defensible → 0 (rate metrics omitted, not garbage)."""
    import io
    import logging as _logging

    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    from deepspeed_tpu.utils.logging import logger as ds_logger

    eng = object.__new__(DeepSpeedEngine)   # method under test is pure
    buf = io.StringIO()
    handler = _logging.StreamHandler(buf)
    ds_logger.addHandler(handler)
    try:
        # config key set and consistent: batch × seq, silent
        eng.sequence_length, eng._seq_len_warned = 8, False
        x = np.zeros((4, 8, 3))
        assert eng._count_batch_tokens((x, )) == 32
        assert not eng._seq_len_warned
        # mismatch against axis 1: config wins, warns once
        eng.sequence_length, eng._seq_len_warned = 16, False
        assert eng._count_batch_tokens((x, )) == 64
        assert eng._seq_len_warned
        assert "sequence_length=16" in buf.getvalue()
        # unset + 2-D input: heuristic, loud once
        buf.truncate(0), buf.seek(0)
        eng.sequence_length, eng._seq_len_warned = None, False
        assert eng._count_batch_tokens((x, )) == 32
        assert "ASSUMING inputs[0] axis 1" in buf.getvalue()
        assert eng._count_batch_tokens((x, )) == 32   # warned once
        assert buf.getvalue().count("ASSUMING") == 1
        # 1-D input counts samples; empty counts nothing
        eng.sequence_length = None
        assert eng._count_batch_tokens((np.zeros(5), )) == 5
        assert eng._count_batch_tokens(()) == 0
    finally:
        ds_logger.removeHandler(handler)
