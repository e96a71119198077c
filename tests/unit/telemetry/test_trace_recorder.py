"""TraceRecorder: span nesting, disabled-mode zero-emission, chrome-trace
schema validity, step records + exposed-comm-fraction, fence mode."""

import json
import os

import pytest

from deepspeed_tpu import telemetry
from deepspeed_tpu.telemetry.trace import (CHROME_EVENT_KEYS, STEPS_FILE,
                                           TRACE_FILE, TraceRecorder)


_live = []


@pytest.fixture(autouse=True)
def _telemetry_reset():
    telemetry.shutdown()
    yield
    telemetry.shutdown()
    # close stragglers NOW — an atexit-time close would write into pytest's
    # torn-down tmp dirs and closed log streams
    while _live:
        _live.pop().close()


def _recorder(tmp_path, **kw):
    kw.setdefault("sync_fn", lambda: None)  # no device in these tests
    rec = TraceRecorder(str(tmp_path), **kw)
    _live.append(rec)
    return rec


def test_span_nesting_and_phase_attribution(tmp_path):
    rec = _recorder(tmp_path)
    rec.begin_step(0)
    with rec.span("backward"):
        with rec.span("grad_reduce"):
            pass
    record = rec.end_step()
    # nested span contributes its own phase AND its chrome event
    assert set(record["phases"]) == {"backward", "grad_reduce"}
    assert record["phases"]["grad_reduce"] <= record["phases"]["backward"]
    names = [e["name"] for e in rec.chrome_trace()["traceEvents"]]
    assert names.count("backward") == 1 and names.count("grad_reduce") == 1


def test_begin_end_span_api_tolerates_mismatch(tmp_path, caplog):
    rec = _recorder(tmp_path)
    rec.begin_step(0)
    rec.begin_span("forward")
    rec.end_span("forward")
    rec.end_span("forward")  # unbalanced: warns, never raises
    rec.end_step()
    assert rec.steps_recorded == 1


def test_chrome_trace_schema_valid(tmp_path):
    rec = _recorder(tmp_path)
    rec.begin_step(3)
    with rec.span("forward"):
        pass
    rec.comm_event("all_reduce", "q_int8", 4096, 1100, 0.002, 8)
    rec.end_step(metrics={"loss": 1.0})
    path = rec.write_chrome_trace()
    trace = json.loads(open(path).read())   # json.loads: schema contract
    assert isinstance(trace["traceEvents"], list) and trace["traceEvents"]
    for ev in trace["traceEvents"]:
        for key in CHROME_EVENT_KEYS:
            assert key in ev, (key, ev)
        assert ev["ph"] == "X"
    # comm events ride their own track with byte args
    comm = [e for e in trace["traceEvents"]
            if e["name"] == "all_reduce[q_int8]"]
    assert comm and comm[0]["args"]["wire_bytes"] == 1100


def test_step_record_stream_and_fraction(tmp_path):
    rec = _recorder(tmp_path)
    for step in range(3):
        rec.begin_step(step)
        with rec.span("forward"):
            pass
        rec.comm_event("reduce_scatter", None, 1 << 20, None, 0.001, 8)
        rec.end_step()
    rec.close()
    lines = open(os.path.join(str(tmp_path), STEPS_FILE)).read().splitlines()
    assert len(lines) == 3
    for line in lines:
        r = json.loads(line)
        assert 0.0 <= r["comm"]["exposed_comm_fraction"] <= 1.0
        assert r["comm"]["ops"]["reduce_scatter"]["count"] == 1
    # per-step attribution resets between steps (count 1 each, not 1..3)


def test_trace_steps_budget(tmp_path):
    rec = _recorder(tmp_path, trace_steps=2)
    for step in range(5):
        rec.begin_step(step)
        rec.end_step()
    assert rec.steps_recorded == 2
    assert not rec.recording


def test_fence_mode_syncs_at_boundaries(tmp_path):
    syncs = []
    rec = TraceRecorder(str(tmp_path), fence=True,
                        sync_fn=lambda: syncs.append(1))
    _live.append(rec)
    rec.begin_step(0)
    with rec.span("forward"):
        pass
    rec.end_step()
    assert len(syncs) >= 2  # span begin + end (+ step end)


def test_disabled_mode_zero_emission(tmp_path, monkeypatch):
    """With telemetry disabled the module emit helpers are inert: no
    recorder, no files, span() hands back a nullcontext."""
    monkeypatch.chdir(tmp_path)
    assert not telemetry.enabled
    assert telemetry.get_recorder() is None
    assert telemetry.get_registry() is None
    telemetry.begin_step(0)
    telemetry.begin_span("forward")
    telemetry.end_span("forward")
    telemetry.record_comm_event("all_reduce", None, 4096, None, 0.001)
    assert telemetry.end_step() is None
    with telemetry.span("anything"):
        pass
    assert telemetry.counter("x") is None
    telemetry.observe("y", 1.0)
    assert telemetry.prometheus_text() == ""
    assert os.listdir(str(tmp_path)) == []  # nothing written anywhere


def test_configure_shutdown_roundtrip(tmp_path):
    class MC:
        enabled = True
        prometheus_port = 0
        rank0_only = True

    class Cfg:
        trace_dir = str(tmp_path)
        trace_steps = 0
        fence = False
        metrics = MC()

    rec, reg = telemetry.configure(Cfg())
    assert telemetry.enabled and rec is telemetry.get_recorder()
    telemetry.begin_step(0)
    telemetry.end_step()
    telemetry.shutdown()
    assert not telemetry.enabled
    # shutdown flushed the chrome trace
    assert os.path.exists(os.path.join(str(tmp_path), TRACE_FILE))


def test_unterminated_step_flushed_by_next_begin(tmp_path):
    rec = _recorder(tmp_path)
    rec.begin_step(0)
    rec.begin_step(0)   # idempotent for the same step
    rec.begin_step(1)   # flushes step 0
    rec.end_step()
    rec.close()
    steps = [json.loads(l)["step"] for l in
             open(os.path.join(str(tmp_path), STEPS_FILE))]
    assert steps == [0, 1]


def test_max_events_cap_drops_not_grows(tmp_path):
    rec = _recorder(tmp_path, max_events=4)
    for i in range(10):
        with rec.span(f"s{i}"):
            pass
    trace = rec.chrome_trace()
    assert len(trace["traceEvents"]) == 4
    assert trace["otherData"]["dropped_events"] == 6


def test_hidden_comm_and_overlap_efficiency(tmp_path):
    """exposed=False comm events book hidden time: they feed
    overlap_efficiency but never the exposed fraction."""
    rec = _recorder(tmp_path)
    rec.begin_step(0)
    rec.comm_event("reduce_scatter", "overlap", 1 << 20, None, 0.004, 8)
    rec.comm_event("reduce_scatter", "overlap", 1 << 20, None, 0.012, 8,
                   exposed=False)
    record = rec.end_step()
    comm = record["comm"]
    assert comm["exposed_ms"] == pytest.approx(4.0)
    assert comm["hidden_ms"] == pytest.approx(12.0)
    assert comm["total_ms"] == pytest.approx(16.0)
    assert comm["overlap_efficiency"] == pytest.approx(0.75)
    row = comm["ops"]["reduce_scatter[overlap]"]
    assert row["hidden_ms"] == pytest.approx(12.0)
    assert row["total_ms"] == pytest.approx(4.0)  # exposed-only, as ever


def test_no_comm_step_scores_perfect_overlap(tmp_path):
    """A fully jitted step has no eager comm events: hidden==exposed==0 and
    overlap_efficiency is vacuously 1.0 (trace_report prints the explicit
    fully-fused note instead of implying a measurement)."""
    rec = _recorder(tmp_path)
    rec.begin_step(0)
    record = rec.end_step()
    assert record["comm"]["total_ms"] == 0.0
    assert record["comm"]["overlap_efficiency"] == 1.0
    assert not record["comm"]["ops"]


def test_bucket_spans_land_in_overlap_section(tmp_path):
    """bucket_reduce/<k> spans populate the step record's overlap section,
    never the phase columns."""
    rec = _recorder(tmp_path)
    rec.begin_step(0)
    with rec.span("backward"):
        pass
    for k in range(3):
        with rec.bucket_span(k, nbytes=1024):
            pass
    record = rec.end_step()
    assert record["overlap"]["buckets"] == 3
    assert set(record["overlap"]["bucket_ms"]) == {
        "bucket_reduce/0", "bucket_reduce/1", "bucket_reduce/2"}
    assert set(record["phases"]) == {"backward"}
    names = [e["name"] for e in rec.chrome_trace()["traceEvents"]]
    assert names.count("bucket_reduce/1") == 1


def test_gather_bucket_spans_share_overlap_section(tmp_path):
    """param_gather/<k> spans (the forward-prefetch direction) land in the
    same overlap section as bucket_reduce/<k>, never the phase columns."""
    rec = _recorder(tmp_path)
    rec.begin_step(0)
    with rec.span("forward"):
        pass
    with rec.bucket_span(0, kind="param_gather", nbytes=2048):
        pass
    with rec.bucket_span(0, nbytes=1024):
        pass
    record = rec.end_step()
    assert record["overlap"]["buckets"] == 2
    assert set(record["overlap"]["bucket_ms"]) == {
        "param_gather/0", "bucket_reduce/0"}
    assert set(record["phases"]) == {"forward"}


def test_moe_stats_land_in_step_record(tmp_path):
    """Routed-token accounting: per-layer stats accumulate over the gas
    window's micro-batches (mean), land under the record's ``moe`` section
    with the cross-layer aggregates, and reset at the next step."""
    rec = _recorder(tmp_path)
    rec.begin_step(0)
    rec.moe_stat("layers_0/moe", {"k": 1, "drop_fraction": 0.2,
                                  "overflow_tokens": 4.0,
                                  "load_imbalance": 2.0, "aux_loss": 1.0})
    rec.moe_stat("layers_0/moe", {"k": 1, "drop_fraction": 0.4,
                                  "overflow_tokens": 8.0,
                                  "load_imbalance": 4.0, "aux_loss": 1.2})
    rec.moe_stat("layers_1/moe", {"k": 2, "drop_fraction": 0.0,
                                  "overflow_tokens": 0.0,
                                  "load_imbalance": 1.0, "aux_loss": 0.9})
    record = rec.end_step()
    moe = record["moe"]
    l0 = moe["layers"]["layers_0/moe"]
    assert abs(l0["drop_fraction"] - 0.3) < 1e-9  # mean of 2 micro-batches
    assert abs(l0["overflow_tokens"] - 6.0) < 1e-9
    assert l0["k"] == 1
    assert moe["layers"]["layers_1/moe"]["k"] == 2
    assert abs(moe["drop_fraction_mean"] - 0.15) < 1e-9
    assert abs(moe["load_imbalance_max"] - 3.0) < 1e-9
    assert abs(moe["aux_loss_total"] - (1.1 + 0.9)) < 1e-9
    # next step starts clean
    rec.begin_step(1)
    record = rec.end_step()
    assert "moe" not in record


def test_moe_stats_without_step_are_dropped(tmp_path):
    rec = _recorder(tmp_path)
    rec.moe_stat("moe", {"k": 1, "drop_fraction": 0.5})  # no open step
    rec.begin_step(0)
    record = rec.end_step()
    assert "moe" not in record


def test_moe_vector_stats_mean_elementwise(tmp_path):
    """List-valued stats (per-expert capacity utilization, ISSUE 15) mean
    elementwise over the gas window, like the scalars."""
    rec = _recorder(tmp_path)
    rec.begin_step(0)
    rec.moe_stat("layers_0/moe", {"k": 1, "drop_fraction": 0.2,
                                  "expert_util": [0.2, 0.6]})
    rec.moe_stat("layers_0/moe", {"k": 1, "drop_fraction": 0.4,
                                  "expert_util": [0.4, 1.0]})
    record = rec.end_step()
    l0 = record["moe"]["layers"]["layers_0/moe"]
    assert l0["expert_util"] == pytest.approx([0.3, 0.8])
    assert l0["drop_fraction"] == pytest.approx(0.3)


def test_moe_vector_stats_partial_window_and_resize(tmp_path):
    """A vector present in only SOME of the window's calls means over its
    own call count (not diluted by _n), and a length change (resized
    expert group) restarts the sum instead of zip-truncating."""
    rec = _recorder(tmp_path)
    rec.begin_step(0)
    rec.moe_stat("m", {"k": 1, "drop_fraction": 0.2})  # no vector
    rec.moe_stat("m", {"k": 1, "drop_fraction": 0.4,
                       "expert_util": [0.5, 0.7]})
    record = rec.end_step()
    layer = record["moe"]["layers"]["m"]
    assert layer["expert_util"] == pytest.approx([0.5, 0.7])  # ÷1, not ÷2
    assert layer["drop_fraction"] == pytest.approx(0.3)
    rec.begin_step(1)
    rec.moe_stat("m", {"k": 1, "expert_util": [1.0] * 8})
    rec.moe_stat("m", {"k": 1, "expert_util": [0.2, 0.4]})  # resized
    record = rec.end_step()
    assert record["moe"]["layers"]["m"]["expert_util"] == \
        pytest.approx([0.2, 0.4])
