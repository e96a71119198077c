"""tools/trace_report.py: golden-output test on a canned JSONL fixture
(the tool is loaded via importlib)."""

import importlib.util
import json
import os

spec = importlib.util.spec_from_file_location(
    "trace_report", os.path.join(os.path.dirname(__file__), "..", "..",
                                 "..", "tools", "trace_report.py"))
trace_report = importlib.util.module_from_spec(spec)
spec.loader.exec_module(trace_report)


FIXTURE = [
    {"step": 0, "wall_ms": 100.0,
     "phases": {"forward": 50.0, "backward": 30.0, "grad_reduce": 10.0,
                "optimizer": 15.0},
     "comm": {"total_ms": 20.0, "exposed_ms": 20.0,
              "exposed_comm_fraction": 0.2,
              "ops": {"all_reduce": {"count": 2, "total_ms": 8.0,
                                     "avg_ms": 4.0, "msg_bytes": 2097152,
                                     "wire_bytes": 2097152, "gbps": 2.097},
                      "reduce_scatter[q_int8]": {
                          "count": 2, "total_ms": 12.0, "avg_ms": 6.0,
                          "msg_bytes": 4194304, "wire_bytes": 1114112,
                          "gbps": 0.743}}},
     "metrics": {"loss": 2.0, "tokens": 8192}},
    {"step": 1, "wall_ms": 60.0,
     "phases": {"forward": 25.0, "backward": 20.0, "grad_reduce": 5.0,
                "optimizer": 10.0},
     "comm": {"total_ms": 6.0, "exposed_ms": 6.0,
              "exposed_comm_fraction": 0.1,
              "ops": {"reduce_scatter[q_int8]": {
                  "count": 2, "total_ms": 6.0, "avg_ms": 3.0,
                  "msg_bytes": 4194304, "wire_bytes": 1114112,
                  "gbps": 1.486}}},
     "metrics": {"loss": 1.5, "tokens": 8192}},
]

GOLDEN = """\
== per-step breakdown (ms) ==
  step   wall_ms     forward    backward grad_reduce   optimizer   comm_ms  exposed_frac
     0    100.00       50.00       30.00       10.00       15.00     20.00         0.200
     1     60.00       25.00       20.00        5.00       10.00      6.00         0.100

== run summary (2 steps) ==
mean step wall: 80.00 ms | exposed comm: 13.00 ms | exposed-comm-fraction: 0.163
tokens/s (all chips): 102400
  backward            25.00 ms  (31.2%)
  forward             37.50 ms  (46.9%)
  grad_reduce          7.50 ms  ( 9.4%)
  optimizer           12.50 ms  (15.6%)

== collectives by op[variant] ==
op[variant]                         count    avg_ms      wire  eff_Gbps
all_reduce                              2     4.000    2.0MiB      2.10
reduce_scatter[q_int8]                  4     4.500    2.1MiB      0.99"""


def _write_fixture(tmp_path):
    path = tmp_path / "steps.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in FIXTURE))
    return str(path)


def test_golden_report(tmp_path):
    path = _write_fixture(tmp_path)
    steps = trace_report.load_steps(path)
    summary = trace_report.summarize(steps)
    lines = []
    trace_report.render_report(steps, summary, print_fn=lines.append)
    assert "\n".join(lines).rstrip() == GOLDEN


def test_summary_numbers(tmp_path):
    steps = trace_report.load_steps(_write_fixture(tmp_path))
    s = trace_report.summarize(steps)
    assert s["steps"] == 2
    assert s["wall_ms_mean"] == 80.0
    assert s["exposed_comm_fraction_mean"] == (26.0 / 160.0)
    # per-variant rows merged across steps, each call counted once
    rs = s["comm_ops"]["reduce_scatter[q_int8]"]
    assert rs["count"] == 4 and rs["total_ms"] == 18.0
    assert rs["wire_bytes"] == 2 * 1114112
    assert s["comm_ops"]["all_reduce"]["count"] == 2


def test_load_steps_skips_torn_lines(tmp_path, capsys):
    path = tmp_path / "steps.jsonl"
    path.write_text(json.dumps(FIXTURE[0]) + "\n" + '{"step": 1, "wall')
    steps = trace_report.load_steps(str(path))
    assert len(steps) == 1  # torn tail skipped, not fatal


def test_cli_refuses_a_directory_without_step_records(tmp_path, capsys):
    """The report is over step records: a directory that holds none (only
    some other tool's summary) is an error, not an empty report."""
    (tmp_path / "comm_summary.json").write_text(json.dumps({"ops": {}}))
    assert trace_report.main([str(tmp_path)]) == 1
    assert "no step records found" in capsys.readouterr().err


def test_cli_json_mode_and_chrome_validation(tmp_path, capsys):
    _write_fixture(tmp_path)
    (tmp_path / "trace.json").write_text(json.dumps({
        "traceEvents": [{"name": "forward", "ph": "X", "ts": 0.0,
                         "dur": 5.0, "pid": 0, "tid": 0}]}))
    rc = trace_report.main([str(tmp_path), "--json"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["chrome_trace"]["valid"]
    assert out["steps"] == 2

    # an event missing required keys is reported invalid
    (tmp_path / "trace.json").write_text(json.dumps({
        "traceEvents": [{"name": "forward"}]}))
    ok, detail = trace_report.validate_chrome_trace(
        str(tmp_path / "trace.json"))
    assert not ok and "missing keys" in detail


FUSED_STEP = {"step": 0, "wall_ms": 40.0,
              "phases": {"forward": 25.0, "backward": 10.0},
              "comm": {"total_ms": 0.0, "exposed_ms": 0.0,
                       "exposed_comm_fraction": 0.0, "ops": {}}}


def test_fully_fused_step_prints_explicit_note(tmp_path):
    """Zero comm events because the whole step is jitted: the report says
    so instead of silently printing exposed-comm-fraction = 0."""
    path = tmp_path / "steps.jsonl"
    path.write_text(json.dumps(FUSED_STEP) + "\n")
    steps = trace_report.load_steps(str(path))
    summary = trace_report.summarize(steps)
    assert summary["fused_steps"] == 1
    assert summary["comm_attribution_unavailable"]
    lines = []
    trace_report.render_report(steps, summary, print_fn=lines.append)
    text = "\n".join(lines)
    assert "comm attribution unavailable (fully fused step)" in text
    assert "(fused)" in text  # the per-step column says so too


def test_mixed_fused_steps_keep_measured_fractions(tmp_path):
    path = tmp_path / "steps.jsonl"
    path.write_text(json.dumps(FIXTURE[0]) + "\n" +
                    json.dumps(FUSED_STEP) + "\n")
    steps = trace_report.load_steps(str(path))
    summary = trace_report.summarize(steps)
    assert summary["fused_steps"] == 1
    assert not summary["comm_attribution_unavailable"]
    lines = []
    trace_report.render_report(steps, summary, print_fn=lines.append)
    text = "\n".join(lines)
    assert "0.200" in text and "(fused)" in text
    assert "comm attribution unavailable" not in text


def test_hidden_comm_feeds_overlap_efficiency():
    rec = dict(FIXTURE[0])
    rec["comm"] = dict(rec["comm"], hidden_ms=60.0)
    summary = trace_report.summarize([rec])
    assert summary["hidden_comm_ms_mean"] == 60.0
    assert summary["overlap_efficiency"] == 60.0 / 80.0
    lines = []
    trace_report.render_report([rec], summary, print_fn=lines.append)
    assert any("overlap-efficiency" in ln for ln in lines)


MOE_FIXTURE = [
    {"step": 0, "wall_ms": 10.0, "phases": {"forward": 5.0},
     "comm": {"total_ms": 0.0, "exposed_ms": 0.0, "ops": {}},
     "moe": {"layers": {"layers_0/moe": {
         "k": 1, "drop_fraction": 0.2, "overflow_tokens": 4.0,
         "load_imbalance": 2.0, "aux_loss": 1.0}},
         "drop_fraction_mean": 0.2, "load_imbalance_max": 2.0,
         "aux_loss_total": 1.0}},
    {"step": 1, "wall_ms": 10.0, "phases": {"forward": 5.0},
     "comm": {"total_ms": 0.0, "exposed_ms": 0.0, "ops": {}},
     "moe": {"layers": {"layers_0/moe": {
         "k": 1, "drop_fraction": 0.4, "overflow_tokens": 8.0,
         "load_imbalance": 4.0, "aux_loss": 1.2}},
         "drop_fraction_mean": 0.4, "load_imbalance_max": 4.0,
         "aux_loss_total": 1.2}},
]


def test_moe_table_rendered_and_summarized(tmp_path):
    """Step records carrying the ``moe`` section render the routed-token
    table (per-layer means across steps) and export it in --json."""
    path = tmp_path / "steps.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in MOE_FIXTURE))
    steps = trace_report.load_steps(str(path))
    summary = trace_report.summarize(steps)
    layer = summary["moe_layers"]["layers_0/moe"]
    assert abs(layer["drop_fraction"] - 0.3) < 1e-9
    assert abs(layer["load_imbalance"] - 3.0) < 1e-9
    assert summary["moe_steps"] == 2
    lines = []
    trace_report.render_report(steps, summary,
                               print_fn=lambda *a: lines.append(" ".join(
                                   str(x) for x in a)))
    text = "\n".join(lines)
    assert "MoE routed-token accounting" in text
    assert "layers_0/moe" in text
    assert "0.300" in text  # mean drop fraction


# ------------------------------------------------------- MFU/HBM (ISSUE 14)
MFU_STEPS = [
    {"step": 0, "wall_ms": 100.0, "phases": {"forward": 50.0},
     "comm": {"total_ms": 5.0, "exposed_ms": 5.0,
              "exposed_comm_fraction": 0.05, "ops": {}},
     "metrics": {"loss": 2.0, "mfu": 0.40,
                 "step_flops_per_chip": 1e12},
     "hbm": {"live_bytes": 2 * 2**30, "peak_bytes": 3 * 2**30,
             "limit_bytes": 16 * 2**30}},
    {"step": 1, "wall_ms": 100.0, "phases": {"forward": 50.0},
     "comm": {"total_ms": 5.0, "exposed_ms": 5.0,
              "exposed_comm_fraction": 0.05, "ops": {}},
     "metrics": {"loss": 1.5, "mfu": 0.44},
     "hbm": {"live_bytes": 2 * 2**30, "peak_bytes": 4 * 2**30,
             "limit_bytes": 16 * 2**30}},
]


def test_mfu_hbm_columns_and_summary(tmp_path):
    path = tmp_path / "steps.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in MFU_STEPS))
    steps = trace_report.load_steps(str(path))
    s = trace_report.summarize(steps)
    assert abs(s["mfu_mean"] - 0.42) < 1e-12 and s["mfu_steps"] == 2
    assert s["hbm"]["peak_bytes_max"] == 4 * 2**30
    assert s["hbm"]["limit_bytes"] == 16 * 2**30
    lines = []
    trace_report.render_report(steps, s, print_fn=lines.append)
    text = "\n".join(lines)
    assert "mfu" in text and "hbm_MiB" in text
    assert "0.4000" in text and "0.4400" in text
    assert "MFU (mean over 2 steps): 0.4200" in text
    assert "HBM: live max" in text and "25.0% used" in text


def test_old_records_render_without_mfu_columns(tmp_path):
    # archives predating ISSUE 14 must render byte-stable (no new columns)
    path = _write_fixture(tmp_path)
    steps = trace_report.load_steps(path)
    lines = []
    trace_report.render_report(steps, trace_report.summarize(steps),
                               print_fn=lines.append)
    header = [l for l in lines if l.startswith("  step")][0]
    assert "mfu" not in header and "hbm" not in header


def test_compiled_programs_table_and_planner_delta(tmp_path):
    path = tmp_path / "steps.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in MFU_STEPS))
    (tmp_path / "trace.json").write_text(json.dumps({
        "traceEvents": [{"name": "step 0", "ph": "X", "ts": 0.0,
                         "dur": 5.0, "pid": 0, "tid": 2}],
        "otherData": {
            "compiled_programs": [
                {"name": "train/micro_step[flat]", "calls": 8,
                 "flops": 2.5e9, "bytes_accessed": 1e6,
                 "peak_hbm_bytes": 3 * 2**30, "source": "xla"},
                {"name": "train/apply_update", "calls": 2,
                 "flops": 1e7, "bytes_accessed": 5e5,
                 "peak_hbm_bytes": 4 * 2**30, "source": "xla"}],
            "mem_planner": {"stage": 2, "total_bytes": 2 * 2**30},
        }}))
    meta = trace_report.load_trace_metadata(str(tmp_path / "trace.json"))
    delta = trace_report.planner_vs_measured(meta)
    assert delta["measured_bytes"] == 4 * 2**30
    assert delta["ratio"] == 2.0

    rc = trace_report.main([str(tmp_path), "--json"])
    assert rc == 0

    lines = []
    steps = trace_report.load_steps(str(path))
    summary = trace_report.summarize(steps)
    summary["compiled_programs"] = meta["compiled_programs"]
    summary["mem_planner_delta"] = delta
    trace_report.render_report(steps, summary, print_fn=lines.append)
    text = "\n".join(lines)
    assert "== compiled programs (XLA cost model, per chip) ==" in text
    assert "train/micro_step[flat]" in text
    assert "planner vs measured (stage 2)" in text and "x2.00" in text


def test_cli_json_carries_compiled_programs(tmp_path, capsys):
    path = tmp_path / "steps.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in MFU_STEPS))
    (tmp_path / "trace.json").write_text(json.dumps({
        "traceEvents": [],
        "otherData": {"compiled_programs": [
            {"name": "p", "flops": 1.0, "peak_hbm_bytes": 10,
             "calls": 1, "source": "xla"}],
            "mem_planner": {"stage": 3, "total_bytes": 5}}}))
    rc = trace_report.main([str(tmp_path), "--json"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert abs(out["mfu_mean"] - 0.42) < 1e-12
    assert out["compiled_programs"][0]["name"] == "p"
    assert out["mem_planner_delta"]["ratio"] == 2.0


def test_moe_expert_util_columns(tmp_path):
    """Records carrying per-expert capacity utilization render the
    util_mean/util_max columns (ISSUE-15 satellite); archives without the
    vector keep the exact legacy table (has_util gate)."""
    recs = [dict(r) for r in MOE_FIXTURE]
    for r, util in zip(recs, ([0.2, 0.6], [0.4, 1.0])):
        moe = json.loads(json.dumps(r["moe"]))  # deep copy
        moe["layers"]["layers_0/moe"]["expert_util"] = util
        r["moe"] = moe
    path = tmp_path / "steps.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in recs))
    steps = trace_report.load_steps(str(path))
    summary = trace_report.summarize(steps)
    layer = summary["moe_layers"]["layers_0/moe"]
    assert abs(layer["expert_util_mean"] - 0.55) < 1e-9  # mean of means
    assert abs(layer["expert_util_max"] - 1.0) < 1e-9
    assert layer["experts"] == 2
    lines = []
    trace_report.render_report(steps, summary,
                               print_fn=lambda *a: lines.append(" ".join(
                                   str(x) for x in a)))
    text = "\n".join(lines)
    assert "util_mean" in text and "util_max" in text
    assert "0.550" in text and "1.000" in text
    # legacy archive: no util columns, table byte-stable
    path.write_text("".join(json.dumps(r) + "\n" for r in MOE_FIXTURE))
    steps = trace_report.load_steps(str(path))
    legacy = []
    trace_report.render_report(steps, trace_report.summarize(steps),
                               print_fn=lambda *a: legacy.append(" ".join(
                                   str(x) for x in a)))
    assert "util_mean" not in "\n".join(legacy)
