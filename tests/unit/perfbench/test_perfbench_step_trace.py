"""``perfbench/step_trace.py`` (ISSUE 37) on small traces written by
``program_trace.write_planes``: a launched step's spans joined to its
execution on the chip by the ``run_id`` that the runtime's enqueue event
inside ``ds:serve.launch`` and the module event share; the checks the table
makes of itself; the edges of the traced stretch; a parent's trace; and the
arithmetic of the five readers built on it.  Times in the source are
microseconds."""

import copy
import json
import os

import pytest

from deepspeed_tpu.telemetry import names
from perfbench import loader, program_trace, step_trace

import pb_helpers as pb
from test_perfbench_program_trace import PAGED, US, _write, op, span

RAGGED, BURST, TAKE = 33, 44, 55                    # program ids
RECORD = {"trace": {"busy_s": 1.0}}
MLP = "%fusion.4 = bf16[768,14336]{1,0} fusion(bf16[8]{0} %p), kind=kOutput"
WHILE = "%while.3 = (s32[], bf16[560,128,8,128]{3,2,1,0}) while(s32[] %p)"
METRICS = ("serve_ragged_step_device_ms", "serve_burst_iteration_device_ms",
           "serve_ragged_paged_kernel_ms",
           "serve_burst_paged_kernel_ms_per_iteration",
           "serve_launch_slack_ms_p05")
SERVING_CELLS = ["mistral7b_serve_chat", "evabyte_serve_longctx",
                 "command_a_plus_serve_rag", "pangu_ultra_moe_serve_reason"]


def module(program, pid, start, end, run_id):
    return (f"jit_{program}({pid})", start * US, end * US,
            {"run_id": run_id}, {})


def ragged_ops(start, end, paged):
    """A ragged step's ops: ``paged`` us in the kernel, the rest in the MLP."""
    return [op(PAGED, start, start + paged, RAGGED,
               "jit(ds_ragged_step_llama)/ds.attn/ds_paged_decode/"
               "pallas_call"),
            op(MLP, start + paged, end, RAGGED,
               "jit(ds_ragged_step_llama)/ds.mlp/dot_general")]


def burst_ops(start, end, calls, paged):
    """A burst's ops: a ``while`` (no scope path) that holds ``calls`` kernel
    calls of ``paged`` us each, the rest of every slot in the MLP."""
    slot = (end - start) / calls
    held = []
    for i in range(calls):
        s = start + i * slot
        held += [op(PAGED, s, s + paged, BURST,
                    "jit(ds_decode_burst)/while/body/ds.attn/"
                    "ds_paged_decode/pallas_call"),
                 op(MLP, s + paged, s + slot, BURST,
                    "jit(ds_decode_burst)/while/body/ds.mlp/dot_general")]
    return [op(WHILE, start, end, BURST)] + held


def enqueue(at, run_id):
    return ("DoEnqueueProgram", at * US, (at + 1) * US,
            {"run_id": run_id, "queue_id": 0}, {})


def turn(start, end, launch=None, fetched=None, **counts):
    ids = {k: v for k, v in (("launch", launch), ("fetched", fetched))
           if v is not None}
    return span("ds:serve.step", start, end, step=launch or 0,
                launched_ahead=1, **counts, **ids)


def life(n, kind, launch, fetch, burst_k=0, **device_counts):
    """The spans of step ``n``'s life: its launch span with the enqueue of
    its program (run_id 100 + n) and of the small program before it, and,
    where ``fetch`` is given, its fetch and dispatch."""
    out = [span("ds:serve.launch", *launch, launch=n, kind=kind,
                burst_k=burst_k),
           enqueue(launch[0] + 1, 500 + n),          # jit__take_chosen
           enqueue(launch[0] + 3, 100 + n)]
    if fetch:
        out += [span("ds:serve.fetch", *fetch, launch=n, **device_counts),
                span("ds:serve.dispatch", fetch[1], fetch[1] + 2, launch=n)]
    return out


#: A traced stretch [100, 2000] us of a trace that begins at 0.  Step 7 was
#: launched before the trace and straddles the stretch's start; steps 8-12
#: are whole (two bursts: 9 of four iterations, 12 of two; step 11 fetches
#: nothing, so fetch 12 brings two launches' device counts); step 13
#: straddles the stretch's end.
EXECS = {7: (20, 180), 8: (190, 500), 9: (510, 910), 10: (920, 1200),
         11: (1210, 1400), 12: (1405, 1605), 13: (1620, 2100)}
TRACE = {
    "/device:TPU:0": {
        "XLA Modules": [
            module("ds_ragged_step_llama", RAGGED, *EXECS[7], 107),
            module("_take_chosen", TAKE, 182, 186, 508),
            module("ds_ragged_step_llama", RAGGED, *EXECS[8], 108),
            module("ds_decode_burst", BURST, *EXECS[9], 109),
            module("ds_ragged_step_llama", RAGGED, *EXECS[10], 110),
            module("ds_ragged_step_llama", RAGGED, *EXECS[11], 111),
            module("ds_decode_burst", BURST, *EXECS[12], 112),
            module("_take_chosen", TAKE, 1608, 1614, 513),
            module("ds_ragged_step_llama", RAGGED, *EXECS[13], 113)],
        "XLA Ops": (
            ragged_ops(*EXECS[7], 60)
            + [op("%fusion.9 = s32[64]{0} fusion(s32[64]{0} %p), kind=kLoop",
                  182, 186, TAKE, "jit(_take_chosen)/select_n")]
            + ragged_ops(*EXECS[8], 100) + burst_ops(*EXECS[9], 8, 10)
            + ragged_ops(*EXECS[10], 80) + ragged_ops(*EXECS[11], 30)
            + burst_ops(*EXECS[12], 4, 20)
            + [op("%fusion.9 = s32[64]{0} fusion(s32[64]{0} %p), kind=kLoop",
                  1608, 1614, TAKE, "jit(_take_chosen)/select_n")]
            + ragged_ops(*EXECS[13], 200)),
    },
    "/host:CPU": {
        "python3": (
            [span("pb:traced", 100, 2000),
             turn(104, 190, 8, 7, kind="ragged", live_tokens=700,
                  token_budget=768, burst_k=0),
             span("ds:serve.fetch", 118, 184, launch=7),
             turn(198, 510, 9, 8, kind="burst", live_tokens=12,
                  token_budget=16, burst_k=4),
             turn(515, 918, 10, 9, kind="ragged", live_tokens=500,
                  token_budget=768, burst_k=0),
             turn(925, 1208, 11, 10, kind="ragged", live_tokens=768,
                  token_budget=768, burst_k=0),
             turn(1212, 1230, 12, 11, kind="burst", live_tokens=6,
                  token_budget=8, burst_k=2),
             turn(1408, 1615, 13, 12, kind="ragged", live_tokens=300,
                  token_budget=768, burst_k=0)]
            + life(8, "ragged", (105, 115), (216, 504), launches_covered=1,
                   expert_copies=40)
            + life(9, "burst", (200, 214), (532, 914), burst_k=4,
                   launches_covered=1, expert_copies=12)
            + life(10, "ragged", (520, 530), (942, 1204), launches_covered=1,
                   expert_copies=30)
            + life(11, "ragged", (930, 940), None)
            + life(12, "burst", (1215, 1225), (1422, 1609), burst_k=2,
                   launches_covered=2, expert_copies=50)
            + life(13, "ragged", (1410, 1420), None)),
    },
}


def edit(trace, line, match, change):
    """A copy of ``trace`` with ``change(event)`` in place of every host event
    of ``line`` that ``match`` accepts (None: dropped)."""
    out = copy.deepcopy(trace)
    evs = out["/host:CPU"][line]
    out["/host:CPU"][line] = [c for e in evs for c in (
        [change(e)] if match(e) else [e]) if c is not None]
    return out


def is_span(name, launch):
    return lambda e: e[0] == name and e[3].get("launch") == launch \
        and "step" not in e[3]


def table(tmp_path, trace):
    return step_trace.join(step_trace.read_file(_write(tmp_path, trace)),
                           names)


@pytest.fixture(scope="module")
def joined(tmp_path_factory):
    return table(tmp_path_factory.mktemp("joined"), TRACE)


def test_rows_are_joined_by_the_run_id_of_the_launch(joined):
    rows = {r["launch"]: r for r in joined["rows"]}
    assert sorted(rows) == [8, 9, 10, 11, 12]
    assert joined["joined_by"] == {"run_id": 6, "order": 0}   # 13: an edge
    assert joined["unjoined"] == 0
    assert joined["checks"] == {"kind": 0, "order": 0, "launch_clock": 0,
                                "fetch_clock": 0}
    assert [(r["kind"], r["burst_k"]) for r in rows.values()] == [
        ("ragged", 0), ("burst", 4), ("ragged", 0), ("ragged", 0),
        ("burst", 2)]
    for n, r in rows.items():
        assert (r["exec_start"], r["exec_end"]) == tuple(
            t * US for t in EXECS[n])
        assert r["device_ms"] == pytest.approx(
            (EXECS[n][1] - EXECS[n][0]) / 1e3)
        assert r["launch_start"] <= r["exec_start"]
        assert r["counts"]["launch"] == n       # the turn that launched it
        assert r["before_end"] == EXECS[n - 1][1] * US
    # the turn's counts, the fetch's device counts
    assert rows[10]["counts"]["live_tokens"] == 500
    assert rows[10]["device_counts"] == {"launches_covered": 1,
                                         "expert_copies": 30}
    assert (rows[10]["fetch_start"], rows[10]["fetch_end"]) == (942 * US,
                                                               1204 * US)
    # a step that fetched nothing has no fetch; its counts ride with the next
    assert rows[11]["fetch_end"] is None and not rows[11]["device_counts"]
    assert rows[12]["device_counts"]["launches_covered"] == 2


def test_chip_time_inside_an_execution_counts_leaf_ops_once(joined):
    rows = {r["launch"]: r for r in joined["rows"]}
    assert rows[8]["class_ms"] == pytest.approx(
        {"paged_kernel": 0.1, "mlp": 0.21})
    assert rows[8]["kernel_ms"] == pytest.approx({"ds_paged_decode": 0.1})
    assert rows[8]["scope_ms"] == pytest.approx({"ds.attn": 0.1,
                                                 "ds.mlp": 0.21})
    # the burst's ``while`` holds its ops and is not counted beside them
    assert rows[9]["class_ms"] == pytest.approx(
        {"paged_kernel": 0.08, "mlp": 0.32})
    assert sum(rows[9]["class_ms"].values()) == pytest.approx(
        rows[9]["device_ms"])


def test_an_edge_step_is_clipped_to_the_stretch_and_the_table_adds_up(joined):
    edges = {r["launch"]: r for r in joined["edges"]}
    assert sorted(edges) == [7, 13]
    # step 7: launched before the trace, [20, 180] of which [100, 180] inside
    assert edges[7]["launch_start"] is None and not edges[7]["whole"]
    assert edges[7]["inside_ms"] == pytest.approx(0.08)
    assert edges[7]["device_ms"] == pytest.approx(0.16)
    assert edges[7]["paged_kernel_ms"] == pytest.approx(0.0)  # [20, 80]
    # step 13: [1620, 2100] of which [1620, 2000]; its kernel [1620, 1820]
    assert edges[13]["inside_ms"] == pytest.approx(0.38)
    assert edges[13]["paged_kernel_ms"] == pytest.approx(0.2)
    whole = sum(r["paged_kernel_ms"] for r in joined["rows"])
    assert whole == pytest.approx(0.1 + 0.08 + 0.08 + 0.03 + 0.08)
    assert whole + 0.2 == pytest.approx(joined["paged_kernel_ms_in_stretch"])
    # the joined executions cover the chip's busy time but the two small
    # programs between steps
    assert joined["busy_ms"] == pytest.approx(
        (180 - 100 + 310 + 400 + 280 + 190 + 200 + 380 + 4 + 6) / 1e3)
    assert joined["uncovered_ms"] == pytest.approx(0.010)
    assert joined["uncovered_ms_by_program"] == pytest.approx(
        {"jit__take_chosen": 0.010})


FAULTS = {
    # name -> (line edit, the check that counts it)
    "kind": (lambda t: edit(t, "python3", is_span("ds:serve.launch", 10),
                            lambda e: e[:3] + (dict(e[3], kind="burst"), )
                            + e[4:]), "kind"),
    "launched_after_it_ran": (
        lambda t: edit(t, "python3", is_span("ds:serve.launch", 10),
                       lambda e: (e[0], 921 * US, 922 * US) + e[3:]),
        "launch_clock"),
    "fetched_before_it_ended": (
        lambda t: edit(t, "python3", is_span("ds:serve.fetch", 10),
                       lambda e: (e[0], 942 * US, 990 * US) + e[3:]),
        "fetch_clock"),         # 210 us before its step ended: over the slack
    "an_enqueue_that_names_another_step": (
        lambda t: edit(t, "python3", lambda e: e[0] == "DoEnqueueProgram"
                       and e[3]["run_id"] == 110,
                       lambda e: e[:3] + (dict(e[3], run_id=111), ) + e[4:]),
        "order"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_is_counted_by_its_check(tmp_path, fault):
    make, check = FAULTS[fault]
    t = table(tmp_path, make(TRACE))
    assert t["checks"][check] == 1
    assert sum(t["checks"].values()) == 1


def test_an_execution_within_the_slack_of_its_fetch_passes(tmp_path):
    late = edit(TRACE, "python3", is_span("ds:serve.fetch", 10),
                lambda e: (e[0], 942 * US, 1010 * US) + e[3:])     # 190 us
    assert sum(table(tmp_path, late)["checks"].values()) == 0


def test_a_launch_without_its_enqueue_is_joined_by_the_order(tmp_path):
    bare = edit(TRACE, "python3", lambda e: e[0] == "DoEnqueueProgram"
                and e[3]["run_id"] == 110, lambda e: None)
    t = table(tmp_path, bare)
    assert t["joined_by"] == {"run_id": 5, "order": 1}
    assert [r["launch"] for r in t["rows"]] == [8, 9, 10, 11, 12]
    assert sum(t["checks"].values()) == 0 and t["unjoined"] == 0


def test_a_lost_launch_span_inside_the_stretch_is_an_unjoined_row(tmp_path):
    lost = edit(TRACE, "python3", is_span("ds:serve.launch", 10),
                lambda e: None)
    t = table(tmp_path, lost)
    assert t["unjoined"] == 1
    assert [r["launch"] for r in t["rows"]] == [8, 9, 11, 12]


def _without_ids(trace):
    """The trace a parent before PR 37 writes: no ``launch`` on any span."""
    strip = lambda e: e[:3] + ({k: v for k, v in e[3].items()
                                if k not in ("launch", "fetched")}, ) + e[4:]
    return edit(trace, "python3", lambda e: e[0].startswith("ds:"), strip)


def test_a_parents_trace_gives_no_table(tmp_path):
    assert table(tmp_path, _without_ids(TRACE)) is None


# ------------------------------------------------------------- the readers
#: the stretch's whole ragged steps 8, 10, 11 and whole bursts 9 (k 4), 12
#: (k 2); milliseconds
WANT = {
    "serve_ragged_step_device_ms": (0.31 + 0.28 + 0.19) / 3,
    "serve_burst_iteration_device_ms": (0.4 + 0.2) / 6,
    "serve_ragged_paged_kernel_ms": (0.1 + 0.08 + 0.03) / 3,
    "serve_burst_paged_kernel_ms_per_iteration": (0.08 + 0.08) / 6,
    # steps 8..12 were launched ahead: end of the execution before minus end
    # of the launch span: 65, 286, 380, 260, 175 us; the 5th percentile by
    # linear interpolation
    "serve_launch_slack_ms_p05": (65 + 0.2 * (175 - 65)) / 1e3,
}


@pytest.fixture
def read(tmp_path, monkeypatch):
    """``read(metric, trace)``: the metric's reader on a written trace."""
    monkeypatch.setattr(program_trace, "ROOT", str(tmp_path))
    monkeypatch.setattr(step_trace, "_CACHE", {})
    written = []

    def run(metric, trace, record=RECORD):
        if trace is not None:       # the newest trace under the root
            written.append(_write(tmp_path, trace, f"cell{len(written)}"))
            os.utime(written[-1], (2e9 + len(written), ) * 2)
        return loader.load_reader(pb.ROOT, metric).read(record)

    return run


@pytest.mark.parametrize("metric", METRICS)
def test_reader(read, metric):
    assert read(metric, None) is None                    # no trace file
    assert read(metric, TRACE, {"trace": None}) is None  # an untraced run
    assert read(metric, TRACE) == pytest.approx(WANT[metric])


@pytest.mark.parametrize("metric", METRICS)
def test_reader_gives_nothing_on_a_parents_trace(read, metric, capsys):
    assert read(metric, _without_ids(TRACE)) is None
    assert "INFO step_trace" not in capsys.readouterr().out


def _only_edge_bursts():
    """A stretch [100, 800] that holds no WHOLE burst: burst 9 straddles its
    end (EvaByte has three bursts in five seconds)."""
    t = copy.deepcopy(TRACE)
    t["/host:CPU"]["python3"][0] = span("pb:traced", 100, 800)
    return t


@pytest.mark.parametrize("metric,want", [
    # burst 9 is [510, 910], [510, 800] of it inside the stretch: an edge
    ("serve_burst_iteration_device_ms", None),
    ("serve_burst_paged_kernel_ms_per_iteration", None),
    ("serve_ragged_step_device_ms", 0.31),
])
def test_a_stretch_with_no_whole_burst_reads_none_for_the_bursts(
        read, metric, want):
    assert read(metric, _only_edge_bursts()) == \
        (want and pytest.approx(want))


def test_a_stretch_with_no_burst_at_all_reads_none_for_the_bursts(read):
    t = copy.deepcopy(TRACE)
    t["/host:CPU"]["python3"][0] = span("pb:traced", 100, 505)
    assert read("serve_burst_iteration_device_ms", t) is None
    assert read("serve_burst_paged_kernel_ms_per_iteration", t) is None
    assert read("serve_ragged_step_device_ms", t) == pytest.approx(0.31)


def test_one_info_line_a_traced_run(read, capsys):
    for metric in METRICS:
        read(metric, TRACE if metric == METRICS[0] else None)
    lines = [line for line in capsys.readouterr().out.splitlines()
             if line.startswith("INFO step_trace: ")]
    assert len(lines) == 1
    said = json.loads(lines[0].split(": ", 1)[1])
    assert (said["rows"], said["edge_rows"], said["unjoined"]) == (5, 2, 0)
    assert said["failed_checks"] == {"kind": 0, "order": 0,
                                     "launch_clock": 0, "fetch_clock": 0}
    assert said["joined_by"] == {"run_id": 6, "order": 0}
    ragged, burst = said["kinds"]["ragged"], said["kinds"]["burst"]
    assert (ragged["n"], ragged["iterations"]) == (3, 3)
    assert (burst["n"], burst["iterations"]) == (2, 6)
    assert ragged["device_ms_mean"] == pytest.approx(0.26)
    assert ragged["device_ms_p50"] == pytest.approx(0.28)
    assert ragged["sums"]["live_tokens"] == 700 + 500 + 768
    assert ragged["sums"]["expert_copies"] == 40 + 30
    assert burst["sums"]["expert_copies"] == 12 + 50
    assert burst["sums"]["launches_covered"] == 3
    assert burst["kernel_ms"] == pytest.approx({"ds_paged_decode": 0.16})
    assert said["launched_ahead_share"] == 1.0
    assert said["covered_share"] == pytest.approx(100 * (1 - 0.010 / 1.85))
    assert said["uncovered_ms_by_program"] == pytest.approx(
        {"jit__take_chosen": 0.010})
    parts = said["paged_kernel_ms"]
    assert parts["whole_ragged"] + parts["whole_burst"] + parts["edges"] == \
        pytest.approx(parts["in_stretch"])
    assert [e["launch"] for e in said["edges"]] == [7, 13]


def test_the_manifest_lists_each_metric_where_its_reader_finds_steps():
    by_name = {m["name"]: m for m in pb.read_manifest(pb.ROOT)["per_layer"]}
    mine = [by_name[n] for n in METRICS]
    # EvaByte's traced stretch holds no decode burst (every step carries
    # prefill rows): the two metrics of a burst have nothing to read there
    no_burst = [c for c in SERVING_CELLS if c != "evabyte_serve_longctx"]
    for m in mine:
        assert m["workloads"] == (no_burst if "_burst_" in m["name"]
                                  else SERVING_CELLS)
        assert m["moves"] == "serve_tokens_per_s" and m["unit"] == "ms"
    assert [(m["better"], m["source"], m["layer"]) for m in mine] \
        == [("lower", "device_trace", "ragged engine step")] * 2 \
        + [("lower", "device_trace", "kernels")] * 2 \
        + [("higher", "program_span", "scheduler")]
