"""``perfbench/train_step_trace.py`` (ISSUE 56) on small traces written by
``program_trace.write_planes``: a launched training program's span joined to
its execution on the chip by ``run_id`` and by order; the gap before it split
at the enqueue's end and put down to the innermost span and to the runtime's
own host events; ops under a ``while`` counted once; the idle that adds up;
accumulation over two micro-steps; the stretch's edges; the five readers and
their manifest entries.  Times in the source are microseconds."""

import copy
import os
import re

import pytest

from deepspeed_tpu.telemetry import names
from perfbench import loader, program_trace, train_step_trace as tst

import pb_helpers as pb
from test_perfbench_program_trace import FLASH, GATHER, US, _write, op, span

MICRO, ACC, APPLY, FOLD = 11, 12, 13, 77            # program ids
RECORD = {"trace": {"busy_s": 1.0}}
MS = 1e-3                                           # a microsecond in ms
METRICS = ("train_micro_step_device_ms", "train_device_gap_ms_per_step",
           "train_host_late_ms_per_step", "train_attn_proj_ms_per_step",
           "train_attn_glue_ms_per_step")
TRAINING_CELLS = ["mistral7b_train_4k", "mistral7b_train_4k_zero3x4",
                  "smallthinker_21b_train_8k"]
FWD = "jit(ds_micro_flat)/jvp(LlamaModel)/"
BWD = "jit(ds_micro_flat)/transpose(jvp(LlamaModel))/"
FUSION = "%fusion.{} = bf16[4096,4096]{{1,0}} fusion(bf16[8]{{0}} %p), " \
    "kind=kOutput"
WHILE = "%while.3 = (s32[], f32[4096,4096]{1,0}) while(s32[] %p)"


def module(program, pid, start, end, run_id):
    return (f"jit_{program}({pid})", start * US, end * US,
            {"run_id": run_id}, {})


def enqueue(at, run_id, name="DoEnqueueProgram"):
    return (name, at * US, (at + 1) * US, {"run_id": run_id}, {})


def micro_ops(t):
    """A micro-step's 400 us: 390 busy, 10 idle at t + 170; the attention
    block's parts under their scopes, a ``while`` that holds the head's two
    ops, a gather of the block's weights, two ops under no scope."""
    attn = "layers_0/self_attn/"
    return [
        op(FUSION.format(1), t, t + 50, MICRO,
           FWD + "ds.embed/embed_tokens/gather"),
        op(FUSION.format(2), t + 50, t + 90, MICRO,
           FWD + attn + "ds.attn_proj/q_proj/dot_general"),
        op(FUSION.format(3), t + 90, t + 100, MICRO,
           FWD + attn + "ds.attn_rotary/mul"),
        op(FUSION.format(4), t + 100, t + 110, MICRO,
           FWD + attn + "ds.attn_kv_repeat/broadcast_in_dim"),
        op(FUSION.format(5), t + 110, t + 120, MICRO,
           FWD + attn + "ds.attn_core/transpose"),
        op(FLASH, t + 120, t + 170, MICRO,
           FWD + attn + "ds.attn_core/ds_flash_fwd/pallas_call"),
        op(WHILE, t + 180, t + 300, MICRO),
        op(FUSION.format(6), t + 180, t + 240, MICRO,
           FWD + "ds.lm_head_loss/while/body/dot_general"),
        op(FUSION.format(7), t + 240, t + 300, MICRO,
           FWD + "ds.lm_head_loss/while/body/reduce"),
        op(FUSION.format(8), t + 300, t + 340, MICRO,
           BWD + attn + "ds.attn_proj/o_proj/dot_general"),
        op(GATHER, t + 340, t + 360, MICRO,
           FWD + attn + "ds.attn_proj/k_proj/all-gather"),
        op(FUSION.format(9), t + 360, t + 390, MICRO, FWD + "layers_0/add"),
        op("%copy.3 = f32[8]{0} copy(f32[8]{0} %p)", t + 390, t + 400, MICRO),
    ]


def update_ops(t, end=None):
    return [op(FUSION.format(61), t, end or t + 100, APPLY,
               "jit(ds_apply_update)/convert_element_type")]


#: three whole steps (4, 5, 6) without accumulation in a stretch 100..2500;
#: the execution that leads the line was launched before the trace began, the
#: last step's update straddles the stretch's end
LOOP = {
    "/device:TPU:0": {
        "XLA Modules": [
            module("ds_apply_update", APPLY, 0, 150, 900),
            module("ds_micro_flat", MICRO, 160, 560, 901),
            module("ds_apply_update", APPLY, 600, 700, 902),
            module("ds_micro_flat", MICRO, 780, 1180, 903),
            module("ds_apply_update", APPLY, 1190, 1290, 904),
            module("_threefry_fold_in", FOLD, 1320, 1340, 950),
            module("ds_micro_flat", MICRO, 1400, 1800, 905),
            module("ds_apply_update", APPLY, 1800, 1900, 906),
            module("ds_micro_flat", MICRO, 1900, 2300, 907),
            module("ds_apply_update", APPLY, 2450, 2550, 908)],
        "XLA Ops": (
            update_ops(0, 150) + micro_ops(160) + update_ops(600)
            + micro_ops(780) + update_ops(1190)
            + [op(FUSION.format(70), 1320, 1340, FOLD, "jit(_threefry)/x")]
            + micro_ops(1400) + update_ops(1800) + micro_ops(1900)
            + update_ops(2450)),
    },
    "/device:TPU:1": {"XLA Ops": [op("%fusion.1 = f32[8]{0} fusion()", 0,
                                     2550, MICRO)]},
    "/host:CPU": {
        "python3": [
            span("pb:traced", 100, 2500),
            # step 4: enqueued WHILE the chip idled (150..160, split at 151)
            span("ds:train.micro", 110, 200, step=4, micro_step=4),
            span("pb:step", 562, 655),
            span("ds:train.apply", 565, 650, step=4, micro_step=4),
            # step 5: the batch, a stretch of the engine under no ds: span,
            # the call; its update enqueued BEFORE the chip went idle
            span("pb:forward", 695, 805),
            span("ds:train.shard_batch", 700, 720, step=5, micro_step=5),
            span("ds:train.micro", 740, 800, step=5, micro_step=5),
            span("ds:train.apply", 810, 900, step=5, micro_step=5),
            # step 6: the enqueue's end AFTER the execution's start
            span("ds:train.micro", 1300, 1420, step=6, micro_step=6),
            span("ds:train.apply", 1425, 1500, step=6, micro_step=6),
            span("ds:train.micro", 1510, 1600, step=7, micro_step=7),
            span("ds:train.apply", 1610, 1700, step=7, micro_step=7),
            span("pb:wait_for_device", 1700, 2490),
            span("PjitFunction(ds_micro_flat)", 740, 800),
            span("Tiny", 705, 710)],
        "main/666": [
            enqueue(150, 901), enqueue(570, 902), enqueue(760, 903),
            enqueue(820, 904), enqueue(1305, 950), enqueue(1410, 905),
            enqueue(1430, 906), enqueue(1520, 907), enqueue(1620, 908),
            span("AllocateBufferAwait", 741, 761)],
    },
}

#: two whole steps (8, 9) of two micro-steps each: a step's first fold keeps
#: the gradients and launches nothing, its second runs ``ds_accumulate``
def _accumulating():
    mods, ops, host, runtime = [], [], [span("pb:traced", 5, 3000)], []
    mods.append(module("ds_apply_update", APPLY, 0, 25, 100))
    ops += update_ops(0, 25)
    for k, t in enumerate((0, 1100)):
        step, rid = 8 + k, 10 * (k + 1)
        ids = [dict(step=step, micro_step=2 * step + i) for i in (0, 1)]
        mods += [module("ds_micro_flat", MICRO, t + 30, t + 430, rid + 1),
                 module("ds_micro_flat", MICRO, t + 470, t + 870, rid + 2),
                 module("ds_accumulate", ACC, t + 890, t + 940, rid + 3),
                 module("ds_apply_update", APPLY, t + 940, t + 1040, rid + 4)]
        ops += micro_ops(t + 30) + micro_ops(t + 470) + [
            op(FUSION.format(80), t + 890, t + 940, ACC,
               "jit(ds_accumulate)/add")] + update_ops(t + 940)
        host += [span("ds:train.micro", t + 10, t + 60, **ids[0]),
                 span("ds:train.backward", t + 438, t + 447, **ids[0]),
                 span("ds:train.accumulate", t + 440, t + 445, **ids[0]),
                 span("ds:train.micro", t + 450, t + 500, **ids[1]),
                 span("ds:train.backward", t + 873, t + 902, **ids[1]),
                 span("ds:train.accumulate", t + 875, t + 900, **ids[1]),
                 span("ds:train.apply", t + 905, t + 950, **ids[1])]
        runtime += [enqueue(t + 20, rid + 1), enqueue(t + 460, rid + 2),
                    enqueue(t + 880, rid + 3), enqueue(t + 910, rid + 4)]
    # a later event on the line: the last update's end is not the trace's
    mods.append(module("convert_element_type", FOLD, 2200, 2201, 99))
    return {"/device:TPU:0": {"XLA Modules": mods, "XLA Ops": ops},
            "/host:CPU": {"python3": host, "main/666": runtime}}


def table(tmp_path, planes):
    return tst.join(tst.read_file(_write(tmp_path, planes)), names)


@pytest.fixture(scope="module")
def loop(tmp_path_factory):
    return table(tmp_path_factory.mktemp("loop"), LOOP)


def row(t, step, kind):
    return next(r for r in t["rows"] if r["step"] == step
                and kind in r["program"])


def test_the_reader_keeps_enqueues_spans_and_long_host_events(tmp_path):
    planes = tst.read_file(_write(tmp_path, LOOP))
    assert set(planes) == {"/device:TPU:0", "/host:CPU"}     # chip 0 only
    kept = {e[0] for evs in planes["/host:CPU"].values() for e in evs}
    assert {"DoEnqueueProgram", "AllocateBufferAwait", "pb:forward",
            "PjitFunction(ds_micro_flat)"} <= kept
    assert "Tiny" not in kept               # 5 us: under HOST_EVENT_NS


def test_rows_are_joined_by_run_id_and_the_table_checks_itself(loop):
    assert loop["steps"] == [4, 5, 6]
    assert loop["joined_by"] == {"run_id": 8, "order": 0}
    assert loop["unjoined"] == 0
    assert loop["checks"] == {"program": 0, "clock": 0, "order": 0,
                              "unlabelled": 0, "adds_up": 0}
    r = row(loop, 5, "ds_micro")
    assert (r["program"], r["micro_step"]) == ("ds_micro_flat", 5)
    assert (r["span_start"], r["enqueued_at"], r["exec_start"],
            r["before_end"]) == (740 * US, 761 * US, 780 * US, 700 * US)
    assert r["device_ms"] == pytest.approx(400 * MS)


@pytest.mark.parametrize("step, kind, gap, late, queued", [
    (4, "ds_micro", 10, 1, 9),          # enqueued while the chip idled
    (5, "ds_apply", 10, 0, 10),         # enqueued before it went idle
    (6, "ds_micro", 90, 90, 0),         # enqueue's end after the start
    (6, "ds_apply", 0, 0, 0),           # back to back
])
def test_a_gap_is_split_at_the_enqueues_end(loop, step, kind, gap, late,
                                            queued):
    r = row(loop, step, kind)
    assert r["gap_ms"] == pytest.approx(gap * MS)
    assert r["host_late_ms"] == pytest.approx(late * MS)
    assert r["queued_ms"] == pytest.approx(queued * MS)


def test_the_hosts_part_goes_to_the_innermost_span_and_the_open_events(loop):
    # 700..761 of step 5's micro-step: the batch, an engine stretch under the
    # benchmark's span alone, the call up to its enqueue's end
    r = row(loop, 5, "ds_micro")
    assert r["host_late_ms_by_span"] == pytest.approx({
        "ds:train.shard_batch": 20 * MS, "pb:forward": 20 * MS,
        "ds:train.micro": 21 * MS})
    assert r["host_late_ms_by_event"] == pytest.approx({
        "AllocateBufferAwait": 20 * MS,
        "PjitFunction(ds_micro_flat)": 21 * MS})
    # 560..571 of step 4's update: under no span, the benchmark's, its own
    assert row(loop, 4, "ds_apply")["host_late_ms_by_span"] == pytest.approx({
        "outside_spans": 2 * MS, "pb:step": 3 * MS, "ds:train.apply": 6 * MS})
    # another program's ops in the gap are busy time, not gap
    r = row(loop, 6, "ds_micro")
    assert r["host_late_ms_by_span"] == pytest.approx({
        "outside_spans": 10 * MS, "ds:train.micro": 80 * MS})
    assert loop["other_programs_ms"] == pytest.approx(
        {"_threefry_fold_in": 20 * MS})


def test_a_long_wait_under_a_benchmark_span_fails_the_check(tmp_path):
    planes = copy.deepcopy(LOOP)
    host = planes["/host:CPU"]["python3"]
    # step 6's micro-step: no other program in its gap (1290..1400), the
    # engine under the benchmark's span alone until its own opens at 1398
    host[:] = [e for e in host if (e[0], e[3].get("step"))
               != ("ds:train.micro", 6)] + [
        span("pb:forward", 1291, 1425),
        span("ds:train.micro", 1398, 1420, step=6, micro_step=6)]
    chip = planes["/device:TPU:0"]
    chip["XLA Modules"] = [m for m in chip["XLA Modules"]
                           if "_threefry" not in m[0]]
    chip["XLA Ops"] = [e for e in chip["XLA Ops"] if e[1] != 1320 * US]
    t = table(tmp_path, planes)
    assert row(t, 6, "ds_micro")["host_late_ms_by_span"] == pytest.approx({
        "outside_spans": 1 * MS, "pb:forward": 107 * MS,
        "ds:train.micro": 2 * MS})
    assert t["checks"]["unlabelled"] == 1


def test_every_op_is_counted_once_and_the_attention_block_has_its_parts(loop):
    r = row(loop, 4, "ds_micro")
    # the while's two leaves, not the while beside them
    assert r["scope_ms"]["ds.lm_head_loss"] == pytest.approx(120 * MS)
    assert sum(r["class_ms"].values()) == pytest.approx(390 * MS)
    assert sum(r["innermost_scope_ms"].values()) + r["unscoped_ms"] \
        == pytest.approx(390 * MS)
    assert r["idle_inside_ms"] == pytest.approx(10 * MS)
    assert r["idle_before_first_op_ms"] == 0.0
    assert r["unscoped_ms"] == pytest.approx(40 * MS)
    assert {k[0]: v for k, v in r["unscoped_ops"].items()} == pytest.approx(
        {"fusion.9 fusion bf16[4096,4096]": 30 * MS,
         "copy.3 copy f32[8]": 10 * MS})
    # forward and backward products; rotary, repeat and the layout change but
    # not the kernel; the weights' gather is the collectives'
    assert r["attn_proj_ms"] == pytest.approx(80 * MS)
    assert r["attn_glue_ms"] == pytest.approx(30 * MS)
    assert r["attn_collective_ms"] == pytest.approx(20 * MS)
    assert r["class_ms"]["attention"] == pytest.approx(
        r["attn_proj_ms"] + r["attn_glue_ms"])
    assert r["class_ms"]["flash_kernel"] == pytest.approx(50 * MS)
    assert r["kernel_ms"] == pytest.approx({"ds_flash_fwd": 50 * MS})


def test_gaps_and_idle_inside_executions_are_the_stretchs_idle(loop):
    idle = loop["idle"]
    assert idle["between_ms"] == pytest.approx(
        (10 + 40 + 80 + 10 + 90 + 150) * MS)
    assert idle["inside_ms"] == pytest.approx(4 * 10 * MS)
    assert idle["ends_ms"] == 0.0
    assert idle["stretch_ms"] == pytest.approx(420 * MS)
    assert loop["stretch_ms"] - loop["busy_ms"] == pytest.approx(420 * MS)


def test_a_wait_inside_an_execution_before_its_first_op(tmp_path):
    """A four-chip host's form of the gap: the execution has started, no op
    runs yet."""
    planes = copy.deepcopy(LOOP)
    chip = planes["/device:TPU:0"]
    chip["XLA Ops"] = [e for e in chip["XLA Ops"]
                       if not 780 * US <= e[1] < 830 * US]   # step 5's embed
    t = table(tmp_path, planes)
    r = row(t, 5, "ds_micro")
    assert r["idle_before_first_op_ms"] == pytest.approx(50 * MS)
    assert r["idle_inside_ms"] == pytest.approx(60 * MS)
    assert t["checks"]["adds_up"] == 0


def test_edge_rows_are_not_counted(loop):
    # step 7's update straddles the stretch's end, so the step is not whole
    # and its micro-step, whole as a row, is in no reader's mean
    assert [(r["step"], r["kind"]) for r in loop["edges"]] == [
        (None, names.PROGRAM_APPLY), (7, names.PROGRAM_APPLY)]
    assert row(loop, 7, "ds_micro")["whole"]
    assert {r["step"] for r in tst.whole_rows(loop)} == {4, 5, 6}
    s = tst.summarize(loop)
    assert (s["whole_steps"], s["rows"], s["edge_rows"]) == (3, 6, 3)
    assert s["programs"]["ds_micro_flat"] == {"n": 3, "device_ms_mean":
                                              pytest.approx(400 * MS)}
    assert s["gap_ms_per_step"]["host_late_by_span"]["ds:train.micro"] \
        == pytest.approx((1 + 21 + 80) / 3 * MS)
    assert s["innermost_scope_ms"]["ds.attn_proj"] == pytest.approx(100 * MS)
    assert s["attention_ms"] == pytest.approx({
        "proj": 80 * MS, "glue": 30 * MS, "collective": 20 * MS,
        "flash_kernel": 50 * MS, "class_attention": 110 * MS,
        "outside_the_four_scopes": 0.0}, abs=1e-9)
    # the micro-step's ops under no scope (the optimizer's are its program's)
    assert s["unscoped_ms"] == pytest.approx(140 * MS)
    assert s["micro_step_unscoped_ms_by_class"] == pytest.approx(
        {"residual": 30 * MS, "unclassed": 10 * MS})
    assert [u["op"] for u in s["micro_step_unscoped_longest"]] == [
        "fusion.9 fusion bf16[4096,4096]", "copy.3 copy f32[8]"]
    # step 6's micro-step starts 11 us before its enqueue ends: the clocks
    assert s["started_before_enqueued_ms_max"] == pytest.approx(11 * MS)


@pytest.mark.parametrize("renamed", ["EnqueueRenamed", None])
def test_a_trace_without_the_enqueue_event_is_joined_by_order(tmp_path,
                                                              renamed):
    planes = copy.deepcopy(LOOP)
    runtime = planes["/host:CPU"]["main/666"]
    runtime[:] = [enqueue(e[1] / US, e[3]["run_id"], renamed)
                  for e in runtime if "run_id" in e[3] and renamed]
    t = table(tmp_path, planes)
    assert t["joined_by"] == {"run_id": 0, "order": 8}
    assert t["steps"] == [4, 5, 6] and t["unjoined"] == 0
    assert not any(t["checks"].values())
    # split at the span's end: 200 is past the start, so all of it is late
    r = row(t, 4, "ds_micro")
    assert (r["host_late_ms"], r["queued_ms"]) == pytest.approx((10 * MS, 0))
    assert tst.per_step(t, "gap_ms") == pytest.approx(230 / 3 * MS)


def test_an_enqueue_that_names_another_spans_execution_is_an_order_fault(
        tmp_path):
    planes = copy.deepcopy(LOOP)
    runtime = planes["/host:CPU"]["main/666"]
    runtime[2], runtime[5] = enqueue(760, 905), enqueue(1410, 903)
    t = table(tmp_path, planes)
    assert t["checks"]["order"] == 2


def test_a_lost_span_is_unjoined(tmp_path):
    planes = copy.deepcopy(LOOP)
    host = planes["/host:CPU"]["python3"]
    host[:] = [e for e in host if e[3].get("step") != 7]
    t = table(tmp_path, planes)
    assert t["unjoined"] == 2               # two executions past the spans


def test_accumulation_over_two_micro_steps(tmp_path):
    t = table(tmp_path, _accumulating())
    assert t["steps"] == [8, 9] and t["unjoined"] == 0
    assert not any(t["checks"].values())
    assert t["joined_by"] == {"run_id": 8, "order": 0}
    s = tst.summarize(t)
    assert {k: v["n"] for k, v in s["programs"].items()} == {
        "ds_micro_flat": 4, "ds_accumulate": 2, "ds_apply_update": 2}
    acc = row(t, 8, "ds_accumulate")
    assert (acc["micro_step"], acc["gap_ms"], acc["host_late_ms"]) \
        == (17, pytest.approx(20 * MS), pytest.approx(11 * MS))
    assert acc["host_late_ms_by_span"] == pytest.approx({
        "ds:train.backward": 2 * MS, "ds:train.accumulate": 6 * MS,
        "outside_spans": 3 * MS})
    assert acc["class_ms"] == pytest.approx({"accumulate": 50 * MS})
    # a step's mean micro-step, and its gaps: 5 + 40 + 20 and 90 + 40 + 20
    assert tst.micro_step_device_ms(t) == pytest.approx(400 * MS)
    assert tst.per_step(t, "gap_ms") == pytest.approx((65 + 150) / 2 * MS)
    assert tst.per_step(t, "attn_proj_ms") == pytest.approx(160 * MS)


def test_the_lines_last_event_may_be_cut_by_the_traces_end(tmp_path):
    planes = _accumulating()
    planes["/device:TPU:0"]["XLA Modules"].pop()
    t = table(tmp_path, planes)
    assert t["steps"] == [8]
    assert [(r["step"], r["kind"]) for r in t["edges"]] == [
        (None, names.PROGRAM_APPLY), (9, names.PROGRAM_APPLY)]


# ------------------------------------------------------------- the readers
@pytest.fixture()
def traced_root(tmp_path, monkeypatch):
    monkeypatch.setattr(tst, "_CACHE", {})
    made = []

    def write(planes):      # a trace of its own each time, the newest read
        made.append(_write(tmp_path / str(len(made)), planes))
        os.utime(made[-1], (len(made), len(made)))
    monkeypatch.setattr(program_trace, "find_trace",
                        lambda root=None: made[-1] if made else None)
    return write


def reader(name):
    return loader.load_reader(pb.ROOT, name)


def test_the_five_readers_on_the_table(traced_root, capsys):
    traced_root(LOOP)
    values = {m: reader(m).read(RECORD) for m in METRICS}
    assert values == pytest.approx({
        "train_micro_step_device_ms": 400 * MS,
        "train_device_gap_ms_per_step": 230 / 3 * MS,
        "train_host_late_ms_per_step": 163 / 3 * MS,
        "train_attn_proj_ms_per_step": 80 * MS,
        "train_attn_glue_ms_per_step": 30 * MS})
    out = capsys.readouterr().out
    assert out.count("INFO train_step_trace: ") == 1     # one read a process
    assert '"whole_steps": 3' in out and '"reader_s"' in out


def test_a_loop_that_never_waits_reads_zero_not_none(traced_root):
    planes = copy.deepcopy(_accumulating())
    chip = planes["/device:TPU:0"]
    # one op from the stretch's start to its end: no gap anywhere
    chip["XLA Ops"].append(op(FUSION.format(99), 0, 3000, MICRO))
    traced_root(planes)
    for m in METRICS[1:3]:
        assert reader(m).read(RECORD) == 0.0


def test_an_executable_an_older_tree_compiled_reads_zero_not_none(
        traced_root):
    """The compile cache's key leaves metadata out: a tree with the names may
    run a program whose ops carry none of them."""
    planes = copy.deepcopy(LOOP)
    chip = planes["/device:TPU:0"]
    chip["XLA Ops"] = [
        (*e[:4], {**e[4], "tf_op": re.sub(r"ds\.attn_\w+/", "",
                                          e[4].get("tf_op", ""))})
        for e in chip["XLA Ops"]]
    traced_root(planes)
    assert [reader(m).read(RECORD) for m in METRICS[3:]] == [0.0, 0.0]
    assert reader(METRICS[0]).read(RECORD) == pytest.approx(400 * MS)
    s = tst.summarize(tst.traced(RECORD))
    assert s["attention_ms"]["outside_the_four_scopes"] == pytest.approx(
        s["attention_ms"]["class_attention"]) == pytest.approx(110 * MS)


@pytest.mark.parametrize("name", METRICS)
def test_readers_return_none_where_there_is_nothing_to_read(
        name, traced_root, monkeypatch):
    read = reader(name).read
    assert read({"trace": None}) is None                # no traced run
    assert read(RECORD) is None                         # no trace file
    traced_root({"/host:CPU": LOOP["/host:CPU"]})       # the CPU's trace
    assert read(RECORD) is None
    serving = copy.deepcopy(LOOP)                       # no ds:train.micro
    serving["/host:CPU"]["python3"] = [
        e for e in serving["/host:CPU"]["python3"]
        if not e[0].startswith("ds:train")]
    traced_root(serving)
    assert read(RECORD) is None
    traced_root(LOOP)
    assert read(RECORD) is not None
    monkeypatch.delattr(names, "SCOPE_ATTN_PROJ")       # a parent's program
    assert read(RECORD) is None
    monkeypatch.setattr(program_trace, "program_names", lambda: None)
    assert read(RECORD) is None


@pytest.mark.parametrize("name, source", zip(METRICS, (
    "device_trace", "device_trace", "program_span", "device_trace",
    "device_trace")))
def test_the_manifests_entry(name, source):
    manifest = loader.load_manifest(pb.ROOT)
    entry = loader.find(manifest["per_layer"], name, "metric")
    assert entry == {
        "name": name, "unit": "ms", "better": "lower", "source": source,
        "layer": "engine loop", "moves": "train_tokens_per_s_per_chip",
        "workloads": TRAINING_CELLS}
    for cell in TRAINING_CELLS:
        assert entry in loader.metrics_of_cell(manifest, "per_layer", cell)
