"""The reader of ``serve_kv_cache_ms_per_step`` (ISSUE 28) on written traces:
the scope ``ds.kv_cache`` is found by membership in an op's path, inside
``ds.attn`` (the K/V scatter of a program whose cache is a buffer a layer) and
outside it (the copies of a layer in a program whose cache is one array); a
program without the scope, an untraced run and a program without the names
give nothing and raise nothing."""

import copy
import os

import pytest

import pb_helpers as pb
from perfbench import loader, program_trace, serve_trace
from test_perfbench_program_trace import RAGGED, US, _write, op, span

METRIC = "serve_kv_cache_ms_per_step"
STEP = "jit(ds_ragged_step_evabyte)/"
SCATTER = op("%fusion.4 = bf16[180,128,32,128]{3,2,1,0} fusion(bf16[8]{0} "
             "%p), kind=kInput", 500, 510, RAGGED,
             STEP + "ds.attn/ds.kv_cache/scatter")
COPY = op("%fusion.5 = bf16[16,2,180,128,32,128]{5,4,3,2,1,0} fusion("
          "bf16[8]{0} %p), kind=kLoop", 510, 530, RAGGED,
          STEP + "ds.kv_cache/dynamic_update_slice")
# straddles the end of the traced stretch: 20 of its 40 us count
LATE = op("%fusion.4 = bf16[180,128,32,128]{3,2,1,0} fusion(bf16[8]{0} %p), "
          "kind=kInput", 880, 920, RAGGED,
          STEP + "ds.attn/ds.kv_cache/scatter")
ELSEWHERE = [
    op("%fusion.1 = f32[65,16,32,128]{3,2,1,0} fusion(bf16[8]{0} %p), "
       "kind=kLoop", 0, 30, RAGGED, STEP + "ds.attn/ds.eva_summary/gather"),
    op("%fusion.3 = bf16[768,11008]{1,0} fusion(bf16[8]{0} %p), "
       "kind=kOutput", 60, 500, RAGGED, STEP + "ds.mlp/dot_general")]


def _trace(ops):
    return {
        "/device:TPU:0": {
            "XLA Modules": [(f"jit_ds_ragged_step_evabyte({RAGGED})", 0,
                             1000 * US, {}, {})],
            "XLA Ops": ELSEWHERE + list(ops)},
        "/host:CPU": {"python3": [
            span("pb:traced", 0, 900),
            span("ds:serve.step", 0, 400, step=1, kind="ragged"),
            span("ds:serve.step", 400, 800, step=2, kind="burst"),
            span("ds:serve.step", 950, 1000, step=3, kind="ragged")]}}


@pytest.fixture
def reader(tmp_path, monkeypatch):
    monkeypatch.setattr(program_trace, "ROOT", str(tmp_path))
    monkeypatch.setattr(serve_trace, "_CACHE", {})
    return loader.load_reader(pb.ROOT, METRIC)


RECORD = {"trace": {"busy_s": 1.0}}


@pytest.mark.parametrize("ops,expected", [
    ((SCATTER, ), 0.01 / 2),
    ((COPY, ), 0.02 / 2),
    ((SCATTER, COPY, LATE), (0.01 + 0.02 + 0.02) / 2),
], ids=["inside_attn", "outside_attn", "both_and_the_stretch_end"])
def test_reads_the_scope_by_membership(reader, tmp_path, ops, expected):
    assert reader.read(RECORD) is None               # no trace file
    _write(tmp_path, _trace(ops))
    assert reader.read({"trace": None}) is None      # an untraced run
    assert reader.read(RECORD) == pytest.approx(expected)


def test_gives_nothing_where_no_op_has_the_scope(reader, tmp_path):
    _write(tmp_path, _trace(()))
    assert reader.read(RECORD) is None
    # nor where no step was traced
    stepless = copy.deepcopy(_trace((SCATTER, )))
    stepless["/host:CPU"]["python3"] = stepless["/host:CPU"]["python3"][:1]
    newer = _write(tmp_path, stepless, cell="newer")
    os.utime(newer, (2e9, 2e9))
    serve_trace._CACHE.clear()
    assert reader.read(RECORD) is None


def test_gives_nothing_with_a_program_without_the_names(reader, tmp_path,
                                                        monkeypatch):
    import sys
    import deepspeed_tpu.telemetry
    _write(tmp_path, _trace((SCATTER, COPY)))
    monkeypatch.setitem(sys.modules, "deepspeed_tpu.telemetry.names", None)
    monkeypatch.delattr(deepspeed_tpu.telemetry, "names")
    assert reader.read(RECORD) is None
