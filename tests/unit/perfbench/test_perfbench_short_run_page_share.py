"""The reader of ``serve_short_run_page_share`` (ISSUE 30) on written traces:
over the traced ``ds:serve.step`` spans of both kinds, the page loads whose
item computed one slab of rows (``short_pages``) over all the kernel's loads
(``grid_pages``); a program whose steps lack the count (the parent commit's),
an untraced run and a run with no trace file give nothing and raise
nothing."""

import copy
import os

import pytest

import pb_helpers as pb
from perfbench import loader, program_trace, serve_trace
from test_perfbench_program_trace import RAGGED, US, _write, op, span

METRIC = "serve_short_run_page_share"
RECORD = {"trace": {"busy_s": 1.0}}
OPS = [op("%fusion.3 = bf16[768,14336]{1,0} fusion(bf16[8]{0} %p), "
          "kind=kOutput", 60, 500, RAGGED,
          "jit(ds_ragged_step_llama)/ds.mlp/dot_general")]


def _trace(steps):
    return {
        "/device:TPU:0": {
            "XLA Modules": [(f"jit_ds_ragged_step_llama({RAGGED})", 0,
                             1000 * US, {}, {})],
            "XLA Ops": OPS},
        "/host:CPU": {"python3": [span("pb:traced", 0, 900)] + steps}}


WITH_COUNTS = [
    span("ds:serve.step", 0, 400, step=1, kind="ragged", grid_pages=500,
         live_pages=500, short_pages=390),
    span("ds:serve.step", 400, 800, step=2, kind="burst", grid_pages=6000,
         live_pages=6000, short_pages=6000),
    # starts after the traced stretch: not counted
    span("ds:serve.step", 950, 1000, step=3, kind="ragged", grid_pages=900,
         live_pages=900, short_pages=0)]


@pytest.fixture
def reader(tmp_path, monkeypatch):
    monkeypatch.setattr(program_trace, "ROOT", str(tmp_path))
    monkeypatch.setattr(serve_trace, "_CACHE", {})
    return loader.load_reader(pb.ROOT, METRIC)


def test_reads_the_short_loads_over_all_loads(reader, tmp_path):
    assert reader.read(RECORD) is None               # no trace file
    _write(tmp_path, _trace(WITH_COUNTS))
    assert reader.read({"trace": None}) is None      # an untraced run
    assert reader.read(RECORD) == pytest.approx(100.0 * 6390 / 6500)


@pytest.mark.parametrize("strip", [("short_pages", ),
                                   ("short_pages", "grid_pages")],
                         ids=["the_parent", "no_page_counts"])
def test_gives_nothing_without_the_count(reader, tmp_path, strip):
    bare = copy.deepcopy(WITH_COUNTS)
    bare = [e[:3] + ({k: v for k, v in e[3].items() if k not in strip}, )
            + e[4:] for e in bare]
    _write(tmp_path, _trace(bare))
    assert reader.read(RECORD) is None


def test_a_step_without_the_count_is_left_out_of_both_sums(reader, tmp_path):
    steps = copy.deepcopy(WITH_COUNTS)
    steps[1] = steps[1][:3] + ({k: v for k, v in steps[1][3].items()
                                if k != "short_pages"}, ) + steps[1][4:]
    newer = _write(tmp_path, _trace(steps))
    os.utime(newer, (2e9, 2e9))
    assert reader.read(RECORD) == pytest.approx(100.0 * 390 / 500)


def test_the_manifest_lists_it_for_both_serving_cells():
    entry = loader.find(pb.read_manifest(pb.ROOT)["per_layer"], METRIC,
                        "metric")
    assert entry == {
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "serve_tokens_per_s",
        "workloads": ["mistral7b_serve_chat", "evabyte_serve_longctx"]}
