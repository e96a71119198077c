"""The reader of ``serve_expanded_row_share`` (ISSUE 51) on written traces:
over the traced ``ds:serve.step`` spans of kind ``ragged``, the live rows that
took the expanded form of multi-head latent attention (``expanded_rows``) over
the rows that took either; a program that counts none (the parent commit's: 0
on every step), a cache that is not a latent one (no such counts: its live
rows, none expanded), bursts beside the ragged steps, an untraced run and a
run with no trace file."""

import os

import pytest

import pb_helpers as pb
from perfbench import loader, program_trace, serve_trace
from test_perfbench_program_trace import RAGGED, US, _write, op, span

METRIC = "serve_expanded_row_share"
CELL = "longcat_flash_serve_agent"
RECORD = {"trace": {"busy_s": 1.0}}
STEP = "jit(ds_ragged_step_longcat_flash)/ds.attn/"
OPS = [
    op("%ds_paged_mla_chunk.3 = bf16[2048,8192]{1,0} custom-call()", 0, 300,
       RAGGED, STEP + "pallas_call"),
    op("%ds_paged_latent.3 = bf16[128,1024,512]{2,1,0} custom-call()", 300,
       400, RAGGED, STEP + "pallas_call")]


def _trace(steps):
    return {
        "/device:TPU:0": {
            "XLA Modules": [(f"jit_ds_ragged_step_longcat_flash({RAGGED})",
                             0, 1000 * US, {}, {})],
            "XLA Ops": OPS},
        "/host:CPU": {"python3": [span("pb:traced", 0, 900)] + steps}}


def ragged(step, at, **counts):
    return span("ds:serve.step", at, at + 100, step=step, kind="ragged",
                **counts)


@pytest.fixture
def traced(tmp_path, monkeypatch):
    """A checkout whose newest trace is the written one."""
    monkeypatch.setattr(program_trace, "ROOT", str(tmp_path))
    monkeypatch.setattr(program_trace, "_CACHE", {})
    monkeypatch.setattr(serve_trace, "_CACHE", {})
    for name in ("BENCHMARK.json", "perfbench/configs"):
        os.makedirs(os.path.dirname(tmp_path / name), exist_ok=True)
        os.symlink(os.path.join(pb.ROOT, name), tmp_path / name)
    return lambda trace: _write(tmp_path, trace, cell=CELL)


@pytest.fixture
def read():
    return loader.load_reader(pb.ROOT, METRIC).read


@pytest.mark.parametrize("steps, want", [
    # two chunk steps with their decode rows and a step of decode rows alone
    ([ragged(1, 0, live_tokens=2000, absorbed_rows=40, expanded_rows=1960),
      ragged(2, 100, live_tokens=2048, absorbed_rows=48, expanded_rows=2000),
      ragged(3, 200, live_tokens=32, absorbed_rows=32, expanded_rows=0)],
     100.0 * 3960 / 4080),
    # a burst beside them is not read: one row a sequence, never expanded
    ([ragged(1, 0, live_tokens=1024, absorbed_rows=24, expanded_rows=1000),
      span("ds:serve.step", 100, 300, step=2, kind="burst", live_tokens=512,
           absorbed_rows=512, expanded_rows=0)],
     100.0 * 1000 / 1024),
    # the parent commit: the counts are there and read 0
    ([ragged(1, 0, live_tokens=1000, absorbed_rows=1000, expanded_rows=0)],
     0.0),
    # a cache that is not a latent one: live rows, none of them expanded
    ([ragged(1, 0, live_tokens=700), ragged(2, 100, live_tokens=68)], 0.0),
], ids=["chunks_and_decode_rows", "a_burst_is_not_read", "the_parent",
        "no_latent_cache"])
def test_reads_the_expanded_rows_over_the_rows_of_either_form(
        read, traced, steps, want):
    assert read(RECORD) is None                      # no trace file
    traced(_trace(steps))
    assert read({"trace": None}) is None             # an untraced run
    assert read(RECORD) == pytest.approx(want)


@pytest.mark.parametrize("steps", [
    [span("ds:serve.step", 0, 300, step=1, kind="burst", live_tokens=512,
          absorbed_rows=512, expanded_rows=0)],
    [ragged(1, 0, live_tokens=0, absorbed_rows=0, expanded_rows=0)],
], ids=["bursts_alone", "no_live_row"])
def test_gives_nothing_without_a_ragged_steps_row(read, traced, steps):
    traced(_trace(steps))
    assert read(RECORD) is None


def test_the_share_of_the_counts():
    share = loader.load_reader(pb.ROOT, METRIC).share
    assert share([{"absorbed_rows": 25, "expanded_rows": 75}]) == 75.0
    assert share([{"live_tokens": 10}]) == 0.0
    assert share([]) is None


def test_the_manifest_lists_it_for_the_latent_cells():
    """ISSUE 51 names the two cells with a latent cache.  Each has its set
    of metrics pinned to another cell's (``test_perfbench_pangu_ultra_moe.
    py``: openPangu's less Command A+'s; ``test_perfbench_longcat_flash.py``:
    LongCat's less openPangu's), files this PR may not edit, so the entry
    also lists Command A+'s cell, where no row can take the form and it
    reads 0."""
    manifest = pb.read_manifest(pb.ROOT)
    entry = loader.find(manifest["per_layer"], METRIC, "metric")
    assert entry == {
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "serve_tokens_per_s", "workloads": [
            "command_a_plus_serve_rag", "pangu_ultra_moe_serve_reason", CELL]}
