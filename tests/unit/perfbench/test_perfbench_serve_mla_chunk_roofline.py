"""The reader of ``serve_mla_chunk_kernel_roofline_share`` (ISSUE 53) on a
written trace: over the WHOLE ragged steps of the joined table
(``perfbench/step_trace.py``) that hold an expanded row, what the expanded
form of multi-head latent attention must move and compute, from the launched
step's own counts and the traced cell's widths, over the chip's time inside
``ds_paged_mla_chunk`` within those steps' executions.  The shares are worked
out here by hand, at both cells' head counts and calls; a step of decode rows
alone, a step at the stretch's edge and the absorbed kernel's time are not
read; a program that counts no expanded row gives nothing, never 0.  Times in
the source are microseconds."""

import os

import pytest

import pb_helpers as pb
from perfbench import loader, program_trace, serve_trace, step_trace
from test_perfbench_program_trace import _write, op, span
from test_perfbench_step_trace import life, module, turn

METRIC = "serve_mla_chunk_kernel_roofline_share"
#: cell -> (calls = depth x cache entries a layer, heads)
CELLS = {"pangu_ultra_moe_serve_reason": (7, 128),
         "longcat_flash_serve_agent": (8, 64)}
RECORD = {"trace": {"busy_s": 1.0},
          "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
RAGGED = 33
PROGRAM = "ds_ragged_step_longcat_flash"
SCOPE = f"jit({PROGRAM})/ds.attn/pallas_call"
CHUNK = "%ds_paged_mla_chunk.{} = bf16[2048,8192]{{1,0}} custom-call()"
LATENT = "%ds_paged_latent.{} = bf16[128,1024,512]{{2,1,0}} custom-call()"
MLP = "%fusion.4 = bf16[2048,12288]{1,0} fusion()"
#: launch -> its execution on the chip; the stretch is [100, 150 000]: steps
#: 8-10 are whole, step 11 straddles its end
EXECS = {8: (2000, 60000), 9: (60010, 100000), 10: (100010, 110000),
         11: (110010, 170000)}
#: launch -> (us inside ds_paged_mla_chunk, us inside ds_paged_latent)
KERNELS = {8: (40000, 3000), 9: (20000, 0), 10: (0, 6000), 11: (30000, 1000)}


def by_hand(rows, keys, pages, calls, heads):
    """Seconds: the published widths (rank 512, rope 64, nope 128, value
    128), blocks of 128 tokens, bfloat16, a v5e's peaks."""
    tokens = calls * (pages // heads) * 128
    flops = 2 * heads * (keys * 320 + tokens * 512 * 256)
    moved = 2 * (tokens * 576 + calls * rows * heads * 320)
    return max(flops / 197e12, moved / 819e9)


def _trace(counts, kernels=KERNELS):
    """``counts``: launch -> the counts of the turn that launched it."""
    ops, host = [], [span("pb:traced", 100, 150000)]
    for n, (start, end) in EXECS.items():
        at = start
        for instr, us in zip((CHUNK, LATENT), kernels[n]):
            if us:
                ops.append(op(instr.format(n), at, at + us, RAGGED, SCOPE))
                at += us
        ops.append(op(MLP, at, end, RAGGED,
                      f"jit({PROGRAM})/ds.mlp/dot_general"))
        before = EXECS.get(n - 1, (100, 1990))
        host.append(turn(before[0] + 10, before[1] + 10, n, n - 1,
                         kind="ragged", block_size=128, token_budget=2048,
                         burst_k=0, **counts[n]))
        host += life(n, "ragged", (before[0] + 20, before[0] + 30),
                     (start + 40, end + 4) if n < 11 else None)
    return {
        "/device:TPU:0": {
            "XLA Modules": [module(PROGRAM, RAGGED, *EXECS[n], 100 + n)
                            for n in EXECS],
            "XLA Ops": ops},
        "/host:CPU": {"python3": host}}


def counts(rows=0, keys=0, pages=0, absorbed=0):
    return {"live_tokens": rows + absorbed, "absorbed_rows": absorbed,
            "expanded_rows": rows, "expanded_keys": keys,
            "expanded_pages": pages, "latent_keys": 1000 * absorbed,
            "grid_pages": 40 * absorbed}


@pytest.fixture
def traced(tmp_path, monkeypatch):
    """A checkout whose newest trace is the written one, of ``cell``."""
    monkeypatch.setattr(program_trace, "ROOT", str(tmp_path))
    for module_ in (program_trace, serve_trace, step_trace):
        monkeypatch.setattr(module_, "_CACHE", {})
    for name in ("BENCHMARK.json", "perfbench/configs"):
        os.makedirs(os.path.dirname(tmp_path / name), exist_ok=True)
        os.symlink(os.path.join(pb.ROOT, name), tmp_path / name)
    return lambda trace, cell: _write(tmp_path, trace, cell=cell)


@pytest.fixture
def read():
    return loader.load_reader(pb.ROOT, METRIC).read


def _steps(heads):
    """Three whole steps and the edge's: a run of 1960 rows from position
    4096 (6 blocks of 1024 keys), two runs of 2000 rows over 2 + 3 blocks,
    a step of decode rows; ``expanded_keys`` over 8 entries' calls (the
    engine's sum; Pangu's 7 would be its own: the reader takes the count)."""
    pairs = 1960 * 4096 + 1960 * 1961 // 2
    return {8: counts(1960, 8 * pairs, 6 * 8 * heads, absorbed=40),
            9: counts(2000, 8 * 5_000_000, 5 * 8 * heads),
            10: counts(absorbed=48),
            11: counts(2048, 8 * 9_000_000, 9 * 8 * heads)}


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("forms", ["expanded_rows_only", "both_forms"])
def test_reads_the_forms_floor_over_the_chunk_kernels_time(
        read, traced, cell, forms):
    calls, heads = CELLS[cell]
    steps, kernels = _steps(heads), KERNELS
    want = [(1960, 8 * (1960 * 4096 + 1960 * 1961 // 2), 6 * 8 * heads),
            (2000, 8 * 5_000_000, 5 * 8 * heads)]
    ms = 40.0 + 20.0
    if forms == "expanded_rows_only":
        # no absorbed row and no ds_paged_latent op in any step; step 10 a
        # chunk of 2048 rows over 8 blocks, 5 ms in the kernel
        steps[10] = counts(2048, 8 * 7_000_000, 8 * 8 * heads)
        steps = {n: dict(c, absorbed_rows=0, live_tokens=c["expanded_rows"])
                 for n, c in steps.items()}
        kernels = {8: (40000, 0), 9: (20000, 0), 10: (5000, 0),
                   11: (30000, 0)}
        want.append((2048, 8 * 7_000_000, 8 * 8 * heads))
        ms += 5.0
    assert read(RECORD) is None                      # no trace file
    traced(_trace(steps, kernels), cell)
    assert read({"trace": None}) is None             # an untraced run
    floor = sum(by_hand(*w, calls, heads) for w in want)
    assert read(RECORD) == pytest.approx(100.0 * floor / (ms / 1e3))
    # the edge step's 30 ms of kernel and its counts are not read, nor the
    # absorbed kernel's time
    t = step_trace.traced(RECORD)
    assert [r["launch"] for r in t["rows"]] == [8, 9, 10]
    assert [r["launch"] for r in t["edges"]] == [11]


def test_the_share_of_one_step_by_hand(read, traced):
    """LongCat's widths, one call-set of 8: 1960 rows against 6056 tokens
    of context in 40 ms of the kernel."""
    steps = _steps(64)
    steps[9] = counts(absorbed=32)
    traced(_trace(steps, {**KERNELS, 9: (0, 20000)}),
           "longcat_flash_serve_agent")
    pairs = 8 * (1960 * 4096 + 1960 * 1961 // 2)          # 79 599 520
    tokens = 8 * 48 * 128                                  # 49 152
    flops = 2 * 64 * (pairs * (128 + 64 + 128) + tokens * 512 * (128 + 128))
    moved = 2 * (tokens * (512 + 64) + 8 * 1960 * 64 * (128 + 64 + 128))
    assert flops == 4_085_030_060_032 and moved == 698_875_904
    assert flops / 197e12 > moved / 819e9                  # its operations
    assert read(RECORD) == pytest.approx(100 * (flops / 197e12) / 0.040)
    assert read(RECORD) == pytest.approx(51.84, abs=0.01)


@pytest.mark.parametrize("case", ["decode_rows_alone", "a_parent_of_pr51",
                                  "no_latent_cache"])
def test_gives_nothing_without_an_expanded_row(read, traced, case):
    steps = {n: counts(absorbed=32 + n) for n in EXECS}
    if case == "a_parent_of_pr51":          # expanded_rows is there, reads 0
        steps = {n: {k: v for k, v in c.items()
                     if k not in ("expanded_keys", "expanded_pages")}
                 for n, c in steps.items()}
    if case == "no_latent_cache":
        steps = {n: {"live_tokens": 700} for n in EXECS}
    traced(_trace(steps), "longcat_flash_serve_agent")
    assert step_trace.traced(RECORD)["rows"]             # the join is there
    assert read(RECORD) is None


def test_the_two_functions_count_what_the_form_needs():
    reader = loader.load_reader(pb.ROOT, METRIC)
    # 10 latent rows of 576 read once; 3 rows x 2 heads: 192 in, 128 out
    assert reader.must_move_bytes(10, 3, 2, 512, 64, 128, 128) == \
        (10 * 576 + 3 * 2 * 320) * 2
    # 7 pairs: a score over 192 and a value over 128; 10 latent rows made
    # into keys and values of 128 + 128 through rank 512; 2 heads
    assert reader.must_compute_flops(7, 10, 2, 512, 64, 128, 128) == \
        2 * 2 * (7 * 320 + 10 * 512 * 256)
    # bytes bind where rows are many and pairs few
    peaks = RECORD["peaks"]
    widths = (64, 512, 64, 128, 128)
    step = {"expanded_rows": 2000, "expanded_keys": 2000,
            "expanded_pages": 64, "block_size": 128}
    assert reader.floor_s(step, 1, widths, peaks) == pytest.approx(
        2 * (128 * 576 + 2000 * 64 * 320) / 819e9)


def test_the_manifest_lists_it_for_the_two_latent_cells():
    manifest = pb.read_manifest(pb.ROOT)
    assert loader.find(manifest["per_layer"], METRIC, "metric") == {
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "serve_tokens_per_s", "workloads": [
            "pangu_ultra_moe_serve_reason", "longcat_flash_serve_agent"]}
    # the two older latent readers stay: the ABSORBED kernel's alone
    assert loader.load_reader(
        pb.ROOT, "serve_latent_kernel_roofline_share").KERNEL == \
        "ds_paged_latent"
