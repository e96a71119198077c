"""ds_bench collective sweep (reference bin/ds_bench surface)."""

import re

import numpy as np
import pytest

from deepspeed_tpu.benchmarks.comm_bench import run


def test_sweep_all_ops():
    from deepspeed_tpu.benchmarks.comm_bench import ALL_OPS
    rows = run(axis="dp", minsize=12, maxsize=12, iters=2, warmup=1,
               print_fn=lambda *a: None)
    assert len(rows) == len(ALL_OPS)  # one size, every op incl. engine ops
    for op, size, wire, lat, algbw, busbw, iqr in rows:
        assert size >= 4096 and wire > 0 and lat > 0 and algbw > 0 \
            and busbw > 0
        assert iqr >= 0  # repeat>1 default: IQR measured, non-negative


def test_quantized_ops_report_reduced_wire_bytes():
    """The acceptance bar: quantized all-gather / reduce-scatter move fewer
    wire bytes than their flat fp32 siblings (int8 payload + scales < 4B/el),
    and the hierarchical variants shrink the inter-node payload further."""
    rows = {op: (size, wire)
            for op, size, wire, *_ in run(
                axis="dp", minsize=16, maxsize=16, iters=2, warmup=1,
                print_fn=lambda *a: None)}
    for flat, quant in (("all_gather", "quant_all_gather"),
                        ("reduce_scatter", "quant_reduce_scatter")):
        assert rows[quant][1] < rows[flat][1], (flat, quant, rows)
    assert rows["hier_quant_reduce_scatter"][1] < \
        rows["quant_reduce_scatter"][1]
    assert rows["hier_all_reduce"][1] < rows["all_reduce"][1]
    # flat ops: wire == logical bytes
    assert rows["all_reduce"][0] == rows["all_reduce"][1]


def test_json_output(tmp_path):
    import json
    out = tmp_path / "bench.json"
    run(ops=("all_reduce", "quant_reduce_scatter"), axis="dp", minsize=12,
        maxsize=12, iters=1, warmup=1, repeat=2, print_fn=lambda *a: None,
        json_path=str(out))
    payload = json.loads(out.read_text())
    assert payload["axis"] == "dp" and payload["mesh"]["dp"] == 8
    assert len(payload["rows"]) == 2
    for row in payload["rows"]:
        # uniform schema incl. the repeat/median/IQR stats fields
        assert set(row) >= {"op", "bytes", "wire_bytes", "latency_us",
                            "algbw_gbps", "busbw_gbps", "iqr_us", "repeat",
                            "wire_dtype"}
        assert row["repeat"] == 2 and row["iqr_us"] >= 0
    by_op = {r["op"]: r for r in payload["rows"]}
    assert by_op["all_reduce"]["wire_dtype"] == "fp32"
    assert by_op["quant_reduce_scatter"]["wire_dtype"] == "int8"


def test_probe_op_single_row_schema():
    """The in-process probe API the autotuner's probe stage rides: one
    uniform-schema row per call, wire format selectable per probe."""
    from deepspeed_tpu.benchmarks.comm_bench import probe_op
    flat = probe_op("reduce_scatter", 1 << 12, iters=1, warmup=0, repeat=2)
    q = probe_op("quant_reduce_scatter", 1 << 12, iters=1, warmup=0,
                 repeat=2, wire="fp8", group_size=128)
    for row in (flat, q):
        assert {"op", "bytes", "wire_bytes", "latency_us", "iqr_us",
                "repeat", "wire_dtype", "algbw_gbps", "busbw_gbps",
                "bucket_mb", "direction", "overlap_efficiency",
                "exposed_comm_frac"} <= set(row)
        assert row["latency_us"] > 0 and row["repeat"] == 2
    assert flat["wire_dtype"] == "fp32"
    assert q["wire_dtype"] == "fp8"
    assert q["wire_bytes"] < flat["wire_bytes"]  # fp8 payload + scales


def test_hier_ops_skipped_on_unsplittable_axis():
    """A size-2 axis has no non-trivial (outer, inner) split — the hier rows
    must be skipped, not reported as fake hierarchy measurements."""
    rows = run(ops=("hier_all_reduce", ), axis="tp", mesh_spec="dp=4,tp=2",
               minsize=12, maxsize=12, iters=1, warmup=1,
               print_fn=lambda *a: None)
    assert rows == []
    from deepspeed_tpu.utils import groups
    groups.reset_mesh()


def test_explicit_mesh_axis():
    rows = run(ops=("all_to_all", ), axis="tp", mesh_spec="dp=2,tp=4",
               minsize=12, maxsize=12, iters=2, warmup=1,
               print_fn=lambda *a: None)
    assert rows and rows[0][0] == "all_to_all"
    from deepspeed_tpu.utils import groups
    groups.reset_mesh()


def test_degenerate_axis_rejected():
    from deepspeed_tpu.utils import groups
    groups.reset_mesh()
    with pytest.raises(SystemExit, match="nothing to benchmark"):
        run(axis="pp", minsize=12, maxsize=12, print_fn=lambda *a: None)
    groups.reset_mesh()


def test_facade_parity_ops():
    """The reference comm surface beyond the core collectives: reduce/
    gather/coalesced variants compute, SPMD-impossible ops raise with
    guidance, probes answer."""
    import jax.numpy as jnp
    import deepspeed_tpu.comm as dist
    dist.init_distributed()
    x = jnp.arange(8.0)
    # facade convention (test_dist): input = concatenation of per-rank
    # locals; reduce sums the 8 one-element shards -> 28 everywhere
    r = dist.reduce(x, dst=0)
    np.testing.assert_allclose(np.asarray(r), 28.0)
    g = dist.gather(x)
    assert g.shape[0] >= x.shape[0]
    outs = dist.all_reduce_coalesced([x, 2 * x])
    assert len(outs) == 2
    assert dist.allgather_fn(None, x) is not None
    assert dist.has_all_gather_into_tensor() and dist.is_available()
    assert isinstance(dist.get_all_ranks_from_group(), list)
    dist.monitored_barrier(timeout=60)
    with pytest.raises(NotImplementedError, match="ppermute"):
        dist.send(x, dst=1)
    with pytest.raises(NotImplementedError, match="shard_batch"):
        dist.scatter(x)


def test_group_rank_introspection():
    """Subgroup member lists respect the axis factorization."""
    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.utils import groups
    groups.reset_mesh()
    dist.destroy_process_group()
    groups.initialize_mesh(dp=4, tp=2)
    dist.init_distributed()
    g_tp = dist.new_group(("tp", ))
    ranks = dist.get_all_ranks_from_group(g_tp)
    assert len(ranks) == 2 == g_tp.size()
    assert dist.get_global_rank(g_tp, 1) == ranks[1]
    with pytest.raises(IndexError):
        dist.get_global_rank(g_tp, 5)
    assert len(dist.get_all_ranks_from_group()) == 8
    groups.reset_mesh()
    dist.destroy_process_group()


def test_cli_is_the_op_sweep_and_nothing_else(tmp_path, capsys):
    """``ds_bench`` takes the ten flags of the collective sweep (what a
    cell decides has no flag here), and its ``--json`` rows are
    ``bench_row``s."""
    import json
    from deepspeed_tpu.benchmarks.comm_bench import cli_main, bench_row
    with pytest.raises(SystemExit):
        cli_main(["--help"])
    flags = set(re.findall(r"--[a-z][a-z-]*", capsys.readouterr().out))
    assert flags == {"--help", "--op", "--axis", "--mesh", "--minsize",
                     "--maxsize", "--iters", "--warmup", "--repeat",
                     "--intra", "--json"}
    out = tmp_path / "out.json"
    cli_main(["--op", "all_reduce", "--minsize", "12", "--maxsize", "12",
              "--iters", "1", "--warmup", "1", "--json", str(out)])
    capsys.readouterr()
    payload = json.loads(out.read_text())
    assert set(payload) == {"mesh", "axis", "dtype", "wire_format",
                            "quantization_group_size", "rows"}
    (row, ) = payload["rows"]
    assert list(row) == list(bench_row()) and row["op"] == "all_reduce"
