"""Collective micro-benchmark — the ``ds_bench`` CLI.

Reference: ``bin/ds_bench`` forwards to the DeepSpeedExamples communication
suite (all_reduce/all_gather/all_to_all/pt2pt sweeps printing algbw/busbw
per size, nccl-tests conventions).  Here the sweep runs in-process over the
mesh's collectives (psum / all_gather / all_to_all / ppermute on a chosen
axis), with the same bandwidth accounting as ``utils/comms_logging.get_bw``
— plus the collectives-engine variants (hierarchical all-reduce, quantized
all-gather/reduce-scatter, 2-hop hierarchical-quantized reduce-scatter)
so the comm trajectory of ``comm_optimizations`` configs is measurable.

    ds_bench                       # sweep all ops over the dp axis
    ds_bench --op quant_all_gather --axis dp --maxsize 28
    ds_bench --mesh dp=4,tp=2      # explicit mesh factorization
    ds_bench --json out.json       # machine-readable rows

Prints one table row per (op, size): logical bytes, wire bytes (what the
bottleneck link actually carries — post-quantization payload + scales),
latency, algbw, busbw.  Bandwidths are computed from WIRE bytes.
"""

import argparse
import json
import time

import numpy as np


OPS = ("all_reduce", "all_gather", "reduce_scatter", "all_to_all", "pt2pt")
# collectives-engine variants (comm/collectives/): hierarchy + quantization
ENGINE_OPS = ("hier_all_reduce", "quant_all_gather", "quant_reduce_scatter",
              "hier_quant_reduce_scatter")
ALL_OPS = OPS + ENGINE_OPS

WIRE_FORMAT = "int8"
GROUP_SIZE = 2048


def _timed_stats(f, args, iters, warmup, repeat=1):
    """Per-call latency statistics of ``f(*args)``: after ``warmup`` calls,
    time ``repeat`` independent blocks of ``iters`` calls each and return
    ``(median, iqr)`` over the per-block averages.  Single-shot timings on
    small messages are noise-dominated (scheduler jitter, dispatch
    variance) — the median resists outliers and the IQR reports how noisy
    the probe actually was, so a downstream cost model can weigh it.
    ``block_until_ready`` fences the async dispatch; safe with warmup=0."""
    import jax
    out = None
    for _ in range(warmup):
        out = f(*args)
    if out is not None:
        jax.block_until_ready(out)
    samples = []
    for _ in range(max(1, repeat)):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = f(*args)
        jax.block_until_ready(out)
        samples.append((time.perf_counter() - t0) / iters)
    med = float(np.median(samples))
    iqr = float(np.percentile(samples, 75) - np.percentile(samples, 25)) \
        if len(samples) > 1 else 0.0
    return med, iqr


class UnsplittableAxis(ValueError):
    """The axis has no non-trivial (outer, inner) factorization — hier_*
    ops are skipped for it, every other error still fails the bench."""


def _hier(mesh, axis, intra):
    """(smesh, outer_axis, inner_axis, n_out, n_in) for the hier ops: the
    topology layer's split when it can see one, else an even power-of-two
    split so the hierarchical schedule is still measurable on flat/virtual
    meshes (the virtual CPU mesh has no physical topology)."""
    from ..comm.backend import ProcessGroup
    from ..comm.collectives.topology import factor_group
    g = ProcessGroup(mesh, (axis, ))
    h = factor_group(g, intra_node_size=intra)
    if h is not None and len(h.inner_axes) == 1 and len(h.outer_axes) == 1:
        return (h.mesh, h.outer_axes[0], h.inner_axes[0], h.outer_size,
                h.inner_size)
    n = mesh.shape[axis]
    inner = 1
    while inner * inner < n and n % (inner * 2) == 0:
        inner *= 2
    if inner <= 1 or inner >= n:
        # a 1-sized factor on either side is not a hierarchy — measuring it
        # as one would report bogus hier_* rows (e.g. axis size 2)
        raise UnsplittableAxis(
            f"axis {axis!r} (size {n}) has no non-trivial split for "
            "hierarchical ops — pass --intra or use an axis of size ≥ 4")
    from ..comm.collectives.topology import split_mesh
    return (split_mesh(mesh, axis, inner), axis + "_out", axis + "_in",
            n // inner, inner)


def _bench_one(op, axis, nbytes, mesh, iters, warmup, intra=0, repeat=1,
               wire=WIRE_FORMAT, group_size=GROUP_SIZE):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from ..comm.collectives import quantized as Q

    n = mesh.shape[axis]
    elems = max(n, nbytes // 4 // n * n)  # fp32, divisible by axis size
    x = jnp.arange(elems, dtype=jnp.float32)
    size_bytes = elems * 4
    wire_bytes = size_bytes
    bw_op = op

    def make(fn, m=mesh, in_spec=None, out_spec=None):
        return jax.jit(jax.shard_map(
            fn, mesh=m,
            in_specs=P(axis) if in_spec is None else in_spec,
            out_specs=P(axis) if out_spec is None else out_spec,
            check_vma=False))

    if op == "all_reduce":
        f = make(lambda t: jax.lax.psum(t, axis) / n)
    elif op == "all_gather":
        f = make(lambda t: jax.lax.all_gather(t, axis).reshape(-1)[:t.shape[0]])
    elif op == "reduce_scatter":
        f = make(lambda t: jax.lax.psum_scatter(
            t.reshape(n, -1), axis, scatter_dimension=0,
            tiled=False).reshape(-1))
    elif op == "all_to_all":
        f = make(lambda t: jax.lax.all_to_all(
            t.reshape(n, -1), axis, split_axis=0, concat_axis=0,
            tiled=False).reshape(-1))
    elif op == "pt2pt":
        perm = [(i, (i + 1) % n) for i in range(n)]
        f = make(lambda t: jax.lax.ppermute(t, axis, perm))
        bw_op = "send"
    elif op == "hier_all_reduce":
        from ..comm.collectives.engine import _jit_hier_all_reduce
        from ..comm.reduce_op import ReduceOp
        smesh, out_ax, in_ax, n_out, n_in = _hier(mesh, axis, intra)
        # pad the per-rank block to n_in divisibility via elems choice: elems
        # is divisible by n; require further by n*n_in
        elems = max(n * n_in, elems // (n * n_in) * (n * n_in))
        x = jnp.arange(elems, dtype=jnp.float32)
        size_bytes = elems * 4
        wire_bytes = size_bytes // n_in  # fp payload crossing DCN
        # measure the exact kernel the engine ships, not a re-derivation
        f = _jit_hier_all_reduce(smesh, (in_ax, ), (out_ax, ),
                                 ReduceOp.AVG, n)
        bw_op = "all_reduce"
    elif op == "quant_all_gather":
        f = make(lambda t: Q.quantized_all_gather(
            t, (axis, ), 0, wire, group_size).reshape(-1)[:t.shape[0]],
            out_spec=P())
        wire_bytes = Q.quantized_wire_bytes(elems, wire, group_size)
        bw_op = "all_gather"
    elif op == "quant_reduce_scatter":
        f = make(lambda t: Q.all_to_all_quant_reduce(
            t, (axis, ), 0, n, wire_format=wire,
            group_size=group_size), in_spec=P(), out_spec=P(axis))
        wire_bytes = Q.quantized_wire_bytes(elems, wire, group_size)
        bw_op = "reduce_scatter"
    elif op == "hier_quant_reduce_scatter":
        smesh, out_ax, in_ax, n_out, n_in = _hier(mesh, axis, intra)
        f = make(lambda t: Q.hierarchical_quant_reduce_scatter(
            t, (in_ax, ), (out_ax, ), 0, n_in, n_out,
            wire_format=wire, group_size=group_size),
            m=smesh, in_spec=P(), out_spec=P((in_ax, out_ax)))
        # quantized payload crossing DCN on 1/n_in of the data
        wire_bytes = Q.quantized_wire_bytes(elems // n_in, wire,
                                            group_size)
        bw_op = "reduce_scatter"
    else:
        raise ValueError(op)

    lat, iqr = _timed_stats(f, (x, ), iters, warmup, repeat=repeat)

    from ..utils.comms_logging import calc_bw_log
    algbw, busbw = calc_bw_log(bw_op, wire_bytes, lat, n)
    return size_bytes, wire_bytes, lat, algbw, busbw, iqr


# ------------------------------------------------------------- row schema
def bench_row(**fields):
    """THE uniform ``ds_bench --json`` row: every producer (the op sweep,
    :func:`probe_op`, the autotuner's trial archive) builds rows through
    this one constructor, so a field added to the schema lands everywhere
    at once instead of drifting across hand-built dict literals.  Unset
    schema fields are explicit ``None``; extra producer-specific keys
    (trial names) pass through."""
    row = {"op": None, "bytes": None, "wire_bytes": None,
           "latency_us": None, "iqr_us": None, "repeat": None,
           "wire_dtype": None, "algbw_gbps": None, "busbw_gbps": None,
           "bucket_mb": None, "direction": None,
           "overlap_efficiency": None, "exposed_comm_frac": None,
           "mfu": None, "peak_hbm_bytes": None}
    row.update(fields)
    return row


# ------------------------------------------------------------- probe API
def probe_op(op, nbytes, axis="dp", mesh=None, iters=5, warmup=2, repeat=3,
             intra=0, wire=WIRE_FORMAT, group_size=GROUP_SIZE):
    """One in-process micro-probe — the reusable ``ds_bench`` candidate
    machinery the autotuner's topology-probe stage calls directly (no
    subprocess orchestration).  Runs ``op`` at ``nbytes`` with warmup +
    ``repeat`` timed blocks and returns ONE row in the uniform
    ``ds_bench --json`` schema (median ``latency_us`` + ``iqr_us``).

    ``wire`` selects the wire format of the ``quant_*`` /
    ``hier_quant_*`` ops (the per-size probes sweep it); flat ops ignore
    it and report ``wire_dtype: "fp32"``.  Raises
    :class:`UnsplittableAxis` for ``hier_*`` ops on axes with no
    non-trivial split — the caller skips that candidate."""
    from ..utils import groups
    if mesh is None:
        mesh = groups.get_mesh_state().mesh
    size, wire_bytes, lat, algbw, busbw, iqr = _bench_one(
        op, axis, nbytes, mesh, iters, warmup, intra=intra, repeat=repeat,
        wire=wire, group_size=group_size)
    return bench_row(
        op=op, bytes=int(size), wire_bytes=int(wire_bytes),
        latency_us=lat * 1e6, iqr_us=iqr * 1e6, repeat=int(repeat),
        wire_dtype=(wire if "quant" in op else "fp32"),
        algbw_gbps=algbw, busbw_gbps=busbw)


def run(ops=ALL_OPS, axis="dp", minsize=16, maxsize=26, mesh_spec=None,
        iters=20, warmup=3, print_fn=print, intra=0, json_path=None,
        repeat=3):
    """Sweep collectives over powers-of-two message sizes.  Returns rows of
    (op, bytes, wire_bytes, latency_s, algbw_gbps, busbw_gbps, iqr_s) —
    latency is the MEDIAN over ``repeat`` timed blocks, iqr their
    interquartile range (see ``_timed_stats``); with ``json_path``, also
    writes them as machine-readable JSON."""
    from ..utils import groups
    if mesh_spec:
        kw = {}
        for part in mesh_spec.split(","):
            k, v = part.split("=")
            kw[k] = int(v)
        groups.reset_mesh()
        groups.initialize_mesh(**kw)
    mesh = groups.get_mesh_state().mesh
    if mesh.shape.get(axis, 1) < 2:
        raise SystemExit(
            f"axis {axis!r} has size {mesh.shape.get(axis, 1)} on mesh "
            f"{dict(mesh.shape)} — nothing to benchmark (pass --mesh)")
    rows = []
    print_fn(f"# mesh={dict(mesh.shape)} axis={axis} dtype=fp32 "
             f"wire={WIRE_FORMAT} repeat={repeat}")
    print_fn(f"{'op':<28}{'bytes':>12}{'wire_bytes':>12}{'latency_us':>14}"
             f"{'iqr_us':>10}{'algbw_Gbps':>12}{'busbw_Gbps':>12}")
    for op in ops:
        for p in range(minsize, maxsize + 1, 2):
            try:
                size, wire, lat, algbw, busbw, iqr = _bench_one(
                    op, axis, 1 << p, mesh, iters, warmup, intra=intra,
                    repeat=repeat)
            except UnsplittableAxis as e:
                # hier_* on an unsplittable axis: note and keep sweeping the
                # other ops (any other error still fails the bench loudly)
                print_fn(f"# {op}: skipped ({e})")
                break
            rows.append((op, size, wire, lat, algbw, busbw, iqr))
            print_fn(f"{op:<28}{size:>12}{wire:>12}{lat * 1e6:>14.1f}"
                     f"{iqr * 1e6:>10.1f}{algbw:>12.2f}{busbw:>12.2f}")
    if json_path:
        json_rows = [bench_row(op=op, bytes=int(size),
                               wire_bytes=int(wire), latency_us=lat * 1e6,
                               iqr_us=iqr * 1e6, repeat=repeat,
                               wire_dtype=(WIRE_FORMAT if "quant" in op
                                           else "fp32"),
                               algbw_gbps=algbw, busbw_gbps=busbw)
                     for op, size, wire, lat, algbw, busbw, iqr in rows]
        payload = {
            "mesh": {k: int(v) for k, v in dict(mesh.shape).items()},
            "axis": axis,
            "dtype": "fp32",
            "wire_format": WIRE_FORMAT,
            "quantization_group_size": GROUP_SIZE,
            "rows": json_rows,
        }
        with open(json_path, "w") as fh:
            json.dump(payload, fh, indent=2)
        print_fn(f"# wrote {len(json_rows)} rows to {json_path}")
    return rows


def cli_main(argv=None):
    ap = argparse.ArgumentParser(
        prog="ds_bench", description="collective micro-benchmarks over the "
        "device mesh (reference bin/ds_bench), incl. hierarchical/quantized "
        "engine variants")
    ap.add_argument("--op", choices=ALL_OPS, default=None,
                    help="single op (default: all)")
    ap.add_argument("--axis", default="dp")
    ap.add_argument("--mesh", default=None,
                    help="mesh factorization, e.g. dp=4,tp=2")
    ap.add_argument("--minsize", type=int, default=16,
                    help="log2 of smallest message (default 16 = 64KiB)")
    ap.add_argument("--maxsize", type=int, default=26,
                    help="log2 of largest message (default 26 = 64MiB)")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--repeat", type=int, default=3,
                    help="timed blocks per row; reported latency is their "
                    "MEDIAN and iqr_us their interquartile range (small-"
                    "message single-shot timings are noise-dominated)")
    ap.add_argument("--intra", type=int, default=0,
                    help="intra-node size for hier_* ops (0 = topology "
                    "auto-detect, falling back to an even split)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write machine-readable rows to PATH")
    args = ap.parse_args(argv)
    run(ops=(args.op, ) if args.op else ALL_OPS, axis=args.axis,
        minsize=args.minsize, maxsize=args.maxsize, mesh_spec=args.mesh,
        iters=args.iters, warmup=args.warmup, repeat=args.repeat,
        intra=args.intra, json_path=args.json)


if __name__ == "__main__":
    cli_main()
