#!/usr/bin/env python
"""Time the grouped products of a held-expert layer under a GRADIENT, on the
attached chip, at a training step's shapes: XLA's ``lax.ragged_dot`` and its
transposes, the Pallas ``ds_grouped_matmul`` under a ``custom_vjp`` (the
rows' gradient = a grouped product with the transposed stack, the weights'
gradient = a TRANSPOSED grouped product over the same row groups, the kernel
``ds_grouped_matmul_t`` of this file), and the batched dense products of
``moe/held_experts.padded_swiglu`` over per-expert padded blocks of
``--block-rows`` rows: ``padded_blocks`` is the products alone on blocks laid
beforehand, ``padded_from_sorted`` also gathers the blocks from the sorted
buffer and gathers the result back into it (more glue than the layer has:
there the blocks are gathered from the tokens, as the sorted buffer is).

    python tools/moe_gmm_train_bench.py [--paths ragged_dot,padded] [--out FILE]
    python tools/moe_gmm_train_bench.py --layer [--layer-file a.py,b.py]

The gated feed-forward of ``E`` experts of ``D x I`` over a buffer of ``C``
rows sorted by expert of which the first ``N`` are live (``E x block-rows``
live rows: every expert as many), forward alone and forward with the gradient
of every input.  One JSON line a case and path: ``{"path", "rows", "live",
"fwd_ms", "fwd_bwd_ms", "tflops", ...}`` (``tflops``: nine products of ``D x
I`` a live row over the forward-and-backward time).  A padded path runs where
the fullest expert fits a block and the buffer is no longer than the blocks.
docs/kernels.md has the v5e readings and which path ``moe/held_experts.py``
kept; the kernel below lives here because the readings left it off the path.

``--layer`` times the WHOLE layer instead, ``held_experts_apply`` over
``--tokens`` tokens of which each chooses ``--topk`` of ``--router`` experts
(a seeded router: near even), the first ``--experts`` held: the sort, the
copies' way into the products and back, and the products, forward and with
the five gradients; ``--layer-file`` times other versions of
``moe/held_experts.py`` beside the tree's in the same process (``git show
<commit>:<path>`` into the git-ignored ``.chip_checkout/``).
"""

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402


def _tgmm_kernel(expert_ref, live_ref, x_ref, dy_ref, dw_ref, acc_ref, *, nm):
    """Grid ``(k tile, n tile, row tile m)``, ``m`` innermost: the row tiles
    of one expert are consecutive (the padded layout), so its ``[bk, bn]``
    block of the output is accumulated over them and written once."""
    m = pl.program_id(2)
    live = live_ref[0]

    @pl.when(m < live)
    def _live():
        e = expert_ref[m]
        first = jnp.logical_or(m == 0, expert_ref[jnp.maximum(m - 1, 0)] != e)
        last = jnp.logical_or(m == live - 1,
                              expert_ref[jnp.minimum(m + 1, nm - 1)] != e)

        @pl.when(first)
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], dy_ref[...], (((0, ), (0, )), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(last)
        def _flush():
            dw_ref[0] = acc_ref[...].astype(dw_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k"))
def tgmm(x, dy, group_sizes, *, block_m=256, block_n=256, block_k=512):
    """Transposed grouped product: ``dw[g] = x[rows of g].T @ dy[rows of g]``.
    x: [T, K], dy: [T, N], rows SORTED by group; returns [E, K, N] in x's
    type, zeros for a group with no row."""
    from deepspeed_tpu.ops.pallas._common import interpret_mode
    from deepspeed_tpu.ops.pallas.grouped_matmul import _pad_layout
    T, K = x.shape
    N = dy.shape[1]
    E = group_sizes.shape[0]
    dest, expert_of_tile, live, tp = _pad_layout(group_sizes, T, E, block_m)
    in_group = (jnp.arange(T) < jnp.sum(group_sizes))[:, None]
    xp = jnp.zeros((tp, K), x.dtype).at[dest].set(jnp.where(in_group, x, 0))
    dyp = jnp.zeros((tp, N), dy.dtype).at[dest].set(
        jnp.where(in_group, dy, 0))
    nm, nn, nk = tp // block_m, N // block_n, K // block_k

    def row(k, n, m, e, live):
        return jnp.where(m < live[0], m, jnp.maximum(live[0] - 1, 0))

    dw = pl.pallas_call(
        functools.partial(_tgmm_kernel, nm=nm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(nk, nn, nm),
            in_specs=[
                pl.BlockSpec((block_m, block_k),
                             lambda k, n, m, e, l: (row(k, n, m, e, l), k)),
                pl.BlockSpec((block_m, block_n),
                             lambda k, n, m, e, l: (row(k, n, m, e, l), n))],
            out_specs=pl.BlockSpec(
                (1, block_k, block_n),
                lambda k, n, m, e, l: (e[row(k, n, m, e, l)], k, n)),
            scratch_shapes=[pltpu.VMEM((block_k, block_n), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((E, K, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_mode(),     # off the chip: the tests' CPU
        name="ds_grouped_matmul_t",
    )(expert_of_tile, live, xp, dyp)
    # a group with no row has no tile: its block was never written
    return jnp.where((group_sizes > 0)[:, None, None], dw, 0)


def gmm_with_gradient(blocks):
    """``ds_grouped_matmul`` under a ``custom_vjp`` with tiles ``blocks``."""
    from deepspeed_tpu.ops.pallas.grouped_matmul import gmm
    bm, bn, bk = blocks
    tile = lambda n, want: next(t for t in (want, 512, 256, 128)
                                if n % t == 0)

    def product(x, w, sizes):
        return gmm(x, w, sizes, block_m=bm, block_n=tile(w.shape[2], bn),
                   block_k=tile(w.shape[1], bk))

    @jax.custom_vjp
    def dot(x, w, sizes):
        return product(x, w, sizes)

    def fwd(x, w, sizes):
        return product(x, w, sizes), (x, w, sizes)

    def bwd(res, dy):
        x, w, sizes = res
        in_group = (jnp.arange(x.shape[0]) < jnp.sum(sizes))[:, None]
        # a row in no group comes back from the kernel as whatever its
        # buffer held: it has no gradient
        dx = jnp.where(in_group, product(dy, w.swapaxes(1, 2), sizes), 0)
        dw = tgmm(x, dy, sizes, block_m=bm, block_n=tile(w.shape[2], bn),
                  block_k=tile(w.shape[1], bk))
        return dx, dw, None

    dot.defvjp(fwd, bwd)
    return dot


def sorted_to_blocks(a, sizes, cap):
    """Sorted rows ``[C, D]`` -> blocks ``[E, cap, D]``: slot ``(e, j)`` is
    group e's j-th row, zeros past its rows (``held_experts_apply``'s
    gather)."""
    j = jnp.arange(cap)
    valid = j < sizes[:, None]
    first = jnp.cumsum(sizes) - sizes
    return jnp.where(valid[..., None],
                     a[jnp.where(valid, first[:, None] + j, 0)], 0)


def blocks_to_sorted(blocks, sizes, rows):
    """Blocks ``[E, cap, D]`` -> sorted rows ``[rows, D]`` (a row in no group
    reads some block's row: the caller's mask cuts it off)."""
    ends = jnp.cumsum(sizes)
    i = jnp.arange(rows)
    g = jnp.minimum(jnp.searchsorted(ends, i, side="right"),
                    sizes.shape[0] - 1)
    return blocks[g, jnp.minimum(i - (ends - sizes)[g], blocks.shape[1] - 1)]


def reglu(dot):
    def ffn(x, sizes, w1, w2, w3):
        return dot(jax.nn.relu(dot(x, w1, sizes)) * dot(x, w3, sizes), w2,
                   sizes)
    return ffn


def write(lines, out):
    if out:
        os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
        with open(out, "w") as f:
            f.writelines(json.dumps(line) + "\n" for line in lines)
    return 0


def timed(fn, args, reps):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps, out


def layer_lines(opts):
    """One line a version of ``moe/held_experts.py``: the whole layer under a
    gradient at a training step's shapes."""
    import importlib.util
    from deepspeed_tpu.moe import held_experts
    versions = {"tree": held_experts}
    for path in filter(None, opts.layer_file.split(",")):
        spec = importlib.util.spec_from_file_location(
            "held_experts_%d" % len(versions), path)
        versions[path] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(versions[path])
    T, k, R = opts.tokens, opts.topk, opts.router
    H, D, I = opts.experts, opts.hidden, opts.width
    key = jax.random.PRNGKey(1)
    draw = lambda i, *shape: jax.random.normal(
        jax.random.fold_in(key, i), shape, jnp.float32)
    stack = lambda i, *shape: (draw(i, *shape) / np.sqrt(shape[1])).astype(
        jnp.bfloat16)
    x, cot = draw(0, T, D).astype(jnp.bfloat16), draw(4, T, D)
    topi, topw = held_experts.route(draw(5, T, R), k)
    args = (x, topw, stack(1, H, D, I), stack(2, H, I, D), stack(3, H, D, I))
    lines, want = [], None
    for name, module in versions.items():
        layer = lambda x, topw, w1, w2, w3, m=module: m.held_experts_apply(
            x, topi, topw, w1, w2, w3, experts=R, act=jax.nn.relu)
        fwd = jax.jit(layer)
        both = jax.jit(jax.grad(lambda *a: jnp.sum(
            layer(*a)[0].astype(jnp.float32) * cot), argnums=(0, 1, 2, 3, 4)))
        s_fwd, (_, counts) = timed(fwd, args, opts.reps)
        s_both, grads = timed(both, args, opts.reps)
        got = [np.asarray(g.astype(jnp.float32)) for g in grads]
        want = got if want is None else want
        lines.append({
            "path": "layer:" + name, "tokens": T,
            "copies": int(jnp.sum(counts)), "fullest": int(jnp.max(counts)),
            "fwd_ms": 1e3 * s_fwd, "fwd_bwd_ms": 1e3 * s_both,
            "tflops": int(jnp.sum(counts)) * 18 * D * I / s_both / 1e12,
            "finite": bool(all(np.isfinite(g).all() for g in got)),
            "grad_max_diff_to_first": [
                float(np.max(np.abs(a - b))) for a, b in zip(got, want)],
            "grad_max": [float(np.max(np.abs(b))) for b in want]})
        print(json.dumps(lines[-1]), flush=True)
    return lines


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--experts", type=int, default=16)
    ap.add_argument("--hidden", type=int, default=2560)
    ap.add_argument("--width", type=int, default=768)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--cases", default="15360:12288,16384:12288,"
                    "16384:16384,49152:12288")
    ap.add_argument("--block-rows", type=int, default=1024)
    ap.add_argument("--paths", default="",
                    help="only the paths whose name holds one of these")
    ap.add_argument("--layer", action="store_true")
    ap.add_argument("--layer-file", default="")
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--topk", type=int, default=6)
    ap.add_argument("--router", type=int, default=64)
    ap.add_argument("--out")
    opts = ap.parse_args()
    if opts.layer:
        return write(layer_lines(opts), opts.out)
    from deepspeed_tpu.moe.held_experts import padded_swiglu
    E, D, I, cap = opts.experts, opts.hidden, opts.width, opts.block_rows
    key = jax.random.PRNGKey(0)
    draw = lambda i, *shape: jax.random.normal(
        jax.random.fold_in(key, i), shape, jnp.bfloat16) / np.sqrt(shape[1])
    w1, w3, w2 = draw(1, E, D, I), draw(2, E, D, I), draw(3, E, I, D)
    paths = {"ragged_dot": jax.lax.ragged_dot}
    for blocks in ((256, 256, 512), (256, 768, 512), (512, 256, 512),
                   (512, 768, 512), (512, 768, 2560)):
        paths["gmm_vjp_%dx%dx%d" % blocks] = gmm_with_gradient(blocks)
    paths["padded_blocks"] = paths["padded_from_sorted"] = None
    wanted = [p for p in opts.paths.split(",") if p]
    paths = {name: dot for name, dot in paths.items()
             if not wanted or any(p in name for p in wanted)}
    lines = []
    for case in opts.cases.split(","):
        rows, live = (int(v) for v in case.split(":"))
        x = jax.random.normal(jax.random.fold_in(key, rows), (rows, D),
                              jnp.bfloat16)
        cot = jax.random.normal(jax.random.fold_in(key, rows + 1), (rows, D),
                                jnp.bfloat16)
        rng = np.random.default_rng(rows + live)
        # near-even groups, as a router over random weights gives them;
        # blocks that are full: every group a block's rows
        sizes = np.full(E, cap) if live == E * cap else \
            rng.multinomial(live, np.ones(E) / E)
        fits = sizes.max() <= cap and rows <= E * cap
        sizes = jnp.asarray(sizes, jnp.int32)
        mask = (jnp.arange(rows) < live)[:, None]
        want = None
        for name, dot in paths.items():
            first, cot_as = x, cot
            if dot is not None:
                ffn = reglu(dot)
            elif not fits:
                continue
            elif name == "padded_from_sorted":
                ffn = lambda x, sizes, w1, w2, w3: blocks_to_sorted(padded_swiglu(
                    sorted_to_blocks(x, sizes, cap), w1, w2, w3, jax.nn.relu),
                    sizes, rows)
            else:
                first = sorted_to_blocks(jnp.where(mask, x, 0), sizes, cap)
                cot_as = sorted_to_blocks(jnp.where(mask, cot, 0), sizes, cap)
                ffn = lambda xb, sizes, w1, w2, w3: padded_swiglu(
                    xb, w1, w2, w3, jax.nn.relu)
            keep = mask if first is x else True
            fwd = jax.jit(lambda x, w1, w2, w3, f=ffn, keep=keep: jnp.where(
                keep, f(x, sizes, w1, w2, w3), 0))
            loss = lambda x, w1, w2, w3, f=fwd, cot=cot_as: jnp.sum(
                f(x, w1, w2, w3).astype(jnp.float32)
                * cot.astype(jnp.float32))
            both = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))
            try:
                s_fwd, _ = timed(fwd, (first, w1, w2, w3), opts.reps)
                s_both, grads = timed(both, (first, w1, w2, w3), opts.reps)
                if first is not x:      # the rows' gradient, sorted again
                    grads = (jnp.where(mask, blocks_to_sorted(
                        grads[0], sizes, rows), 0), ) + grads[1:]
            except Exception as e:      # a tiling Mosaic refuses is a result
                lines.append({"path": name, "rows": rows, "live": live,
                              "error": f"{type(e).__name__}: {str(e)[:300]}"})
                print(json.dumps(lines[-1]), flush=True)
                continue
            got = [np.asarray(g.astype(jnp.float32)) for g in grads]
            want = got if want is None else want
            lines.append({
                "path": name, "rows": rows, "live": live,
                "fwd_ms": 1e3 * s_fwd, "fwd_bwd_ms": 1e3 * s_both,
                "tflops": live * 18 * D * I / s_both / 1e12,
                "finite": bool(all(np.isfinite(g).all() for g in got)),
                "dx_past_live_max": float(np.max(np.abs(got[0][live:])))
                if live < rows else 0.0,
                "grad_max_diff_to_ragged_dot": [
                    float(np.max(np.abs(a - b))) for a, b in zip(got, want)],
                "grad_max": [float(np.max(np.abs(b))) for b in want]})
            print(json.dumps(lines[-1]), flush=True)
    return write(lines, opts.out)


if __name__ == "__main__":
    sys.exit(main())
