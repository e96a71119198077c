"""An exact top-k expert layer that is TOLD which experts it holds.

A deployment of a many-expert model divides each layer's experts over several
chips; every chip routes its tokens over the router's FULL width and computes
the part of the result that its own experts give.  This module is that part,
for serving, for the dense forward of ``models/`` and, under ``jax.grad``, for
training (``models/smallthinker.py``): no capacity, no dropped copy, whatever
the routing.

* :func:`route` — router logits ``[T, E]`` -> each token's ``k`` experts and
  weights: softmax over all ``E`` then top-k (Mixtral), or top-k of the
  logits with sigmoid scores (``expert_selection_fn: sigmoid``), either
  normalised over the ``k`` chosen or not; with a ``bias`` the choice is by
  ``score + bias`` and the weights are the scores.
* :func:`held_experts_apply` — the (token, expert) copies whose expert lies
  in ``first_expert .. first_expert + H - 1`` (the stacks' own length) and
  whose row is live are gathered sorted by expert, taken through the grouped
  gated feed-forward (:func:`grouped_swiglu`: ``lax.ragged_dot``, which has a
  gradient; with ``kernel=True``, the choice of the serving step that timed
  it, the Pallas ``ds_grouped_matmul``, which has none) and added back
  weighted.
  Copies that land elsewhere, and dead rows, reach no expert and nothing
  stands in for them.  With every expert held and every row live it is
  Mixtral's layer, operation for operation.

**Cost follows the live copies.**  Of a step's ``T * k`` copies the share
``H / E`` lands here on average and all of them may.  The gathered buffer's
length is a static shape, so there are two: ``tier_rows`` (the mean with a
quarter of room) and ``T * k``, chosen on the device by the count of copies
that landed (``lax.cond``): the step pays for the worst case only when it
happens.
"""

import jax
import jax.numpy as jnp

#: under this many rows one buffer of the worst case's length: the grouped
#: matmuls then read their weights once whatever the rows, and a second
#: shape would buy nothing
_ONE_TIER_ROWS = 1024


def route(router_logits, k, score="softmax", norm_topk=True, scale=1.0,
          bias=None):
    """``(experts [T, k] int32, weights [T, k] float32)`` of router logits
    ``[T, E]``.  ``score``: ``"softmax"`` (over all ``E``, then the ``k``
    largest) or ``"sigmoid"`` (the ``k`` largest logits, each through the
    sigmoid, which is monotone); ``norm_topk``: weights divided by their sum
    over the ``k``; ``scale``: a factor on the weights as they come out
    (``routed_scaling_factor``); ``bias [E]`` (``e_score_correction_bias``):
    the ``k`` are chosen by ``score + bias`` and weighed by the score
    WITHOUT it."""
    if score not in ("softmax", "sigmoid"):
        raise ValueError(f"router score {score!r}")
    logits = router_logits.astype(jnp.float32)
    if bias is not None:
        scores = jax.nn.softmax(logits, axis=-1) if score == "softmax" \
            else jax.nn.sigmoid(logits)
        _, topi = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
        topw = jnp.take_along_axis(scores, topi, axis=-1)
    elif score == "softmax":
        topw, topi = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    else:
        top, topi = jax.lax.top_k(logits, k)
        topw = jax.nn.sigmoid(top)
    if norm_topk:
        topw = topw / jnp.sum(topw, axis=-1, keepdims=True)
    if scale != 1.0:
        topw = topw * scale
    return topi, topw


def _tile(n):
    """The widest of the kernel's tiles that divides ``n``; a width that none
    divides is one tile."""
    return next((t for t in (1024, 512, 256, 128) if n % t == 0), n)


def grouped_matmul(x_sorted, w, group_sizes, kernel=False):
    """``y[i] = x_sorted[i] @ w[g(i)]`` over rows sorted by group: XLA's
    ``lax.ragged_dot`` or, with ``kernel``, the Pallas ``ds_grouped_matmul``
    with row tiles of 256 and the widest tiles of the weights that divide
    them.  The kernel is the CALLER's choice, for the shapes it timed
    (docs/kernels.md: ahead of ``ragged_dot`` on a v5e in a buffer of up to
    2560 rows over 16 experts of 4096 x 4096, behind it in one of 16 384),
    and it has no gradient: a forward that may be differentiated leaves it
    off.  Training keeps ``ragged_dot`` and its transposes: at a training
    step's shapes (15 360 rows over 16 experts of 2560 x 768) they take half
    the time of the kernel under a ``custom_vjp`` with a transposed grouped
    product for the weights' gradient (``tools/moe_gmm_train_bench.py``,
    docs/kernels.md).  A row past ``sum(group_sizes)`` is in no group and its
    result is undefined, with either path and in ``ragged_dot``'s transposes
    too (on a TPU such rows come back as what the buffer held)."""
    if not kernel:
        return jax.lax.ragged_dot(x_sorted, w, group_sizes)
    from ..ops.pallas.grouped_matmul import gmm
    _, K, N = w.shape
    return gmm(x_sorted, w, group_sizes.astype(jnp.int32), block_m=256,
               block_n=_tile(N), block_k=_tile(K))


def grouped_swiglu(x_sorted, group_sizes, w1, w2, w3, kernel=False,
                   act=jax.nn.silu):
    """The gated feed-forward of each group's expert over rows sorted by
    group: ``w2 (act(w1 x) * w3 x)``, SwiGLU with the default ``act``, ReGLU
    with ``jax.nn.relu``.

    x_sorted: [C, D] (group g's rows contiguous, rows past ``sum(group_sizes)``
    in no group: mask what comes back for them); group_sizes: [E]; w1/w3:
    [E, D, I]; w2: [E, I, D]; ``kernel``: :func:`grouped_matmul`'s.  Returns
    [C, D]."""
    gate = grouped_matmul(x_sorted, w1, group_sizes, kernel)
    up = grouped_matmul(x_sorted, w3, group_sizes, kernel)
    return grouped_matmul(act(gate) * up, w2, group_sizes, kernel)


def tier_rows(tokens, k, held, experts):
    """The length of the gathered buffer that holds a step's copies when the
    routing is near even: the mean ``tokens * k * held / experts`` and a
    quarter more, in whole tiles of 128 rows; None where that is no shorter
    than the worst case (every expert held) or the worst case is small."""
    full = tokens * k
    rows = -(-(full * held * 5 // (experts * 4)) // 128) * 128
    return rows if full > _ONE_TIER_ROWS and rows < full else None


def held_experts_apply(x, topi, topw, w1, w2, w3, *, first_expert=0,
                       experts=None, live=None, kernel=False,
                       act=jax.nn.silu):
    """The held experts' part of a top-k expert layer, exact.

    x: [T, D]; topi/topw: [T, k] each token's experts (ids over the router's
    width ``experts``, default: the stacks' length) and weights; w1/w3:
    [H, D, I], w2: [H, I, D] the experts ``first_expert .. first_expert + H -
    1``; live: [T] bool (None: every row); ``kernel``: the Pallas grouped
    matmul (:func:`grouped_matmul`) in the buffer of ``tier_rows``, or in the
    one buffer where there is no second; the worst case's buffer behind the
    ``lax.cond`` keeps ``ragged_dot``, which the chip's readings put ahead
    there; ``act``: the gate's activation (:func:`grouped_swiglu`).  Returns
    ``(out [T, D] in x's type, counts [H] int32)``: the weighted sum over
    each row's experts that are held, and the copies that landed on each held
    expert."""
    T, D = x.shape
    H, k = w1.shape[0], topi.shape[1]
    local = topi.astype(jnp.int32) - first_expert
    here = (local >= 0) & (local < H)
    if live is not None:
        here &= live[:, None]
    key = jnp.where(here, local, H).reshape(-1)       # H: past every group
    order = jnp.argsort(key)                          # stable
    counts = jnp.zeros((H + 1, ), jnp.int32).at[key].add(1)[:H]
    landed = jnp.sum(counts)
    weights = topw.reshape(-1)

    def part(rows, kernel):
        """The layer over the first ``rows`` sorted copies (all that landed
        are among them)."""
        def run(_):
            copy = order[:rows]
            token_of = copy // k
            # a row past the copies that landed is in no group: what the
            # grouped products return for it is undefined, forward and (the
            # rows' gradient) backward, so it is cut off on both sides, the
            # result before its weight multiplies it (the weight's gradient
            # is the result)
            in_group = (jnp.arange(rows) < landed)[:, None]
            y = grouped_swiglu(jnp.where(in_group, x[token_of], 0), counts,
                               w1, w2, w3, kernel, act)
            w = weights[copy].astype(y.dtype)
            y = jnp.where(in_group, y, 0) * w[:, None]
            return jnp.zeros((T, D), y.dtype).at[token_of].add(y)
        return run

    tier = tier_rows(T, k, H, experts or H)
    if tier is None:
        out = part(T * k, kernel)(None)
    else:
        out = jax.lax.cond(landed <= tier, part(tier, kernel),
                           part(T * k, False), None)
    return out.astype(x.dtype), counts
