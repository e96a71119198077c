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
  whose row is live are gathered sorted by expert, taken through the gated
  feed-forward of their experts and added back weighted.  The forward that
  may be differentiated (TRAINING, and the dense forward of ``models/``) lays
  them in per-expert padded blocks ``[H, block_rows, D]`` and runs three
  batched dense products, whose transposes are batched dense products too
  (:func:`padded_swiglu`), and moves its rows into the blocks and back by
  gathers alone, backward too (:func:`to_blocks`, :func:`from_blocks`: on a
  TPU a scatter-add of these rows costs thirty gathers of them); with
  ``kernel=True``, the choice of the serving
  step that timed it, they stay one sorted buffer under the Pallas
  ``ds_grouped_matmul``, which has no gradient.  ``lax.ragged_dot``
  (:func:`grouped_swiglu`) is the worst case's form on both.
  Copies that land elsewhere, and dead rows, reach no expert and nothing
  stands in for them.  With every expert held and every row live it is
  Mixtral's layer, operation for operation.

**Cost follows the live copies.**  Of a step's ``T * k`` copies the share
``H / E`` lands here on average and all of them may.  The gathered buffer's
length is a static shape, so there are two: ``tier_rows`` (the mean with a
quarter of room; differentiable: ``block_rows`` of them an expert) and
``T * k``, chosen on the device by the count of copies that landed (by the
fullest expert's count) in a ``lax.cond``: the step pays for the worst case
only when it happens.
"""

import functools

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
    off.  Training runs neither where the routing is near even, but
    :func:`padded_swiglu`'s batched dense products: at a training step's
    shapes (16 experts of 2560 x 768, 12 288 copies) ``ragged_dot`` and its
    transposes take half the time of the kernel under a ``custom_vjp``, and
    the padded blocks less than either (``tools/moe_gmm_train_bench.py``,
    docs/kernels.md, "Under a gradient"); ``ragged_dot`` stays the form of
    the worst case.  A row past ``sum(group_sizes)`` is in no group and its
    result is undefined, with either path and in ``ragged_dot``'s transposes
    too (on a TPU such rows come back as what the buffer held)."""
    if not kernel:
        return jax.lax.ragged_dot(x_sorted, w, group_sizes)
    from ..ops.pallas.grouped_matmul import gmm
    _, K, N = w.shape
    return gmm(x_sorted, w, group_sizes.astype(jnp.int32), block_m=256,
               block_n=_tile(N), block_k=_tile(K))


def grouped_swiglu(x_sorted, group_sizes, w1, w2, w3, kernel=False,
                   act=jax.nn.silu, row_coef=None):
    """The gated feed-forward of each group's expert over rows sorted by
    group: ``w2 (act(w1 x) * w3 x)``, SwiGLU with the default ``act``, ReGLU
    with ``jax.nn.relu``; with ``row_coef [C, n]`` (each row's own expert's
    coefficients) ``act`` is a ROW-wise function ``act(gate, row_coef)``
    between the grouped products (a normalising activation: PolyNorm).

    x_sorted: [C, D] (group g's rows contiguous, rows past ``sum(group_sizes)``
    in no group: mask what comes back for them); group_sizes: [E]; w1/w3:
    [E, D, I]; w2: [E, I, D]; ``kernel``: :func:`grouped_matmul`'s.  Returns
    [C, D]."""
    gate = grouped_matmul(x_sorted, w1, group_sizes, kernel)
    up = grouped_matmul(x_sorted, w3, group_sizes, kernel)
    gate = act(gate) if row_coef is None else act(gate, row_coef)
    return grouped_matmul(gate * up, w2, group_sizes, kernel)


def padded_swiglu(x_blocks, w1, w2, w3, act=jax.nn.silu, coef=None):
    """:func:`grouped_swiglu` over PER-EXPERT PADDED BLOCKS: three batched
    dense products over the expert axis, under a gradient six more of the
    same kind.  x_blocks: [E, R, D], expert e's rows in block e and rows of
    zeros after them (zeros in, zeros out: ``act(0) * 0 = 0``, and a row of
    zeros gives no weight a gradient); w1/w3: [E, D, I]; w2: [E, I, D];
    ``coef [E, n]``: :func:`grouped_swiglu`'s ``row_coef``, an expert's for
    every row of its block.  Returns [E, R, D]."""
    gate = jnp.einsum("erd,edi->eri", x_blocks, w1)
    up = jnp.einsum("erd,edi->eri", x_blocks, w3)
    gate = act(gate) if coef is None else act(gate, coef[:, None])
    return jnp.einsum("eri,eid->erd", gate * up, w2)


def tier_rows(tokens, k, held, experts):
    """The length of the gathered buffer that holds a step's copies when the
    routing is near even: the mean ``tokens * k * held / experts`` and a
    quarter more, in whole tiles of 128 rows; None where that is no shorter
    than the worst case (every expert held) or the worst case is small."""
    full = tokens * k
    rows = -(-(full * held * 5 // (experts * 4)) // 128) * 128
    return rows if full > _ONE_TIER_ROWS and rows < full else None


def block_rows(tokens, k, held, experts):
    """The rows of ONE held expert's padded block in the forward that may be
    differentiated: its share of :func:`tier_rows` in whole tiles of 128
    rows; None where there is no tier.  A step whose fullest expert holds
    more takes the worst case's buffer."""
    tier = tier_rows(tokens, k, held, experts)
    return tier and -(-tier // (held * 128)) * 128


def in_blocks(counts, tokens, k, experts):
    """Whether :func:`held_experts_apply` without ``kernel`` runs a call in
    padded blocks, from what the call returned: counts [..., H] the copies
    on each held expert -> bool [...], made on the device (what a model
    counts as ``expert_padded_calls``)."""
    rows = block_rows(tokens, k, counts.shape[-1], experts)
    if rows is None:
        return jnp.zeros(counts.shape[:-1], bool)
    return jnp.max(counts, axis=-1) <= rows


def _tokens_sum(rows, slot, w):
    """``out[t] = sum_i w[t, i] rows[slot[t, i]]`` in float32: ``k`` gathers
    of ``T`` rows (``w`` is 0 for a copy with no slot)."""
    return sum(rows[slot[:, i]].astype(jnp.float32) * w[:, i, None]
               for i in range(slot.shape[1]))


@jax.custom_vjp
def to_blocks(x, moves):
    """Tokens ``[T, D]`` -> the slots of the padded blocks ``[S, D]``: slot s
    reads the token of its copy, a slot with none zeros.  ``moves`` =
    ``(copy [S], valid [S], slot [T, k], held [T, k])``: a slot's copy
    ``t * k + i`` and whether it has one; a copy's slot and whether it has
    one.  Its transpose sums each token's slots (:func:`_tokens_sum`), where
    JAX's own would scatter-add ``S`` rows (4.4 ms against 0.4 at the
    training cell's shapes: docs/kernels.md)."""
    copy, valid, slot, _ = moves
    return jnp.where(valid[:, None], x[copy // slot.shape[1]], 0)


def _to_blocks_bwd(moves, g):
    _, _, slot, held = moves
    return _tokens_sum(g, slot, held.astype(jnp.float32)).astype(g.dtype), None


to_blocks.defvjp(lambda x, moves: (to_blocks(x, moves), moves),
                 _to_blocks_bwd)


@jax.custom_vjp
def from_blocks(y, w, moves):
    """The slots' results ``[S, D]`` -> tokens ``[T, D]`` in y's type: each
    token's held copies weighed by ``w [T, k]`` (float32) and summed in
    float32.  ``moves``: :func:`to_blocks`'s.  Its transposes are gathers
    too: a slot's gradient is its token's, weighed; a weight's is its slot's
    result times its token's gradient."""
    _, _, slot, held = moves
    return _tokens_sum(y, slot, jnp.where(held, w, 0)).astype(y.dtype)


def _from_blocks_bwd(res, g):
    y, w, moves = res
    copy, _, slot, held = moves
    g_slots = to_blocks(g, moves)
    dy = g_slots * w.reshape(-1)[copy].astype(g.dtype)[:, None]
    dw_slots = jnp.sum(y.astype(jnp.float32) * g_slots.astype(jnp.float32),
                       axis=-1)
    return dy, jnp.where(held, dw_slots[slot], 0).astype(w.dtype), None


from_blocks.defvjp(lambda y, w, moves: (from_blocks(y, w, moves),
                                        (y, w, moves)), _from_blocks_bwd)


@functools.partial(jax.jit, static_argnames=("rows", "act"))
def _blocks_layer(x, topw, w1, w2, w3, here, key, order, counts, coef=None,
                  *, rows, act):
    """:func:`held_experts_apply`'s layer over ``H`` padded blocks of ``rows``
    slots: slot ``(e, j)`` is expert e's j-th sorted copy, a slot past its
    copies a row of zeros.  Rows move by GATHERS alone, forward and backward
    (:func:`to_blocks`, :func:`from_blocks`).  here [T, k]: the copies on a
    held expert; key [T k]: their expert (H: none); order: ``argsort(key)``;
    counts [H].  Jitted for the trace's sake: a model's layers share their
    shapes, so all but the first reuse its jaxpr (and its derivatives)."""
    (T, D), k, H = x.shape, topw.shape[1], w1.shape[0]
    j = jnp.arange(rows)
    valid = j < counts[:, None]                                 # [H, rows]
    first = jnp.cumsum(counts) - counts
    copy = order[jnp.where(valid, first[:, None] + j, 0)]
    # a held copy's slot: its expert's block, and its place among the
    # sorted copies counted from that expert's first
    slot = key * rows + jnp.argsort(order) - first[jnp.minimum(key, H - 1)]
    moves = (copy.reshape(-1), valid.reshape(-1),
             jnp.where(here, slot.reshape(T, k), 0), here)
    y = padded_swiglu(to_blocks(x, moves).reshape(H, rows, D), w1, w2, w3,
                      act, coef)
    return from_blocks(y.reshape(-1, D), topw.astype(jnp.float32), moves)


def held_experts_apply(x, topi, topw, w1, w2, w3, *, first_expert=0,
                       experts=None, live=None, kernel=False,
                       act=jax.nn.silu, act_coef=None):
    """The held experts' part of a top-k expert layer, exact.

    x: [T, D]; topi/topw: [T, k] each token's experts (ids over the router's
    width ``experts``, default: the stacks' length) and weights; w1/w3:
    [H, D, I], w2: [H, I, D] the experts ``first_expert .. first_expert + H -
    1``; live: [T] bool (None: every row); ``kernel``: the Pallas grouped
    matmul (:func:`grouped_matmul`) in the buffer of ``tier_rows``, or in the
    one buffer where there is no second; without it (the forward that may be
    differentiated) the tier is ``H`` padded blocks of ``block_rows`` under
    :func:`padded_swiglu`, taken when the fullest expert's copies fit one;
    the worst case's buffer behind the ``lax.cond`` keeps ``ragged_dot``,
    which the chip's readings put ahead there; ``act``: the gate's
    activation (:func:`grouped_swiglu`), element-wise, or with ``act_coef
    [H, n]`` (one set of coefficients a held expert) row-wise and told each
    copy's expert: ``act(gate, that expert's coefficients)``.  Returns
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
            # a sorted copy's expert is its key (a row in no group: the last)
            row_coef = None if act_coef is None else \
                act_coef[jnp.minimum(key[copy], H - 1)]
            y = grouped_swiglu(jnp.where(in_group, x[token_of], 0), counts,
                               w1, w2, w3, kernel, act, row_coef)
            w = weights[copy].astype(y.dtype)
            y = jnp.where(in_group, y, 0) * w[:, None]
            return jnp.zeros((T, D), y.dtype).at[token_of].add(y)
        return run

    experts = experts or H
    tier = tier_rows(T, k, H, experts)
    if tier is None:
        out = part(T * k, kernel)(None)
    elif kernel:
        out = jax.lax.cond(landed <= tier, part(tier, True),
                           part(T * k, False), None)
    else:
        rows = block_rows(T, k, H, experts)
        out = jax.lax.cond(
            in_blocks(counts, T, k, experts),
            lambda _: _blocks_layer(x, topw, w1, w2, w3, here, key, order,
                                    counts, act_coef, rows=rows, act=act),
            part(T * k, False), None)
    return out.astype(x.dtype), counts
