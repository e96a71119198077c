"""Sequence-parallel cross entropy — analog of reference
``deepspeed/sequence/cross_entropy.py:11`` (vocab_sequence_parallel_cross_entropy).

With the sequence dim sharded over sp, each rank computes CE over its local
tokens; the mean over the full sequence is a psum.  Usable inside shard_map
(axis-name form) or on global arrays (GSPMD handles the reduction).
"""

import functools

import jax
import jax.numpy as jnp


def softmax_cross_entropy_with_logits(logits, labels):
    """[.., V] logits, [..] int labels → [..] per-token loss (stable)."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return logz - gold


def _chunk_rows(n, v, chunk_size):
    """Rows a chunk: as many as hold the logits of an ``[n, chunk_size]``
    array (``chunk_size`` bounds the logits alive at once), a multiple of 8;
    all ``n`` when that is every row (``chunk_size`` 0 or >= ``v``)."""
    if not chunk_size or chunk_size >= v:
        return n
    rows = -(-n * chunk_size // v)
    rows = -(-rows // 8) * 8
    return n if rows >= n else rows


def _row_chunks(rows, *arrays):
    """Each ``[N, ...]`` array as ``[chunks, rows, ...]``, padded with zeros
    (a padded row carries weight 0).  Chunk ``c`` holds the rows ``c``,
    ``c + chunks``, ...: of rows sharded over devices (a batch over "dp")
    every chunk then takes an equal part from each, where a chunk of
    neighbours would lie on one device and be computed by all."""
    pad = -arrays[0].shape[0] % rows
    return tuple(
        jnp.moveaxis(jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)
                             ).reshape((rows, -1) + a.shape[1:]), 1, 0)
        for a in arrays)


def _rows_back(a, n):
    """``_row_chunks``'s inverse: ``[chunks, rows, ...]`` as ``[n, ...]``."""
    return jnp.moveaxis(a, 0, 1).reshape((-1,) + a.shape[2:])[:n]


def _chunk_stats(xc, w, labels):
    """One chunk of rows: float32 logits ``[rows, V]``, the whole row's
    log-sum-exp, the mask of each row's label (a compare against an iota:
    no gather forward, no scatter backward) and the per-row loss."""
    logits = (xc @ w).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    hit = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1) \
        == labels[:, None]
    gold = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
    return logits, lse, hit, lse - gold


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _head_loss(chunk_size, ld, x, w, labels, row_weights):
    """``x`` and ``w`` in the logits' dtype ``ld``, ``row_weights`` float32.
    The primal alone (an evaluation step): one product a chunk."""
    rows = _chunk_rows(x.shape[0], w.shape[1], chunk_size)

    def body(total, chunk):
        xc, lab, rw = chunk
        loss = _chunk_stats(xc, w, lab)[-1]
        return total + jnp.sum(loss * rw), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                            _row_chunks(rows, x, labels, row_weights))
    return total


def _head_loss_fwd(chunk_size, ld, x, w, labels, row_weights):
    """Loss and, at unit cotangent, both gradients in one pass over the row
    chunks: three products a chunk, nothing for the backward to compute."""
    n, v = x.shape[0], w.shape[1]
    rows = _chunk_rows(n, v, chunk_size)
    # the weights meet the logits' dtype divided by their largest, and the
    # float32 products are multiplied back: 1 / N of a long sequence times a
    # small probability is below float16's range, and the loss scale that
    # would lift it arrives only with the cotangent
    top = jnp.maximum(jnp.max(jnp.abs(row_weights)),
                      jnp.finfo(jnp.float32).tiny)

    def body(dw, chunk):
        xc, lab, rw = chunk
        logits, lse, hit, loss = _chunk_stats(xc, w, lab)
        dlogits = ((jnp.exp(logits - lse[:, None]) - hit) * rw[:, None]
                   ).astype(ld)
        dx = top * jax.lax.dot_general(
            dlogits, w, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dw = dw + top * jax.lax.dot_general(
            xc, dlogits, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dw, (loss, dx)

    dw, (loss, dx) = jax.lax.scan(
        body, jnp.zeros(w.shape, jnp.float32),
        _row_chunks(rows, x, labels, row_weights / top))
    loss, dx = _rows_back(loss, n), _rows_back(dx, n)
    return jnp.sum(loss * row_weights), (dx, dw, loss)


def _head_loss_bwd(chunk_size, ld, res, g):
    # float32 residuals at unit cotangent: a loss scale multiplies float32
    # numbers before they are rounded to the logits' dtype
    dx, dw, loss = res
    return (g * dx).astype(ld), (g * dw).astype(ld), None, g * loss


_head_loss.defvjp(_head_loss_fwd, _head_loss_bwd)


def fused_linear_cross_entropy(x, w, labels, chunk_size, logit_dtype=None,
                               row_weights=None):
    """``sum_n row_weights[n] * CE_n`` of ``x @ w`` against ``labels``
    WITHOUT materializing the [N, V] logits: the TPU answer to the
    reference's chunked logits loss (``deepspeed/sequence/fpdt_layer.py:1137``
    FPDT_LogitsLoss), which chunks the sequence as this does.

    ``x``: [N, D] hidden states, ``w``: [D, V] head kernel, ``labels``: [N]
    int32, ``row_weights``: [N] (``None`` = the mean, ``1 / N``: a mask
    divided by its sum weighs rows as a masked mean does).  Returns the
    float32 scalar.  The rows' weights come in and the scalar goes out
    because ``dW``, summed over rows, cannot be rescaled row by row later.

    ``chunk_size`` bounds the logits alive at once to those of an
    ``[N, chunk_size]`` array: a ``lax.scan`` runs over chunks of
    ``ceil(N * chunk_size / V)`` ROWS (a multiple of 8; ``N`` padded with rows
    of weight 0), each a whole ``[rows, V]`` slab of logits in ``logit_dtype``
    (default ``x.dtype``) widened to float32.  A chunk of rows can finish its
    softmax, so under differentiation the same pass forms ``dlogits =
    (softmax - onehot) * row_weights`` and runs both gradient products while
    the chunk's logits are there: three products a chunk (logits, ``dx``,
    ``dW`` into a float32 carry), none of them twice, and no [N, V] array in
    either pass.  The backward rule only scales the float32 ``dx`` / ``dW``
    by the cotangent (an fp16 run's loss scale arrives there) and casts.
    The cotangent of ``row_weights`` is the per-row loss.  Called without
    differentiation it runs the loss alone, one product a chunk.
    """
    n = x.shape[0]
    ld = jnp.dtype(logit_dtype) if logit_dtype is not None else x.dtype
    if row_weights is None:
        row_weights = jnp.full((n,), 1.0 / n, jnp.float32)
    return _head_loss(int(chunk_size), ld, x.astype(ld), w.astype(ld), labels,
                      row_weights.astype(jnp.float32))


def vocab_sequence_parallel_cross_entropy(logits, labels, sp_axis=None,
                                          reduction="mean"):
    """Per-token CE; if called inside shard_map with ``sp_axis`` given, the
    mean reduces over the global sequence via pmean."""
    loss = softmax_cross_entropy_with_logits(logits, labels)
    if reduction == "none":
        return loss
    local = jnp.mean(loss)
    if sp_axis is not None:
        try:
            local = jax.lax.pmean(local, sp_axis)
        except NameError:
            pass
    return local
