"""Ulysses sequence parallelism — TPU-native re-design of reference
``deepspeed/sequence/layer.py`` (``DistributedAttention`` ``:300``,
``_SeqAllToAll`` ``:245``, ``single_all_to_all`` ``:182``).

Semantics (identical to the reference): the transformer runs with the
**sequence** dimension sharded over the "sp" mesh axis; around attention, an
all-to-all re-shards from sequence-split to **head-split** (each rank holds
full sequence for H/sp heads), local attention runs, and the inverse
all-to-all restores sequence sharding.  On TPU both all-to-alls are
``jax.lax.all_to_all`` over the sp axis inside ``shard_map`` — XLA lays them
on ICI; gradients are handled by autodiff (all_to_all is its own transpose),
so no custom autograd.Function is needed.

GQA/uneven heads (reference ``uneven_heads_all2all`` ``:72-196``): when
``n_heads % sp != 0`` the q heads are zero-padded up to the next multiple of
sp — static shapes, so XLA still tiles the a2a + attention onto the MXU —
and sliced back after the inverse a2a.  KV heads are routed, not
replicated: each rank assembles (from its local sequence chunk) the kv head
every destination rank's q block needs, and ONE all-to-all delivers exactly
those — post-reshard kv memory is [B, S, H_local, D] like q, never
[B, S, n_kv, D] as a sequence all-gather would give.  All head-routing
indices are computed in Python at trace time.
"""

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils import groups


def _default_attention(q, k, v, causal=True, softmax_scale=None, window=0):
    """Local attention core [B, S, H, D].  After the Ulysses a2a the
    sequence axis is global, so causal/sliding-window masks apply directly;
    one shared implementation with attention_core's XLA path."""
    from ..ops.attention import _xla_attention
    return _xla_attention(q, k, v, causal=causal,
                          softmax_scale=softmax_scale, window=window)


def single_all_to_all(x, scatter_idx, gather_idx, axis_name):
    """All-to-all inside a shard_map region (reference ``:182``): scatter
    ``scatter_idx`` across the axis, gather ``gather_idx``."""
    return jax.lax.all_to_all(x, axis_name, split_axis=scatter_idx,
                              concat_axis=gather_idx, tiled=True)


class DistributedAttention:
    """Reference ``DistributedAttention`` (``sequence/layer.py:300``).

    ``local_attention``: callable (q, k, v, **kw) -> out, operating on
    [B, S_full, H_local, D] blocks.  Call this object *inside* a shard_map (or
    GSPMD-jit via ``__call__`` on global arrays with an sp-sharded seq dim).
    """

    def __init__(self, local_attention=None, sequence_process_group=None,
                 scatter_idx=2, gather_idx=1, sp_axis=None):
        self.local_attn = local_attention or _default_attention
        self.sp_axis = sp_axis or groups.SP_AXIS
        self.scatter_idx = scatter_idx  # head dim of [B,S,H,D]
        self.gather_idx = gather_idx    # sequence dim

    @staticmethod
    def _check_gqa_heads(n_q_heads, n_kv):
        """GQA requires q heads in whole groups per kv head — otherwise the
        routing table's clip-mode ``jnp.take`` silently maps the surplus q
        heads onto the LAST kv head (wrong attention, right shapes)."""
        if n_q_heads % n_kv != 0:
            raise ValueError(
                f"invalid GQA config: {n_q_heads} query heads are not an "
                f"integer multiple of {n_kv} kv heads — each kv head must "
                "serve the same whole number of q heads")

    def _align_gqa_local(self, q, k, v):
        """sp=1 / passthrough: the local core expects matched head counts,
        so native-width GQA kv repeats here (callers pass kv UN-repeated —
        the sp>1 reshard aligns on the wire instead)."""
        n_kv, H = k.shape[self.scatter_idx], q.shape[self.scatter_idx]
        if n_kv != H:
            self._check_gqa_heads(H, n_kv)
            rep = H // n_kv
            k = jnp.repeat(k, rep, axis=self.scatter_idx)
            v = jnp.repeat(v, rep, axis=self.scatter_idx)
        return k, v

    # ---- traced form: call inside shard_map; x are local blocks ------------
    def attend_local(self, q, k, v, **kwargs):
        a = self.sp_axis
        sp = jax.lax.axis_size(a)
        if sp == 1:
            k, v = self._align_gqa_local(q, k, v)
            return self.local_attn(q, k, v, **kwargs)
        H = q.shape[self.scatter_idx]
        hpad = (-H) % sp  # uneven heads: zero-pad to the next sp multiple
        if hpad:
            widths = [(0, 0)] * q.ndim
            widths[self.scatter_idx] = (0, hpad)
            q = jnp.pad(q, widths)
        # seq-sharded [B, S/sp, Hp, D] → head-sharded [B, S, Hp/sp, D]
        q = single_all_to_all(q, self.scatter_idx, self.gather_idx, a)
        k = self._kv_reshard(k, sp, H)
        v = self._kv_reshard(v, sp, H)
        out = self.local_attn(q, k, v, **kwargs)
        # back: head-sharded → seq-sharded (+ drop the padding heads)
        out = single_all_to_all(out, self.gather_idx, self.scatter_idx, a)
        if hpad:
            out = jax.lax.slice_in_dim(out, 0, H, axis=self.scatter_idx)
        return out

    def _kv_reshard(self, t, sp, n_q_heads):
        """KV reshard with GQA alignment (reference ``uneven_heads_all2all``,
        ``sequence/layer.py:72``).  Returns kv with exactly the head count
        the local (padded) q block has, so ``local_attn`` always sees
        matched heads:

        * both head counts divisible by sp → all-to-all like Q, then local
          group-repeat (contiguous head blocks keep q↔kv group alignment);
        * else → duplicate-then-route: build, from the local seq chunk, the
          [sp × qh_local] slot layout where slot (r, j) holds the kv head
          rank r's j-th q head attends to, and ONE all-to-all scatters the
          slot axis / gathers the sequence.  No rank ever materializes the
          full [B, S, n_kv, D] kv (the sequence-all-gather fallback this
          replaces); wire+memory cost equals the q path's."""
        n_kv = t.shape[self.scatter_idx]
        self._check_gqa_heads(n_q_heads, n_kv)
        group = max(1, n_q_heads // n_kv)  # q heads per kv head
        if n_kv % sp == 0 and n_q_heads % sp == 0:
            t = single_all_to_all(t, self.scatter_idx, self.gather_idx,
                                  self.sp_axis)
            if n_kv != n_q_heads:
                t = jnp.repeat(t, group, axis=self.scatter_idx)
            return t
        qh_local = -(-n_q_heads // sp)  # padded q heads per rank
        # slot (r, j) ← kv head of global (padded) q head r*qh_local + j;
        # padding q heads clamp to the last real head (their output is
        # sliced away).  Pure-Python index table → static gather.
        g = np.arange(sp * qh_local)
        kv_idx = np.minimum(g, n_q_heads - 1) // group
        t = jnp.take(t, jnp.asarray(kv_idx), axis=self.scatter_idx)
        return single_all_to_all(t, self.scatter_idx, self.gather_idx,
                                 self.sp_axis)

    # ---- eager/GSPMD form: global arrays, seq dim sp-sharded ---------------
    def __call__(self, query, key, value, mesh=None, **kwargs):
        if mesh is None:
            # inside another partial-manual region (e.g. the fused pipeline's
            # {pp,dp,ep}-manual program) the inner shard_map must target the
            # CONTEXT abstract mesh, not the concrete global mesh — enables
            # pp×sp (BASELINE config-5 shape)
            cur = jax.sharding.get_abstract_mesh()
            mesh = cur if getattr(cur, "manual_axes", ()) \
                else groups.get_global_mesh()
        a = self.sp_axis
        if mesh.shape.get(a, 1) == 1:
            key, value = self._align_gqa_local(query, key, value)
            return self.local_attn(query, key, value, **kwargs)
        key_ = (mesh, tuple(sorted(kwargs.items())))
        cache = getattr(self, "_jit_cache", None)
        if cache is None:
            cache = {}
            self._jit_cache = cache
        if key_ not in cache:
            # PARTIAL-manual: only "sp" is a manual axis (the a2a lives on
            # it); batch/head dims keep whatever dp/tp sharding GSPMD gave
            # the operands.  A full-manual region with P(None, a) specs
            # would replicate the batch into every dp group and the heads
            # into every tp rank — correct numerics, dp·tp× dead compute.
            spec = P(None, a)  # [B, S(sp), ...]; trailing dims auto

            def f(q, k, v):
                return self.attend_local(q, k, v, **kwargs)

            cache[key_] = jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                axis_names=frozenset({a}), check_vma=False))
        return cache[key_](query, key, value)


class UlyssesAttention(DistributedAttention):
    """Name parity with user-facing import in reference examples."""
