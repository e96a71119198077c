"""InferenceEngineV2 — FastGen-style continuous batching (reference
``inference/v2/engine_v2.py:30``: ``put``/``query``/``flush`` scheduling API
over a ragged batch + blocked KV cache).

Each engine iteration packs a **fixed token budget** with a mix of decode
tokens (one per running sequence) and prefill chunks, runs ONE jitted ragged
step (``ragged_forward.py``), and samples next tokens for every sequence
whose pending tokens were fully consumed.  Prefills longer than the budget
stream across iterations automatically (chunked prefill).

Differences from the reference, by TPU design:
  * scheduling quantum = token budget (static shapes for XLA), not CUDA-graph
    atoms;
  * ``schedule_step`` is synchronous (launch the step, fetch its tokens);
    its two halves are ``launch_step`` and ``collect_step``, and a serving
    loop (``serving/scheduler.py``, the MII analog) launches the NEXT step
    between them, so that the device holds a queued program when the
    running one ends.  A row whose id the host has not fetched yet (the
    token the step in flight chooses) is a count on the host,
    ``seq.owed``, and takes its id on the device (``_take_chosen``).
"""

import dataclasses
import functools

import numpy as np

import jax
import jax.numpy as jnp

from ... import telemetry as _telemetry
from ...ops.pallas.paged_attention import (chunk_page_loads,
                                           kernel_page_loads, latent_min_rows,
                                           seen_keys)
from ...telemetry import names as _names
from ...utils.logging import logger
from .config_v2 import RaggedInferenceEngineConfig
from .kv_codec import resolve_kv_dtype
from .ragged import (BlockedKVCache, DSStateManager, KVCacheExhausted,
                     window_row_positions)
from .ragged_forward import RAGGED_FORWARDS


@jax.jit
def _tokens_and_counts(logits, counts):
    """A greedy step's tokens with the device's counts behind them (one
    array, one transfer; no counts for a model that makes none), and the
    tokens alone for the step after it."""
    toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jnp.concatenate([toks, counts]), toks


@functools.partial(jax.jit, static_argnames=("k", "n"))
def _burst_last_tokens(toks_out, *, k, n):
    """The ``[n]`` tokens of a burst's last iteration, of its output as the
    host fetches it (``[k, n]``, or flat with the device's counts behind)."""
    return toks_out.reshape(-1)[(k - 1) * n:k * n]


@jax.jit
def _take_chosen(toks, take, chosen):
    """The ids of a batch whose newest tokens the host has not seen: row
    ``i`` with ``take[i] > 0`` gets the token the step before chose for slot
    ``take[i]`` (slot 0 is no sequence's)."""
    return jnp.where(take > 0, chosen[take], toks)


@dataclasses.dataclass
class LaunchedStep:
    """A step the device has been given and whose tokens the host has not
    fetched: what ``launch_step`` / ``launch_burst`` return and
    ``collect_step`` takes."""
    seqs: list            # the sequences whose next token(s) it chooses
    fetch: object         # the device array ``collect_step`` transfers
    counts: dict          # its ``last_step_counts``
    burst_k: int = 0      # 0: a ragged step
    #: the engine's count of its launches when this one was made (from 1,
    #: ragged steps and bursts in one sequence): the ``launch`` of every
    #: span of the step's life (``telemetry/names.py``)
    index: int = 0
    device_counts: object = None   # the device's counts, where not in fetch
    #: how many launches' device counts the fetch brings: this step's and
    #: those of the steps before it that fetched nothing (0: none to bring)
    covers: int = 0
    #: the host's sampling options (then ``fetch`` holds logits rows, and the
    #: tokens are not known on the device: nothing may run ahead of it)
    sample: tuple = None


class InferenceEngineV2:

    def __init__(self, model, params=None, config=None):
        if isinstance(model, tuple):
            model, params = model
        if config is None:
            config = RaggedInferenceEngineConfig()
        elif isinstance(config, dict):
            config = RaggedInferenceEngineConfig(**config)
        self._config = config
        self.module = model
        cfg = model.config
        self.model_config = cfg
        name = type(model).__name__
        if name not in RAGGED_FORWARDS:
            raise ValueError(
                f"no ragged forward registered for {name} "
                f"(have: {list(RAGGED_FORWARDS)})")
        self._step_fn = RAGGED_FORWARDS[name]
        # what this model's step counts on the device (its third output),
        # and the counts of the steps since the last fetch: they stay on the
        # device until tokens that a request waits for carry them back
        self._device_counts = getattr(self._step_fn, "step_counts", ())
        self._no_counts = np.zeros(len(self._device_counts), np.int32)
        self._counts_owed = self._no_counts
        self._launches_owed = 0    # launches whose counts _counts_owed holds
        #: engine steps launched so far, ragged steps and bursts alike
        self.launches = 0
        #: the tokens the newest launched step chose, one a slot, on the
        #: device (None: the host chooses, or the step finished no sequence)
        self._chosen = None
        self._uncollected = 0       # launched steps not yet collected
        if params is None:
            raise ValueError("InferenceEngineV2 needs params")
        self.params = jax.tree_util.tree_map(jnp.asarray, params)

        # ---- tensor parallelism (reference inference_transformer_base
        # sharding + config tensor_parallel.tp_size): params shard via the
        # AutoTP rules, the KV cache shards over kv heads, and GSPMD
        # partitions the jitted step.  The Pallas kernels are single-device
        # programs, so tp>1 routes attention through the partitionable XLA
        # path (per-kv-head parallel).
        tp = int(getattr(config.tensor_parallel, "tp_size", 1) or 1)
        self._tp = tp
        self._tp_mesh = None
        # quantized paged-KV mode (kv_codec.py): the cache stores int8/fp8
        # rows + per-token f32 scales; the ragged step dequantizes on read.
        # Unset (None) keeps today's fp cache and exactly today's programs.
        self._kv_dtype = resolve_kv_dtype(
            getattr(config, "kv_cache_dtype", None))
        # weight-only quantized serving (reference quantization_mode):
        # resident weights in int8/int4 wire format, dequantized INSIDE the
        # jitted ragged step (and inside decode bursts — the wrapper is
        # traced by the burst program)
        from ..quant_serving import resolve_mode
        self._quant_bits = resolve_mode(
            getattr(config, "quantization_mode", None))
        self._quant_meta = {}
        if self._quant_bits is not None and tp > 1:
            raise NotImplementedError(
                "quantization_mode does not compose with tensor "
                "parallelism yet (quant grouping is laid out pre-shard)")
        if self._quant_bits is not None:
            from ..quant_serving import quantize_tree
            self.params, self._quant_meta = quantize_tree(
                self.params, self._quant_bits)
            base_step = self._step_fn
            meta, dt = self._quant_meta, jnp.dtype(config.dtype)

            def dq_step(params, *a, **kw):
                from ..quant_serving import dequantize_tree
                return base_step(dequantize_tree(params, meta, dt), *a,
                                 **kw)

            dq_step.__name__ = dq_step.__qualname__ = \
                _names.PROGRAM_RAGGED_STEP + "dequant"

            # jit the wrapper with the SAME statics AND the kv-cache
            # donation as the registered step (the inner jit's donation is
            # ignored once inlined — dropping it would double peak KV HBM);
            # decode_burst traces the wrapper inside its own program
            self._step_fn = jax.jit(
                dq_step, static_argnames=("cfg", "block_size", "use_kernel",
                                          "kv_dtype"),
                donate_argnums=(1, ))
        if tp > 1:
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
            devs = jax.devices()
            n_kv = cfg.num_key_value_heads
            n_q = getattr(cfg, "num_attention_heads", n_kv)
            # GQA with fewer kv heads than tp ranks: REPLICATE kv (cache +
            # k/v projections — the reference's kernel injection replicates
            # kv heads the same way for tp > n_kv); q/o still shard.
            kv_replicated = (n_kv % tp != 0 and tp % n_kv == 0
                             and n_q % tp == 0)
            if len(devs) % tp or (n_kv % tp and not kv_replicated):
                raise ValueError(
                    f"tp_size={tp} must divide the device count "
                    f"({len(devs)}) and either num_key_value_heads "
                    f"({n_kv}) or — for replicated-kv GQA — be a "
                    f"multiple of it with num_attention_heads ({n_q}) "
                    "divisible by tp")
            self._tp_mesh = Mesh(np.array(devs[:tp]), ("tp", ))
            from ...module_inject import shard_params_for_tp
            rules = None
            import sys as _sys
            mod = _sys.modules.get(type(model).__module__)
            if hasattr(mod, "tp_rules"):
                # shard_params_for_tp restricts specs to the mesh's axes
                # (drops 'zero'/'ep' etc. training pseudo-axes)
                rules = mod.tp_rules(cfg)
            if kv_replicated and rules is not None:
                # replication is the INTENDED layout here — override the
                # k/v rules explicitly rather than riding the divisibility
                # fallback (which warns per layer as if misconfigured)
                rules = dict(rules)
                for key in list(rules):
                    if "k_proj" in key or "v_proj" in key:
                        rules[key] = P()
            self.params = shard_params_for_tp(self.params, self._tp_mesh,
                                              rules=rules)
            # kv cache: shard over kv heads when they divide tp, else the
            # replicated-kv GQA mode (k/v proj leaves auto-replicate in
            # shard_params_for_tp via the divisibility fallback)
            self._kv_sharding = NamedSharding(
                self._tp_mesh,
                P() if kv_replicated else P(None, None, "tp", None))
            # quantized KV × tp (ROADMAP serving follow-on (b)): the
            # per-(token, head) f32 scales shard WITH the
            # cache — their trailing dim IS the kv-head dim the cache
            # shards on, so each rank holds exactly the scales of its own
            # cache shard and the on-read dequant stays rank-local
            self._kv_scales_sharding = NamedSharding(
                self._tp_mesh,
                P() if kv_replicated else P(None, None, "tp"))
        else:
            self._kv_sharding = None
            self._kv_scales_sharding = None

        sm = config.state_manager
        block_size = sm.block_size
        max_blocks_per_seq = -(-sm.max_context // block_size)
        num_blocks = sm.num_blocks
        if num_blocks is None:
            # enough for half the tracked sequences at full context (+1
            # garbage block) — the reference sizes from free memory
            num_blocks = 1 + max(sm.max_ragged_sequence_count,
                                 (sm.max_tracked_sequences *
                                  max_blocks_per_seq) // 2)
        # a window-plus-summary cache (EvaByte) is the MODEL's statement:
        # its config says how long a token's exact K/V live
        eva = getattr(cfg, "attention_class", None) == "eva"
        # so is a latent cache: one row a token a layer for all the heads
        latent = int(getattr(cfg, "kv_latent_dim", 0) or 0)
        if latent and tp > 1:
            raise NotImplementedError(
                "a latent cache has no head axis to shard over tp")
        # and so are entries of a second kind: a state-space layer keeps a
        # fixed row a sequence slot, not pages (ragged.py)
        recurrent = getattr(cfg, "recurrent_state", None)
        if recurrent and (tp > 1 or self._kv_dtype is not None):
            raise NotImplementedError(
                "a cache with recurrent state rows: kv_cache_dtype and "
                "tensor parallelism are not implemented for it (a state row "
                "has no head axis to shard, and no 8-bit form)")
        # and so is the COUNT of the cache's entries (a layer with two
        # attentions states two; a stack run T times a token T a layer, the
        # T of one layer in ONE buffer: ``kv_entries_a_buffer``)
        looped = int(getattr(cfg, "kv_entries_a_buffer", 1) or 1)
        if looped > 1 and tp > 1:
            raise NotImplementedError(
                "cache entries that share a buffer under tensor parallelism")
        self.kv_cache = BlockedKVCache(
            BlockedKVCache.entries_of(cfg), num_blocks, block_size,
            cfg.num_key_value_heads, getattr(cfg, "head_dim", 0),
            dtype=jnp.dtype(config.dtype), kv_dtype=self._kv_dtype,
            window_size=cfg.window_size if eva else 0,
            chunk_size=cfg.chunk_size if eva else 0, latent_dim=latent,
            recurrent=recurrent, max_seqs=sm.max_ragged_sequence_count,
            entries_a_buffer=looped)
        self.state_manager = DSStateManager(sm, self.kv_cache)
        self._budget = int(sm.max_ragged_batch_size)
        #: the fewest iterations a decode burst is launched for.  A turn of
        #: decode rows alone runs ``max_seqs`` rows an iteration as a burst
        #: and ``max_ragged_batch_size`` rows as a ragged step, over the same
        #: layers and the same weights read once: with no more slots than
        #: budget rows a burst of ONE iteration is the smaller program.  A
        #: configuration with more slots than budget rows keeps the ragged
        #: step for such a turn
        self.min_burst = 1 if self.state_manager.max_seqs <= self._budget \
            else 2
        #: what the newest engine step held (``schedule_step`` or a decode
        #: burst): the counts of ``names.SERVE_STEP_COUNTS`` that the batch
        #: builder knows; the scheduler's ``ds:serve.step`` span carries them
        self.last_step_counts = None
        # the device-side cache the step functions thread (and donate): one
        # (k_pages, v_pages[, k_scales, v_scales]) entry a layer
        if self._kv_sharding is not None:
            # replaces the replicated original — a full unsharded cache
            # pinned to device 0 would defeat the point of sharding it
            layer = (self._kv_sharding, ) * 2 \
                + (self._kv_scales_sharding, ) * 2
            self.kv_cache.layers = jax.device_put(
                self.kv_cache.layers,
                tuple(layer[:len(entry)] for entry in self.kv_cache.layers))
        self._kv = self.kv_cache.layers
        token_bytes, seq_bytes = self.kv_cache.bytes_by_kind()
        self._state_row_bytes = seq_bytes     # a constant of the engine
        self._rule_forms = bool(recurrent and recurrent.get("forms"))
        #: what a looped model's steps carry as ``cache_token_bytes``
        self._cache_token_bytes = token_bytes if looped > 1 else None
        n_pages = self.kv_cache.page_layers
        logger.info(
            f"InferenceEngineV2: budget={self._budget} blocks={num_blocks}"
            f"×{block_size} max_seqs={self.state_manager.max_seqs} "
            f"cache={token_bytes} B/token over "
            f"{n_pages} entries of pages"
            + (f" (latent rows of {latent})" if latent else "")
            + (f" ({looped} passes' entries in each of {len(self._kv)} "
               f"buffers; a block of {block_size} tokens is "
               f"{token_bytes * block_size} B)" if looped > 1 else "")
            + (f" + {seq_bytes} B/sequence over "
               f"{self.kv_cache.kinds.count('state')} "
               "entries of recurrent state" if seq_bytes else ""))

    # ------------------------------------------------------------- put/query
    def put(self, batch_uids, batch_tokens, do_schedule=False):
        """Queue prompt (or continuation) tokens (reference ``put`` :130 also
        runs the engine; here scheduling is explicit — pass
        ``do_schedule=True`` for reference-style behavior).

        An unknown uid starts a NEW sequence (the admission path).  A uid
        whose sequence already finished raises instead of silently
        resurrecting it: the done sequence's KV prefix and token history
        would leak into what the caller thinks is a fresh request — flush
        first (a flushed uid is unknown again and admits cleanly).  The
        check runs over the whole batch BEFORE any sequence mutates, so a
        rejected put leaves every sequence untouched (a retry after the
        flush must not double-extend the earlier uids)."""
        batch_uids = list(batch_uids)
        for uid in batch_uids:
            seq = self.state_manager.get_sequence(uid)
            if seq is not None and seq.done:
                raise ValueError(
                    f"put() on finished uid {uid!r} — flush it first "
                    "(continuing a done sequence would silently reuse its "
                    "KV prefix and token history)")
        for uid, toks in zip(batch_uids, batch_tokens):
            toks = [int(t) for t in np.asarray(toks).reshape(-1)]
            seq = self.state_manager.get_or_create_sequence(uid)
            seq.tokens.extend(toks)
        if do_schedule:
            return self.schedule_step()
        return {}

    def query(self, uid):
        """Latest state of a sequence (reference ``query``): returns
        (generated_token_count, last_token) once past the prompt."""
        seq = self.state_manager.get_sequence(uid)
        if seq is None:
            return None
        return {"uid": uid, "length": seq.cur_length,
                "seen": seq.seen_tokens, "done": seq.done,
                "tokens": list(seq.tokens)}

    def flush(self, uids):
        """Release sequences (reference ``flush`` :188)."""
        for uid in uids:
            self.state_manager.flush_sequence(uid)

    # -------------------------------------------------------------- schedule
    def _build_batch(self):
        """Pack the token budget: decode tokens first (latency), then
        prefill chunks (throughput) — the reference scheduler's policy.
        The rows of a sequence are contiguous, their positions consecutive;
        the rows past the last sequence's are dead (slot 0).

        A sequence whose newest token a step in flight is choosing
        (``seq.owed``) gets its row all the same: position and slot are
        counts, and ``take`` names the slot whose chosen token is the row's
        id (``_take_chosen`` fills it in on the device)."""
        T = self._budget
        sm = self.state_manager
        toks = np.zeros(T, np.int32)
        take_from = np.zeros(T, np.int32)   # > 0: the id is on the device
        pos = np.zeros(T, np.int32)
        slots = np.zeros(T, np.int32)  # slot 0 → garbage block
        finishing = []  # (seq, buffer index of its last scheduled token)
        placed = 0
        placed_decode = 0   # of placed: tokens of sequences in pure decode
        deferred = 0        # sequences the KV pool could not grow this step
        deferred_want = 0   # blocks those sequences needed and couldn't get

        cur = 0                        # the next free buffer row
        order = sorted(sm.tracked_sequences.values(),
                       key=lambda s: s.n_pending)
        for seq in order:
            if seq.done:
                continue
            n_pending = seq.n_pending
            if not n_pending:
                continue
            if cur >= T:
                break
            take = min(n_pending, T - cur)
            # KV-pool pressure: schedule only what the free blocks can hold
            # (the reference scheduler's deferral; a dry pool must not crash
            # the step — blocks free as other sequences flush)
            take = min(take, sm.schedulable_tokens(
                seq, seq.seen_tokens + take))
            if take <= 0:
                deferred += 1
                # blocks this sequence would need to advance ONE token —
                # the wanted_blocks figure a typed exhaustion reports
                deferred_want += max(
                    1, self.kv_cache.blocks_for(seq.seen_tokens + 1)
                    - len(seq.blocks))
                continue
            sm.ensure_capacity(seq, seq.seen_tokens + take)
            known = seq.pending()[:take]
            toks[cur:cur + len(known)] = known
            if take > len(known):       # the last of them is still owed
                take_from[cur + take - 1] = seq.slot
            pos[cur:cur + take] = np.arange(
                seq.seen_tokens, seq.seen_tokens + take)
            slots[cur:cur + take] = seq.slot
            if take == n_pending:
                finishing.append((seq, cur + take - 1))
            seq.seen_tokens += take
            placed += take
            if n_pending == 1:
                placed_decode += 1
            cur += take
        if placed == 0:
            if deferred:
                # nothing schedulable AND nothing in flight to free blocks:
                # deferring forever would spin — surface the exhaustion as
                # the typed capacity error so a serving scheduler can
                # catch-and-preempt (serving/scheduler.py)
                raise KVCacheExhausted(
                    deferred_want, sm.free_blocks,
                    detail=f"{deferred} sequence(s) deferred with 0 "
                    f"schedulable tokens and no other work in flight — "
                    f"raise state_manager.num_blocks, lower concurrency, "
                    f"preempt, or flush finished sequences")
            return None
        last_idx = np.zeros(sm.max_seqs, dtype=np.int32)
        for seq, idx in finishing:
            last_idx[seq.slot] = idx
        self.last_step_counts = {
            "kind": _names.KIND_RAGGED, "token_budget": T,
            "live_tokens": placed, "decode_tokens": placed_decode,
            "prefill_tokens": placed - placed_decode,
            **self._page_counts(pos, slots), "burst_k": 0}
        return toks, pos, slots, last_idx, finishing, take_from

    def _table_snapshot(self):
        """The block table as the launched program is to see it.  A COPY:
        the CPU backend may alias a numpy buffer instead of copying it, the
        step runs asynchronously, and the host rewrites rows of the table
        (a flush, a preemption, a window's close) while building the next
        step."""
        return jnp.asarray(self.state_manager.block_table.copy())

    def _count_cache(self, pos, slots):
        """Add to ``last_step_counts`` what the cache holds once this step
        (or burst: ``[k, rows]``) is scheduled — the tokens of the running
        sequences' contexts (``context_tokens``) and the blocks of
        ``block_size`` rows they hold (``held_blocks``) — and, of a
        window-plus-summary cache, the summary pages the step loads and the
        chunks and windows whose last token is among its rows.  Called once
        the program is launched: the counting then runs while the device
        works, not between two steps."""
        kv = self.kv_cache
        seqs = [s for s in self.state_manager.tracked_sequences.values()
                if not s.done]
        ends = pos[slots != 0] + 1 if kv.window_size else None
        self.last_step_counts.update(
            context_tokens=sum(s.seen_tokens for s in seqs),
            held_blocks=sum(len(s.blocks) for s in seqs),
            block_size=kv.block_size,
            summary_pages=self._summary_pages(pos, slots),
            chunks_closed=0 if ends is None else int(
                (ends % kv.chunk_size == 0).sum()),
            windows_closed=0 if ends is None else int(
                (ends % kv.window_size == 0).sum()))

    def _kernel_loads(self, pos, slots, row_pages=None, window=None):
        """``paged_attention.kernel_page_loads`` of one layer's call over
        the rows at positions ``pos`` (inside the block-table row) in slots
        ``slots``, for this engine's shapes and the layer's ``window``
        (default: the model's one ``sliding_window``)."""
        cfg, kv = self.model_config, self.kv_cache
        if window is None:
            window = int(getattr(cfg, "sliding_window", 0) or 0)
        latent = bool(kv.latent_dim)      # all the heads on one latent row
        return kernel_page_loads(
            slots, pos, heads=cfg.num_attention_heads,
            kv_heads=1 if latent else cfg.num_key_value_heads,
            head_dim=kv.latent_row if latent else cfg.head_dim,
            kv_dtype=kv.dtype, block_size=kv.block_size,
            maxb=self.state_manager.block_table.shape[1],
            window=window, row_pages=row_pages, latent=latent)

    def _summary_pages(self, pos, slots):
        """Of ``grid_pages``, the loads of summary blocks: every run (every
        row on the per-token kernel) loads all the summary pages of the
        windows its sequence has closed."""
        kv = self.kv_cache
        if not kv.window_size:
            return 0
        return self._kernel_loads(
            self._row_positions(pos), slots,
            row_pages=pos // kv.window_size * kv.summary_blocks)[1]

    def _row_positions(self, pos):
        """Positions inside the block-table row (``ragged.py``)."""
        kv = self.kv_cache
        if not kv.window_size:
            return pos
        return window_row_positions(pos, kv.window_size, kv.chunk_size)

    def _page_counts(self, pos, slots):
        """The page counts of a step's paged-attention calls over the rows at
        positions ``pos`` in slots ``slots`` (0: a dead row); ``[k, rows]``
        arrays are the ``k`` calls of a burst.  For a model whose layers read
        alike, of ONE layer's call: ``grid_pages``, ``short_pages``,
        ``block_pages``: the K/V page loads the kernel's loops perform and,
        of those, the loads whose item computes one slab of rows and not its
        tile, and the loads of items that take a block of pages through one
        softmax update (``paged_attention.kernel_page_loads``, beside the
        kernels it describes); ``row_pages``: the (row, page) pairs the live rows'
        contexts (their sliding windows) span — ``row_pages / grid_pages``
        is how many rows share one page load.  For a model that states a
        window a layer (``layer_windows``) the four are summed over ALL its
        layers' calls, and ``grid_pages_window`` / ``grid_pages_full`` are
        the loads of its window layers' and of its full layers' calls.
        A latent cache's are :meth:`_latent_page_counts`.  A looped model's
        step (several cache entries a buffer) also carries
        ``cache_token_bytes``: one token's bytes over ALL the entries."""
        if self.kv_cache.latent_dim:
            return self._latent_page_counts(pos, slots)
        windows = getattr(self.model_config, "layer_windows", None)
        if windows is None:
            counts = self._kind_page_counts(pos, slots, int(getattr(
                self.model_config, "sliding_window", 0) or 0))
            if "state" in self.kv_cache.kinds:
                counts.update(self._state_counts(pos, slots))
            if self._cache_token_bytes is not None:
                counts[_names.COUNT_CACHE_TOKEN_BYTES] = \
                    self._cache_token_bytes
            return counts
        total = dict.fromkeys(("grid_pages", "row_pages", "short_pages",
                               "block_pages", "grid_pages_window",
                               "grid_pages_full"), 0)
        for window in sorted(set(windows)):
            layers = windows.count(window)
            kind = self._kind_page_counts(pos, slots, window)
            for name, pages in kind.items():
                total[name] += layers * pages
            total["grid_pages_window" if window else "grid_pages_full"] += \
                layers * kind["grid_pages"]
        return total

    def _latent_page_counts(self, pos, slots):
        """``_page_counts`` of a latent cache, whose rows read it in one of
        two forms (``paged_attention.latent_row_forms``: the choice the step
        program makes from the same rows).  ``absorbed_rows`` /
        ``expanded_rows``: the live rows of ONE call that took each (every
        call reads alike).  The page counts and ``latent_keys`` are the
        ABSORBED kernel's alone: its loads of one call, and the (row, key)
        pairs its rows attend summed over the cache's ENTRIES (every
        attention's call: two a layer where the model states two).  The
        expanded kernel's are ``expanded_keys``, its rows' pairs over the
        entries, and ``expanded_pages``, the latent pages one call of it
        brings in.  For a model that states a window a layer
        (``layer_windows``) the page counts, the pairs and
        ``expanded_pages`` are summed over ALL its layers' calls, a window
        layer's rows seeing their window alone, and ``grid_pages_window`` /
        ``grid_pages_full`` are the absorbed kernel's loads by layer kind,
        as :meth:`_page_counts`'s."""
        cfg, kv = self.model_config, self.kv_cache
        # a burst's [k, rows] has one row a slot: its program holds the
        # absorbed kernel alone (``slot_rows``)
        min_rows = None if np.ndim(slots) == 2 else latent_min_rows(
            cfg, kv.latent_row, kv.dtype, slots.shape[-1])
        pos, slots = np.atleast_2d(pos), np.atleast_2d(slots)
        expanded, keys, pages = chunk_page_loads(
            slots, pos, heads=cfg.num_attention_heads,
            block_size=kv.block_size, min_rows=min_rows)
        absorbed = (slots != 0) & ~expanded
        rows = {_names.COUNT_ABSORBED_ROWS: int(absorbed.sum()),
                _names.COUNT_EXPANDED_ROWS: int(expanded.sum())}
        absorbed_slots = np.where(expanded, 0, slots)
        windows = getattr(cfg, "layer_windows", None)
        if windows is None:
            return {**self._kind_page_counts(pos, absorbed_slots, 0), **rows,
                    _names.COUNT_LATENT_KEYS: int(
                        seen_keys(pos)[absorbed].sum()) * kv.page_layers,
                    _names.COUNT_EXPANDED_KEYS: keys * kv.page_layers,
                    _names.COUNT_EXPANDED_PAGES: pages}
        total = dict.fromkeys(("grid_pages_window", "grid_pages_full"), 0)
        for window in sorted(set(windows)):
            layers = windows.count(window)
            kind = self._kind_page_counts(pos, absorbed_slots, window)
            _, keys, pages = chunk_page_loads(
                slots, pos, heads=cfg.num_attention_heads,
                block_size=kv.block_size, min_rows=min_rows, window=window)
            kind.update({_names.COUNT_LATENT_KEYS: int(
                             seen_keys(pos, window)[absorbed].sum()),
                         _names.COUNT_EXPANDED_KEYS: keys,
                         _names.COUNT_EXPANDED_PAGES: pages})
            for name, n in kind.items():
                total[name] = total.get(name, 0) + layers * n
            total["grid_pages_window" if window else "grid_pages_full"] += \
                layers * kind["grid_pages"]
        return {**total, **rows}

    def _state_counts(self, pos, slots):
        """What a step's recurrent layers do, summed over them: the state
        rows they read (a run that starts past position 0 starts from its
        slot's row) and write (every run leaves its final state), and the
        tokens their scans walk; ``state_row_bytes``: one sequence's row over
        all of them.  A RUN is the contiguous rows of one sequence; in a
        burst (``[k, rows]``) every live row of every iteration is one.  A
        model whose recurrent layers take a run in one of two FORMS by its
        length (``recurrent_state["forms"]``: a gated delta rule) also gets
        the tokens by form, ``rule_slot_tokens`` and ``rule_chunk_tokens``."""
        pos, slots = np.atleast_2d(pos), np.atleast_2d(slots)
        live = slots != 0
        start = live.copy()
        start[:, 1:] &= slots[:, 1:] != slots[:, :-1]
        layers = self.kv_cache.kinds.count("state")
        counts = {_names.COUNT_STATE_ROWS_READ:
                  int((start & (pos > 0)).sum()) * layers,
                  _names.COUNT_STATE_ROWS_WRITTEN: int(start.sum()) * layers,
                  _names.COUNT_SCAN_TOKENS: int(live.sum()) * layers,
                  _names.COUNT_STATE_ROW_BYTES: self._state_row_bytes}
        if self._rule_forms:
            # a run of one token is one update of its slot's row; a longer
            # run's tokens go through the chunk form
            end = live.copy()
            end[:, :-1] &= slots[:, :-1] != slots[:, 1:]
            single = int((start & end).sum())
            counts[_names.COUNT_RULE_SLOT_TOKENS] = single * layers
            counts[_names.COUNT_RULE_CHUNK_TOKENS] = \
                (int(live.sum()) - single) * layers
        return counts

    def _kind_page_counts(self, pos, slots, window):
        """``_page_counts`` of one call of a layer with this ``window``."""
        bs = self.kv_cache.block_size
        pos, slots = self._row_positions(np.atleast_2d(pos)), \
            np.atleast_2d(slots)
        grid, _, short, block = self._kernel_loads(pos, slots, window=window)
        first = np.maximum(pos - window + 1, 0) // bs if window else 0
        pages = np.where(slots != 0, pos // bs + 1 - first, 0)
        return {"grid_pages": grid, "row_pages": int(pages.sum()),
                "short_pages": short, "block_pages": block}

    @staticmethod
    def _sample_row(row, temperature, top_k, top_p, rng):
        """Host-side categorical sampling with the reference generate
        options (temperature / top-k / nucleus top-p)."""
        logits = row.astype(np.float64) / max(temperature, 1e-6)
        if top_k:
            kth = np.partition(logits, -int(top_k))[-int(top_k)]
            logits = np.where(logits < kth, -np.inf, logits)
        p = np.exp(logits - logits.max())
        p /= p.sum()
        if top_p and top_p < 1.0:
            order = np.argsort(-p)
            csum = np.cumsum(p[order])
            # smallest prefix whose mass reaches top_p (always ≥ 1 token)
            keep = csum - p[order] < top_p
            mask = np.zeros_like(p, dtype=bool)
            mask[order[keep]] = True
            p = np.where(mask, p, 0.0)
            p /= p.sum()
        return int(rng.choice(len(p), p=p))

    def schedule_step(self, do_sample=False, temperature=1.0, rng=None,
                      top_k=0, top_p=1.0):
        """One ragged iteration.  Returns {uid: sampled_next_token} for every
        sequence whose pending tokens were fully consumed this step.

        ``rng`` may be a ``np.random.Generator`` or a seed; either way the
        Generator is created once and advances across tokens and steps (a
        seed re-seeded per token would sample identical draws every time).

        ``launch_step`` and ``collect_step`` in one call: the serial
        spelling.  A loop that has other work for the host calls the two
        itself, and launches the next step between them
        (``serving/scheduler.py``).
        """
        step = self.launch_step(do_sample=do_sample, temperature=temperature,
                                rng=rng, top_k=top_k, top_p=top_p)
        return {} if step is None else self.collect_step(step)

    def launch_step(self, do_sample=False, temperature=1.0, rng=None,
                    top_k=0, top_p=1.0):
        """The first half of ``schedule_step``: build the batch, give the
        device its program and, BEHIND it and before anything else, the small
        array the host will fetch (a greedy step's tokens, with the device's
        counts; the finishing rows' logits when the host samples).  The
        device's queue is in order: enqueued here, that array is ready when
        this step ends, whatever is launched after it.  Returns a
        :class:`LaunchedStep`, or None when no sequence has a token to run.

        At most ONE step may be launched on top of an uncollected one, and a
        greedy one at that: its rows may need the tokens the step before it
        chose, which then are taken on the device (``_take_chosen``)."""
        self._check_depth()
        if do_sample:
            if isinstance(rng, np.random.Generator):
                self._rng = rng
                self._rng_seed = None
            elif (getattr(self, "_rng", None) is None
                  or (rng is not None and rng != getattr(self, "_rng_seed", None))):
                # create once per distinct seed; advances across tokens/steps
                self._rng = np.random.default_rng(rng)
                self._rng_seed = rng
        self.last_step_counts = None
        with _telemetry.scope(_names.SERVE_BUILD_BATCH):
            batch = self._build_batch()
        if batch is None:
            return None
        toks, pos, slots, last_idx, finishing, take_from = batch
        seqs = [seq for seq, _ in finishing]
        self.launches += 1
        step = LaunchedStep(seqs, None, self.last_step_counts,
                            index=self.launches)
        with _telemetry.scope(_names.SERVE_LAUNCH, launch=step.index,
                              kind=_names.KIND_RAGGED, burst_k=0):
            step_args = (self.params, self._kv,
                         self._ids_on_device(toks, take_from),
                         jnp.asarray(pos), jnp.asarray(slots),
                         self._table_snapshot(),
                         jnp.asarray(last_idx))
            step_kw = dict(cfg=self.model_config,
                           block_size=self.kv_cache.block_size,
                           use_kernel=self._tp == 1,
                           kv_dtype=self._kv_dtype)
            from ...profiling import cost_model
            if cost_model.capturing():
                # compiled-cost capture of the serving prefill/decode
                # program (one analysis compile, only while capture is
                # armed — docs/observability.md "MFU & HBM")
                cost_model.capture_jit_call(
                    "serve/ragged_step", self._step_fn, step_args, step_kw)
            logits, self._kv, *counted = self._step_fn(*step_args, **step_kw)
            if counted:            # no wait: an addition queued on the device
                self._counts_owed = self._counts_owed + counted[0]
                self._launches_owed += 1
            self._chosen = None
            if seqs and do_sample:
                # ONLY the finishing rows ([F, V]), not every slot
                step.fetch = logits[jnp.asarray([seq.slot for seq in seqs])]
                step.sample = (temperature, top_k, top_p)
                if counted:
                    step.device_counts = self._counts_owed
            elif seqs:
                # greedy: argmax on device, fetch one int per slot instead
                # of [max_seqs, V] logits (the per-step device→host tax on
                # a decode loop); the counts ride back with the tokens
                step.fetch, self._chosen = _tokens_and_counts(
                    logits, self._counts_owed)
            if seqs:
                step.covers, self._launches_owed = self._launches_owed, 0
                self._counts_owed = self._no_counts
        for seq in seqs:
            seq.owed += 1
        self._count_cache(pos, slots)
        self._uncollected += 1
        return step

    def collect_step(self, step):
        """The second half: fetch what a launched step (or burst) chose, the
        one place the host waits for the device.  Returns ``{uid: token}``
        (a burst: ``{uid: [tokens]}``, which it also appends to the
        sequences' histories, as ``burst_decode`` always did); the caller
        appends a ragged step's token to ``seq.tokens`` if decode goes on.
        A sequence flushed since the launch gets nothing.  Steps are
        collected in the order they were launched."""
        self._uncollected -= 1
        if step.fetch is None:
            return {}
        with _telemetry.scope(_names.SERVE_FETCH, launch=step.index) as span:
            fetched = np.asarray(step.fetch)
            if step.device_counts is not None:
                self._book_device_counts(np.asarray(step.device_counts), step,
                                         span)
            elif step.sample is None:
                fetched = self._book_device_counts(fetched, step, span)
        sm, k = self.state_manager, step.burst_k
        if k:
            fetched = fetched.reshape(k, sm.max_seqs)
        out = {}
        for i, seq in enumerate(step.seqs):
            if sm.get_sequence(seq.uid) is not seq:
                continue            # flushed while the step was in flight
            seq.owed -= k or 1
            if k:
                # k tokens scheduled on device: t0 (the pending one) + the
                # k-1 fed-back generations; the newest generation is left
                # pending for the next round
                out[seq.uid] = [int(t) for t in fetched[:, seq.slot]]
                seq.tokens.extend(out[seq.uid])
            elif step.sample is None:
                out[seq.uid] = int(fetched[seq.slot])
            else:
                out[seq.uid] = self._sample_row(fetched[i], *step.sample,
                                                self._rng)
        return out

    @property
    def launches_programs(self):
        """Whether a launch only enqueues: the step function is the compiled
        program the engine registered.  A Python callable put in its place
        (a hook that reads each step's output as it is made) pairs "the
        newest call" with "the tokens just returned", which holds in the
        serial order alone: a scheduler does not run ahead of one."""
        return isinstance(self._step_fn, jax.stages.Wrapped)

    def _check_depth(self):
        if self._uncollected > 1:
            raise RuntimeError(
                f"{self._uncollected} launched steps are uncollected: "
                "collect_step the older one before launching another (the "
                "device holds the tokens of ONE step back)")

    def _ids_on_device(self, toks, take_from):
        """A batch's token ids as the program takes them: the host's, with
        the rows ``take_from`` names filled in from what the step in flight
        chose (one small program queued before the step's)."""
        ids = jnp.asarray(toks)
        if take_from.any():
            ids = _take_chosen(ids, jnp.asarray(take_from), self._chosen)
        return ids

    def _book_device_counts(self, fetched, step, span):
        """Put the device's counts (the step program's ``step_counts``:
        ``step``'s and those of the ``step.covers - 1`` steps before it that
        fetched nothing), the last entries of ``fetched``, where they are
        read: on ``span``, the fetch of the step that counted them, with
        ``launches_covered``; and among ``last_step_counts``: the NEWEST
        launched step's, which is ``step``'s own unless another was launched
        on top of it (what the turn's ``ds:serve.step`` carries: they add).
        Returns what stands before them (the step's tokens, if any)."""
        cut = len(fetched) - len(self._device_counts)
        if self.last_step_counts is None:
            self.last_step_counts = step.counts
        if self._device_counts:
            counts = {name: int(count) for name, count in
                      zip(self._device_counts, fetched[cut:])}
            for name, count in counts.items():
                self.last_step_counts[name] = \
                    self.last_step_counts.get(name, 0) + count
            span.set(**{_names.COUNT_LAUNCHES_COVERED: step.covers},
                     **counts)
        return fetched[:cut]

    # ---------------------------------------------------------- decode burst
    def _decode_burst_step(self, active_uids, produced, max_new_tokens,
                           cap, sample=False, temperature=1.0, top_k=0,
                           top_p=1.0, seed=None):
        """Run up to ``cap`` greedy decode iterations on device in one
        program (``ragged_forward.decode_burst``).  Eligible only when
        EVERY active sequence has exactly one pending token (pure decode —
        a pending prefill chunk keeps the per-step scheduler).  Returns
        {uid: [k tokens]} or None if not eligible."""
        sm = self.state_manager
        seqs = []
        for uid in active_uids:
            seq = sm.get_sequence(uid)
            if seq.n_pending != 1:
                return None
            seqs.append(seq)
        if not seqs:
            return None
        k = min(cap, min(max_new_tokens - len(produced[s.uid])
                         for s in seqs))
        step = self._launch_burst(seqs, k, sample, temperature, top_k, top_p,
                                  seed)
        return None if step is None else self.collect_step(step)

    def burst_decode(self, uids=None, max_tokens=16, do_sample=False,
                     temperature=1.0, top_k=0, top_p=1.0, rng=None):
        """Public fused-decode entry for reference-style serving loops
        (``put``/``schedule_step`` callers): run up to ``max_tokens`` decode
        iterations on device in one program for the given sequences and
        return ``{uid: [tokens]}``.  Requires every targeted sequence to be
        in pure decode (exactly one pending token) — raises otherwise, so a
        scheduler can fall back to ``schedule_step``.  Sampling uses the
        device PRNG path (seed-deterministic; pass ``rng`` as a seed).
        ``launch_burst`` and ``collect_step`` in one call."""
        step = self.launch_burst(uids, max_tokens, do_sample, temperature,
                                 top_k, top_p, rng)
        return {} if step is None else self.collect_step(step)

    def launch_burst(self, uids=None, max_tokens=16, do_sample=False,
                     temperature=1.0, top_k=0, top_p=1.0, rng=None):
        """``burst_decode``'s first half, as ``launch_step`` is
        ``schedule_step``'s: returns a :class:`LaunchedStep`, or None when
        there is nothing to run or the pool cannot afford a burst.  A
        sequence's one pending token may be the one a step in flight is
        choosing."""
        self._check_depth()
        sm = self.state_manager
        if uids is None:
            uids = [s.uid for s in sm.tracked_sequences.values()
                    if not s.done]
        seqs = []
        for uid in uids:
            seq = sm.get_sequence(uid)
            if seq is None or seq.done:
                raise ValueError(f"uid {uid!r} is not an active sequence")
            if seq.n_pending != 1:
                raise ValueError(
                    f"uid {uid!r} is not in pure decode "
                    f"({seq.n_pending} pending tokens) — run "
                    "schedule_step until prefill drains")
            seqs.append(seq)
        k = int(max_tokens)
        cap = int(self._config.decode_burst or 0)
        if cap > 1:   # an explicit call may exceed a DISABLED config, not
            k = min(k, cap)   # a configured cap
        if not seqs:
            return None
        if do_sample and isinstance(rng, np.random.Generator):
            raise ValueError("burst_decode sampling needs a seed, not a "
                             "numpy Generator (device PRNG stream)")
        return self._launch_burst(seqs, k, do_sample, temperature, top_k,
                                  top_p, rng)

    def _burst_length(self, seqs, k):
        """The iterations a burst over ``seqs`` that was asked for ``k`` runs,
        0 where it is not launched: the ONE statement of that rule (the
        scheduler, ``burst_decode`` and ``generate``'s loop all come here).
        The ask, cut to the nearest window's end (a burst, like a step, ends
        there) and halved until the SHARED free pool affords ``k`` more
        positions a sequence; under ``min_burst`` the caller's ragged step
        runs (and defers where the pool is dry); else the floor power of two:
        each distinct static ``k`` is its own compiled program, so arbitrary
        values would compile one a remaining-token count; a power of two
        bounds the family to log2(cap) + 1."""
        sm = self.state_manager
        rooms = [sm.kv_cache.run_room(s.seen_tokens) for s in seqs]
        k = min([k] + [r for r in rooms if r is not None])
        while k > 0 and sum(
                max(0, sm.kv_cache.blocks_for(s.seen_tokens + k)
                    - len(s.blocks)) for s in seqs) > sm.free_blocks:
            k //= 2
        return 0 if k < self.min_burst else 1 << (k.bit_length() - 1)

    def _launch_burst(self, seqs, k, sample, temperature, top_k, top_p,
                      seed):
        sm = self.state_manager
        k = self._burst_length(seqs, k)
        if not k:
            # the pool cannot afford a burst right now (or it would be no
            # cheaper than a step): the caller's ragged step runs
            return None
        n = sm.max_seqs
        with _telemetry.scope(_names.SERVE_BUILD_BATCH):
            tok0 = np.zeros(n, np.int32)
            take_from = np.zeros(n, np.int32)
            pos0 = np.zeros(n, np.int32)
            act = np.zeros(n, bool)
            for seq in seqs:
                sm.ensure_capacity(seq, seq.seen_tokens + k)
                if seq.pending():
                    tok0[seq.slot] = seq.tokens[seq.seen_tokens]
                else:               # a step in flight is choosing it
                    take_from[seq.slot] = seq.slot
                pos0[seq.slot] = seq.seen_tokens
                act[seq.slot] = True
            # k iterations over max_seqs rows each, one token a live row:
            # row i is slot i (ragged_forward.decode_burst)
            pos_k = pos0[None, :] + np.arange(k)[:, None]
            slots_k = np.broadcast_to(np.where(act, np.arange(n), 0), (k, n))
            for seq in seqs:        # as the cache stands when the burst ends
                seq.seen_tokens += k
                seq.owed += k
            self.last_step_counts = {
                "kind": _names.KIND_BURST, "token_budget": n * k,
                "live_tokens": len(seqs) * k,
                "decode_tokens": len(seqs) * k, "prefill_tokens": 0,
                **self._page_counts(pos_k, slots_k), "burst_k": k}
        from .ragged_forward import decode_burst
        if sample:
            if getattr(self, "_burst_key", None) is None or \
                    seed != getattr(self, "_burst_seed", None):
                self._burst_key = jax.random.PRNGKey(seed or 0)
                self._burst_seed = seed
            self._burst_key, key = jax.random.split(self._burst_key)
        else:
            key = None
        self.launches += 1
        with _telemetry.scope(_names.SERVE_LAUNCH, launch=self.launches,
                              kind=_names.KIND_BURST, burst_k=k):
            burst_args = (self.params, self._kv,
                          self._ids_on_device(tok0, take_from),
                          jnp.asarray(pos0), jnp.asarray(act),
                          self._table_snapshot())
            burst_kw = dict(step_fn=self._step_fn, cfg=self.model_config,
                            block_size=self.kv_cache.block_size, k=k,
                            use_kernel=self._tp == 1, sample=sample,
                            key=key, temperature=float(temperature),
                            top_k=int(top_k), top_p=float(top_p),
                            kv_dtype=self._kv_dtype)
            covers = 0
            if self._device_counts:
                burst_kw["counts0"] = self._counts_owed
                self._counts_owed = self._no_counts
                covers, self._launches_owed = self._launches_owed + 1, 0
            from ...profiling import cost_model
            if cost_model.capturing():
                # k is static (a power of two), so the burst variants
                # are a bounded program family worth tabulating per k
                cost_model.capture_jit_call(
                    f"serve/decode_burst[k={k}]", decode_burst, burst_args,
                    burst_kw, meta={"k": int(k)})
            # ONE fetch for k×seqs tokens and the counts behind them; the
            # last iteration's tokens stay for the step after this one
            toks_out, self._kv = decode_burst(*burst_args, **burst_kw)
            self._chosen = _burst_last_tokens(toks_out, k=k, n=n)
        self._count_cache(pos_k, slots_k)
        self.burst_steps = getattr(self, "burst_steps", 0) + 1
        self._uncollected += 1
        return LaunchedStep(seqs, toks_out, self.last_step_counts, burst_k=k,
                            index=self.launches, covers=covers)

    # ------------------------------------------------------------- generate
    def _mark_done(self, uid, produced, tok, eos_token_id, max_new_tokens):
        """Record one generated token and apply the completion rule (EOS or
        the max-new-tokens budget) — the ONE place both the per-step loop
        and the burst path decide a sequence is finished.  Returns True when
        the sequence just completed (the caller drops it from its active
        set); overshoot past EOS inside a burst window is garbage the flush
        drops — ``produced`` truncates exactly."""
        produced[uid].append(tok)
        if (eos_token_id is not None and tok == eos_token_id) or \
                len(produced[uid]) >= max_new_tokens:
            self.state_manager.get_sequence(uid).done = True
            return True
        return False

    def generate(self, prompts, max_new_tokens=32, eos_token_id=None,
                 do_sample=False, temperature=1.0, top_k=0, top_p=1.0,
                 rng=None):
        """Convenience continuous-batching loop: all prompts in flight at
        once, chunked prefill + interleaved decode."""
        uids = list(range(len(prompts)))
        self.put(uids, prompts)
        produced = {u: [] for u in uids}
        active = set(uids)
        burst_cap = int(self._config.decode_burst or 0)
        burst_sample = False
        if do_sample:
            # fused sampling is opt-in AND needs a seed (not a Generator —
            # the device stream can't replicate numpy's)
            if (self._config.decode_burst_sampling
                    and not isinstance(rng, np.random.Generator)):
                burst_sample = True
            else:
                burst_cap = 0
        while active:
            if burst_cap > 1:
                burst = self._decode_burst_step(
                    active, produced, max_new_tokens, burst_cap,
                    sample=burst_sample, temperature=temperature,
                    top_k=top_k, top_p=top_p, seed=rng)
                if burst is not None:
                    for uid, toks in burst.items():
                        for tok in toks:
                            if self._mark_done(uid, produced, tok,
                                               eos_token_id,
                                               max_new_tokens):
                                active.discard(uid)
                                break
                    continue
            next_tokens = self.schedule_step(do_sample=do_sample,
                                             temperature=temperature,
                                             top_k=top_k, top_p=top_p,
                                             rng=rng)
            if not next_tokens:
                # a chunked prefill step consumes budget without finishing
                # any sequence — keep going while work remains
                if any(self.state_manager.get_sequence(u).pending()
                       for u in active):
                    continue
                break
            for uid, tok in next_tokens.items():
                if self._mark_done(uid, produced, tok, eos_token_id,
                                   max_new_tokens):
                    active.discard(uid)
                else:
                    # decode continues next step
                    self.state_manager.get_sequence(uid).tokens.append(tok)
        self.flush(uids)
        return [produced[u] for u in uids]
