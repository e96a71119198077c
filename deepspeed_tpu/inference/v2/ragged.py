"""Ragged state management (reference ``inference/v2/ragged/``):
``BlockedAllocator`` (block free-list, ``blocked_allocator.py``),
``BlockedKVCache`` (paged KV storage, ``kv_cache.py``),
``DSSequenceDescriptor`` + ``DSStateManager`` (``ragged_manager.py:19``).

TPU shape discipline: the cache is one buffer a layer for K and one for V —
``[num_blocks, block_size, Hkv, Dh]`` each — so that a step scatters into a
layer's pages in place and hands the buffer itself to the paged kernel; and
every sequence owns a row of a fixed-width block table ``[max_seqs,
max_blocks_per_seq]``; the jitted ragged forward only ever sees static shapes
(the "ragged" part is metadata).

Two kinds of state in one cache (``attention_class == "eva"``, EvaByte): a
sequence keeps the exact K/V of its CURRENT window of ``window_size`` tokens
and, of every window before it, one summary (key, value) row a chunk of
``chunk_size`` tokens.  Its block-table row is ``[summary blocks of the
closed windows | blocks of the current window | ... | the summary block in
the making]``: a step addresses and masks by the position inside that row,
so the summaries of the current window (its last columns) are beyond every
query's position until the window closes.  How many blocks ``n`` tokens
hold is ``BlockedKVCache.blocks_for`` for every architecture; capacity,
deferral, the row's width and the scheduler's admission claims all ask it.

A LATENT cache (``latent_dim``: a model with multi-head latent attention
states ``kv_latent_dim``) keeps ONE row a token a layer, the same for every
head, in one buffer a layer ``[num_blocks, block_size, latent_row]``: no K
buffer and no V buffer, no head axis.  Allocator, tables, ``blocks_for`` and
the claims do not know the difference.

HOW MANY entries the cache holds is the model's statement too
(``kv_cache_entries``; a model that does not say has one a layer): a layer
with two attentions keeps two, each its own buffer, addressed by the same
block table (a block is a run of tokens in EVERY entry, so the allocator, the
tables and the claims count blocks as before and a token's bytes are the sum
over the entries, ``bytes_by_kind``).  ``num_layers`` below is that count.

SEVERAL entries may live in ONE buffer (``entries_a_buffer``: a looped model,
``models/ouro.py``, states ``kv_entries_a_buffer``, the passes its stack runs a
token): entry ``t * L + l`` (pass ``t`` of layer ``l``, ``L`` buffers) is the
pages ``[t * num_blocks, (t + 1) * num_blocks)`` of buffer ``l``, so a step
that rolls its loop over the passes reaches pass ``t``'s entry by adding ``t
* num_blocks`` to the block table and threads ``L`` buffers, not ``T x L``.
A block is still a run of tokens in every entry: a token claims a row in each
of the ``T x L``, and allocator, tables and claims count blocks as before.

Entries of a SECOND KIND (``recurrent``: a model with state-space layers states
``recurrent_state``, ``models/jamba.py``): a ``"state"`` layer keeps no pages
but ``(conv_state [K - 1, slots, C], ssm_state [slots, S, C])``, a row a
SEQUENCE SLOT (``DSSequenceDescriptor.slot``; slot 0 is the garbage row that
padding writes to) and not a row a token, threaded and donated exactly as
pages are; in the cache's type, but where the model states a leaf's own
(``recurrent_state["dtypes"]``: ``models/qwen3_next.py`` holds a Gated
DeltaNet layer's matrix state ``[slots, Hv, 128, 128]`` in FLOAT32 beside
bfloat16 convolution rows; ``bytes_by_kind`` counts the leaves as they are).  Its bytes are fixed by ``max_seqs``; the
allocator, ``blocks_for`` and the claims count the pages of the ``"pages"``
layers, which every such layer holds alike.  Nothing is cleared on the host: a
run that starts at position 0 starts from zeros inside the step program, so a
freed slot's next owner and a preempted request's recomputation read nothing
of what the row held.  (The axes are the ones a TPU tiles without padding or
relayout: the channels fill the lanes, ``S`` or the slots the sublanes.)
"""

from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

import jax.numpy as jnp


def window_row_positions(positions, window_size, chunk_size):
    """Positions inside a window-plus-summary block-table row, ``[the closed
    windows' summaries | the current window]``, of tokens at ``positions``
    (numpy on the host, jax.numpy inside the step program): what the cache is
    addressed and the causal mask taken by."""
    return positions // window_size * (window_size // chunk_size) \
        + positions % window_size


class KVCacheExhausted(RuntimeError):
    """The block pool cannot satisfy an allocation (capacity, not a bug).

    Carries ``wanted_blocks`` / ``free_blocks`` so a serving scheduler can
    catch-and-preempt (``serving/scheduler.py``) while genuine programming
    errors keep surfacing as other exception types.  Subclasses
    ``RuntimeError`` so pre-existing ``except RuntimeError`` callers keep
    working."""

    def __init__(self, wanted_blocks, free_blocks, detail=""):
        self.wanted_blocks = int(wanted_blocks)
        self.free_blocks = int(free_blocks)
        msg = (f"KV cache exhausted: want {self.wanted_blocks} block(s), "
               f"{self.free_blocks} free")
        if detail:
            msg += f" — {detail}"
        super().__init__(msg)


class BlockedAllocator:
    """Free-list allocator over ``num_blocks`` KV blocks (reference
    ``blocked_allocator.py`` — the linked-list becomes a python set; the
    device never sees this object)."""

    def __init__(self, num_blocks):
        self.num_blocks = int(num_blocks)
        self._free = set(range(self.num_blocks))

    @property
    def free_blocks(self):
        return len(self._free)

    def allocate(self, n):
        if n > len(self._free):
            raise KVCacheExhausted(n, len(self._free))
        out = [self._free.pop() for _ in range(n)]
        return out

    def free(self, blocks):
        for b in blocks:
            if b in self._free:
                raise ValueError(f"double free of block {b}")
            self._free.add(b)


@dataclass
class DSSequenceDescriptor:
    """Host-side record of one tracked sequence (reference
    ``sequence_descriptor.py``)."""
    uid: int
    slot: int                       # row in the block table
    tokens: List[int] = field(default_factory=list)  # full token history
    seen_tokens: int = 0            # tokens already in the KV cache
    #: tokens that launched steps choose on the device and the host has not
    #: fetched: owed to ``tokens``, counted, their values unknown
    owed: int = 0
    blocks: List[int] = field(default_factory=list)   # every block held
    done: bool = False
    # window-plus-summary caches only: ``blocks`` by kind
    summary_blocks: List[int] = field(default_factory=list)  # closed windows
    window_blocks: List[int] = field(default_factory=list)   # current window
    making_blocks: List[int] = field(default_factory=list)   # its summaries

    @property
    def cur_length(self):
        return len(self.tokens)

    def pending(self):
        """Token ids not yet through the model (the known ones)."""
        return self.tokens[self.seen_tokens:]

    @property
    def n_pending(self):
        """How many tokens are not yet through the model: the known ones and
        the one a step in flight is choosing."""
        return len(self.tokens) + self.owed - self.seen_tokens


class BlockedKVCache:
    """Paged KV storage (reference ``kv_cache.py``) + the allocator.

    ``layers`` is the device-side cache as the step programs thread it: one
    entry a layer, ``(k_pages, v_pages)``, each ``[num_blocks, block_size,
    Hkv, Dh]`` and a buffer of its own, so a donated step updates it in place
    (no layer is ever taken out of, or written back into, a larger array).

    With ``kv_dtype`` set ("int8"/"fp8" — ``kv_codec.py``), the pages hold
    the narrow storage dtype and a layer's entry is ``(k_pages, v_pages,
    k_scales, v_scales)``: one f32 per (block, position, kv-head) row,
    ``[num_blocks, block_size, Hkv]``.

    With ``latent_dim`` set a layer's entry is ``(pages, )``, ONE buffer
    ``[num_blocks, block_size, latent_row]`` whose row holds the token's
    ``latent_dim`` values and zeros up to ``latent_row``, the next multiple
    of 128: a TPU array's minor dimension is tiled by 128 lanes, so the
    device pads a narrower row to that anyway, and the paged kernel moves
    whole tiles (docs/kernels.md); ``num_kv_heads``/``head_dim`` are not
    read."""

    @staticmethod
    def entries_of(model_config):
        """How many entries a model's cache holds (``num_layers`` below):
        what it states as ``kv_cache_entries``, one a layer where it does not
        say."""
        return int(getattr(model_config, "kv_cache_entries", 0)
                   or model_config.num_hidden_layers)

    def __init__(self, num_layers, num_blocks, block_size, num_kv_heads,
                 head_dim, dtype=jnp.bfloat16, kv_dtype=None, window_size=0,
                 chunk_size=0, latent_dim=0, recurrent=None, max_seqs=0,
                 entries_a_buffer=1):
        self.block_size = int(block_size)
        self.num_blocks = int(num_blocks)
        #: entries that share one buffer, pass-major (the module docstring)
        self.entries_a_buffer = int(entries_a_buffer or 1)
        if int(num_layers) % self.entries_a_buffer or (
                self.entries_a_buffer > 1 and (
                    kv_dtype is not None or window_size or latent_dim
                    or recurrent)):
            raise NotImplementedError(
                "entries_a_buffer divides the entries, and is not implemented "
                "beside kv_cache_dtype, a window-plus-summary layout, a "
                "latent cache or recurrent state")
        buffers = int(num_layers) // self.entries_a_buffer
        self.kv_dtype = kv_dtype
        # window-plus-summary layout (see the module docstring); 0: every
        # token keeps its K/V for the sequence's life
        self.window_size = int(window_size or 0)
        self.chunk_size = int(chunk_size or 0)
        self.summary_blocks = 0     # blocks of summaries a closed window leaves
        if self.window_size:
            w, c, bs = self.window_size, self.chunk_size, self.block_size
            if c <= 0 or w % c or w % bs or bs % c or (w // c) % bs:
                raise ValueError(
                    f"window_size {w} / chunk_size {c} / block_size {bs}: a "
                    "window is whole chunks and whole blocks, a block whole "
                    "chunks, and a window's summaries (window_size / "
                    "chunk_size rows) whole blocks")
            if kv_dtype is not None:
                raise NotImplementedError(
                    "kv_cache_dtype with a window-plus-summary cache")
            self.summary_blocks = w // c // bs
        self.latent_dim = int(latent_dim or 0)
        self.latent_row = -(-self.latent_dim // 128) * 128
        if self.latent_dim and (kv_dtype is not None or self.window_size):
            raise NotImplementedError(
                "a latent cache with kv_cache_dtype or a window-plus-summary "
                "layout")
        #: the kind of every layer's entry: "pages" (rows a token) or
        #: "state" (a row a sequence slot: the module docstring)
        self.kinds = tuple(recurrent["kinds"]) if recurrent \
            else ("pages", ) * buffers
        if recurrent and (kv_dtype is not None or self.window_size
                          or self.latent_dim):
            raise NotImplementedError(
                "recurrent state beside kv_cache_dtype, a window-plus-summary "
                "layout or a latent cache")
        if len(self.kinds) != buffers or (
                recurrent and int(max_seqs) < 2):
            raise ValueError("recurrent: a kind a layer, and max_seqs slots")
        if kv_dtype is None:
            self.dtype = jnp.dtype(dtype)
        else:
            from .kv_codec import storage_dtype
            self.dtype = jnp.dtype(storage_dtype(kv_dtype))
        #: tokens a row of a page: 2 for a bfloat16 multi-query cache, whose
        #: page is held ``[block_size / 2, 2, Dh]``; the paged kernels' module
        #: states the format and why (``page_row_tokens``)
        from ...ops.pallas.paged_attention import page_row_tokens
        self.token_pairs = 1 if (kv_dtype is not None or self.latent_dim) \
            else page_row_tokens(num_kv_heads, head_dim, self.dtype)
        if self.token_pairs == 2 and (self.window_size
                                      or self.block_size % 2):
            raise NotImplementedError(
                "a bfloat16 multi-query cache holds two tokens a row: an "
                "even block_size, and no window-plus-summary layout")
        shape = (self.entries_a_buffer * self.num_blocks,
                 self.block_size // self.token_pairs,
                 num_kv_heads * self.token_pairs, head_dim)
        #: every leaf a buffer of its own (never a view of a shared one).
        #: scale=1 for never-written positions keeps dequant a no-op on the
        #: zero payload (garbage block included)
        if self.latent_dim:
            self.layers = tuple(
                (jnp.zeros(shape[:2] + (self.latent_row, ), self.dtype), )
                for _ in range(int(num_layers)))
        else:
            pages = lambda: tuple(jnp.zeros(shape, self.dtype) for _ in "kv") \
                + tuple(jnp.ones(shape[:3], jnp.float32)
                        for _ in ("kv" if kv_dtype else ""))
            if recurrent:
                (taps, chans), ssm = recurrent["conv"], recurrent["ssm"]
                # a leaf's type is the model's to state (a matrix state a
                # head is held in float32); the cache's own where it does not
                types = recurrent.get("dtypes", {})
                leaf_type = lambda leaf: jnp.dtype(types.get(leaf)
                                                   or self.dtype)
            state = lambda: (
                jnp.zeros((taps, int(max_seqs), chans), leaf_type("conv")),
                jnp.zeros((int(max_seqs), ) + tuple(ssm), leaf_type("ssm")))
            self.layers = tuple(pages() if kind == "pages" else state()
                                for kind in self.kinds)
        self.allocator = BlockedAllocator(num_blocks)
        # block 0 is the garbage sink: padding tokens in the ragged buffer
        # scatter their K/V there (their slot-0 block-table row is all zeros)
        self.allocator._free.discard(0)

    @property
    def page_layers(self):
        """How many entries keep pages (every one, but in a cache with
        recurrent state; two a layer where a layer has two attentions): what
        one call's page count is multiplied by."""
        return self.kinds.count("pages") * self.entries_a_buffer

    def entry_pages(self, layers, entry):
        """Entry ``entry``'s own pages ``[num_blocks, ...]`` of every leaf,
        out of ``layers`` (this cache's buffers as a step returned them):
        where several entries share a buffer, its slice of that buffer."""
        buffers = len(layers)
        first = entry // buffers * self.num_blocks
        return tuple(leaf[first:first + self.num_blocks]
                     for leaf in layers[entry % buffers])

    def bytes_by_kind(self):
        """``(bytes a token over the "pages" layers, bytes a sequence over
        the "state" layers)`` as the device holds them."""
        size = lambda kind: sum(
            leaf.nbytes for entry, k in zip(self.layers, self.kinds)
            if k == kind for leaf in entry)
        slots = [e[1].shape[0] for e, k in zip(self.layers, self.kinds)
                 if k == "state"]
        return (size("pages") // (self.num_blocks * self.block_size),
                size("state") // slots[0] if slots else 0)

    def blocks_for(self, num_tokens):
        """Blocks a sequence of ``num_tokens`` holds — the ONE function that
        capacity, deferral, the block-table row's width and the scheduler's
        claims go through.  Window-plus-summary: the summaries of the closed
        windows, the summaries of the current window in the making, and the
        current window's own blocks (a window that is full stays open until
        the first token after it arrives)."""
        if not self.window_size or num_tokens <= 0:
            return -(-num_tokens // self.block_size)
        closed = (num_tokens - 1) // self.window_size
        inside = num_tokens - closed * self.window_size
        return (closed + 1) * self.summary_blocks \
            + -(-inside // self.block_size)

    def peak_blocks_for(self, num_tokens, start=0):
        """The most blocks the sequence holds on its way from ``start`` to
        ``num_tokens`` tokens: what admission has to set aside.  Without a
        window that is ``blocks_for(num_tokens)``; with one, a sequence holds
        most when a window is full."""
        peak = self.blocks_for(num_tokens)
        if self.window_size:
            full = num_tokens // self.window_size * self.window_size
            if full >= max(start, 1):
                peak = max(peak, self.blocks_for(full))
        return peak

    def row_width(self, max_context):
        """Columns of a sequence's block-table row."""
        if not self.window_size:
            return -(-int(max_context) // self.block_size)
        closed = (int(max_context) - 1) // self.window_size
        return (closed + 1) * self.summary_blocks \
            + self.window_size // self.block_size

    def run_room(self, seen_tokens):
        """Tokens that may follow ``seen_tokens`` in ONE step (None: no
        limit): a step does not straddle a window's end, because the rows on
        both sides of it would need different block-table rows."""
        if not self.window_size:
            return None
        return self.window_size - seen_tokens % self.window_size


class DSStateManager:
    """Tracks sequences ↔ cache blocks (reference ``ragged_manager.py:19``:
    get_or_create_sequence, flush)."""

    def __init__(self, config, kv_cache: BlockedKVCache):
        self.config = config
        self.kv_cache = kv_cache
        self.max_seqs = int(config.max_ragged_sequence_count)
        self.max_blocks_per_seq = kv_cache.row_width(config.max_context)
        #: the longest context a sequence may reach (max_context, in blocks)
        self.max_tokens = -(-int(config.max_context) // kv_cache.block_size) \
            * kv_cache.block_size
        self._seqs: Dict[int, DSSequenceDescriptor] = {}
        # slot 0 is reserved for padding tokens (its block-table row stays
        # zero, pointing at the garbage block)
        self._free_slots = list(range(1, self.max_seqs))
        # host-side mirror of the device block table
        self.block_table = np.zeros((self.max_seqs, self.max_blocks_per_seq),
                                    dtype=np.int32)

    # ------------------------------------------------------------- tracking
    @property
    def tracked_sequences(self):
        return dict(self._seqs)

    def get_sequence(self, uid) -> Optional[DSSequenceDescriptor]:
        return self._seqs.get(uid)

    def get_or_create_sequence(self, uid) -> DSSequenceDescriptor:
        seq = self._seqs.get(uid)
        if seq is not None:
            return seq
        if not self._free_slots:
            raise RuntimeError("max_ragged_sequence_count exceeded")
        seq = DSSequenceDescriptor(uid=uid, slot=self._free_slots.pop(0))
        self._seqs[uid] = seq
        return seq

    def _check_context(self, seq, total_tokens):
        if total_tokens > self.max_tokens:
            raise RuntimeError(
                f"sequence {seq.uid} exceeds max_context "
                f"({total_tokens} tokens > {self.max_tokens})")

    def _take(self, seq, column, kind=None):
        blk = self.kv_cache.allocator.allocate(1)[0]
        self.block_table[seq.slot, column] = blk
        seq.blocks.append(blk)
        if kind is not None:
            kind.append(blk)

    def ensure_capacity(self, seq: DSSequenceDescriptor, total_tokens):
        """Grow the sequence's block list to hold ``total_tokens``."""
        self._check_context(seq, total_tokens)
        kv = self.kv_cache
        if not kv.window_size:
            need = kv.blocks_for(total_tokens)
            while len(seq.blocks) < need:
                self._take(seq, len(seq.blocks))
            return
        if total_tokens <= 0:
            return
        closed = (total_tokens - 1) // kv.window_size
        if seq.seen_tokens < closed * kv.window_size:
            raise RuntimeError(
                f"sequence {seq.uid}: a step may not straddle a window's end "
                f"({seq.seen_tokens} tokens seen, {total_tokens} wanted, "
                f"window {kv.window_size})")
        if closed * kv.summary_blocks > len(seq.summary_blocks):
            self._close_window(seq)
        base = len(seq.summary_blocks)
        while len(seq.making_blocks) < kv.summary_blocks:
            self._take(seq, self.max_blocks_per_seq - kv.summary_blocks
                       + len(seq.making_blocks), seq.making_blocks)
        inside = total_tokens - closed * kv.window_size
        while len(seq.window_blocks) < -(-inside // kv.block_size):
            self._take(seq, base + len(seq.window_blocks), seq.window_blocks)

    def _close_window(self, seq):
        """The window is full and the next token is about to arrive: its
        exact K/V go back to the pool, its summaries become readable."""
        self.kv_cache.allocator.free(seq.window_blocks)
        seq.summary_blocks += seq.making_blocks
        seq.window_blocks, seq.making_blocks = [], []
        seq.blocks = list(seq.summary_blocks)
        row = self.block_table[seq.slot]
        row[:] = 0
        row[:len(seq.blocks)] = seq.blocks

    def schedulable_tokens(self, seq: DSSequenceDescriptor, want_total):
        """How many of the tokens up to ``want_total`` can be scheduled in
        ONE step with the blocks this sequence holds plus the allocator's
        free pool (the reference scheduler's can-schedule check — a sequence
        the pool cannot grow defers instead of crashing the engine step),
        and, with a window, without passing the window's end.  Raises only
        for the max_context user error."""
        self._check_context(seq, want_total)
        kv = self.kv_cache
        if not kv.window_size:
            affordable = ((len(seq.blocks) + self.free_blocks)
                          * kv.block_size)
            return max(0, min(want_total, affordable) - seq.seen_tokens)
        seen = seq.seen_tokens
        want_total = min(want_total, seen + kv.run_room(seen))
        if want_total <= seen:
            return 0
        closed = (want_total - 1) // kv.window_size
        if closed * kv.summary_blocks > len(seq.summary_blocks):
            # the close gives the window's blocks back, and the next window
            # starts with the blocks of its summaries
            pool = self.free_blocks + len(seq.window_blocks) \
                - kv.summary_blocks
            held = 0
        else:
            pool = self.free_blocks \
                - (kv.summary_blocks - len(seq.making_blocks))
            held = len(seq.window_blocks)
        affordable = closed * kv.window_size + (held + pool) * kv.block_size
        return max(0, min(want_total, affordable) - seen)

    def flush_sequence(self, uid):
        """Release a sequence (reference ``flush``)."""
        seq = self._seqs.pop(uid, None)
        if seq is None:
            return
        if seq.blocks:
            self.kv_cache.allocator.free(seq.blocks)
        self.block_table[seq.slot, :] = 0
        self._free_slots.append(seq.slot)

    @property
    def free_blocks(self):
        return self.kv_cache.allocator.free_blocks
