"""Jamba on the serving path at tiny size on the CPU: ``InferenceEngineV2``
(chunked prefill, decode through the cache, several sequences interleaved in
one ragged buffer, bursts) against the plain reference's full forward
(``perfbench/reference/jamba.py``) on LOGITS, with seeded weights; the cache's
two kinds of entry; and what a recurrent state row must survive: a chunk's
end, a neighbour, a freed slot's next owner, a preemption.

``A`` and ``dt`` are drawn AS PUBLISHED here (``A = -(1 .. S)`` by state index,
``softplus(bias)`` log-uniform in 0.001 .. 0.1, a small ``dt_proj``), so that
a state remembers for hundreds of tokens and a fault in carrying it shows."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2 import ragged_forward as rf
from deepspeed_tpu.inference.v2.ragged import BlockedKVCache
from deepspeed_tpu.models import jamba
from deepspeed_tpu.serving import build_serving_engine

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)
from perfbench.reference import jamba as reference  # noqa: E402

CFG = jamba.jamba_tiny()
SIZES = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
             num_attention_heads=4, num_key_value_heads=1,
             attn_layer_period=6, attn_layer_offset=2, mamba_d_state=16,
             mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=8,
             rms_norm_eps=1e-6, num_hidden_layers=6)


def as_published(params, seed=0):
    """``A`` and ``dt`` as the published initialisation draws them."""
    rng = np.random.default_rng(seed)
    S, C = CFG.mamba_d_state, CFG.d_inner
    params = jax.tree_util.tree_map(lambda x: x, params)
    for name, lp in params.items():
        if "mamba" not in lp:
            continue
        mp = lp["mamba"]
        mp["A_log"] = jnp.asarray(np.log(np.broadcast_to(
            np.arange(1, S + 1, dtype=np.float32)[:, None], (S, C))
        ).reshape(1, S * C))
        dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (1, C)))
        mp["dt_proj"] = {"bias": jnp.asarray(np.log(np.expm1(dt)),
                                             jnp.float32),
                         "kernel": mp["dt_proj"]["kernel"] * 0.1}
    return params


@pytest.fixture(scope="module")
def params():
    model = jamba.JambaModel(CFG)
    p = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    p = jax.tree_util.tree_map(lambda x: x, p["params"])
    # a table of small rows (the benchmark's generator draws it so): the
    # logits are the layers' to decide, not the input token's
    p["embed_tokens"]["weight"] = p["embed_tokens"]["weight"] * 0.1
    return as_published(p)


def engine(params, dtype="float32", budget=16, burst=4, blocks=64, seqs=5,
           cfg=CFG):
    return InferenceEngineV2(jamba.JambaModel(cfg), params=params, config={
        "dtype": dtype, "decode_burst": burst, "state_manager": {
            "max_tracked_sequences": 8, "max_ragged_sequence_count": seqs,
            "max_context": 160, "block_size": 8, "num_blocks": blocks,
            "max_ragged_batch_size": budget}})


def prompts_of(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, size=n).tolist() for n in lengths]


def worst_gap(params, prompts, produced):
    """The benchmark's measure: (largest reference logit - reference logit
    of the engine's token) / std, the worst over the generated positions of
    every request; and the share of positions where the engine's token is the
    reference's argmax."""
    gaps, hits, n = [], 0, 0
    for p, toks in zip(prompts, produced):
        ids = np.asarray(p + toks[:-1], np.int32)
        at = np.arange(len(p) - 1, len(p) - 1 + len(toks))
        lg = np.asarray(reference.logits_at(params, ids, at, SIZES))
        chosen = lg[np.arange(len(toks)), toks]
        gaps.append(float(((lg.max(-1) - chosen) / lg.std(-1)).max()))
        hits += int((lg.argmax(-1) == np.asarray(toks)).sum())
        n += len(toks)
    return max(gaps), hits / n


# --------------------------------------------------- the engine = the reference
def test_the_dense_forward_is_the_reference(params):
    ids = np.asarray(prompts_of([40])[0])
    want = reference.logits_at(params, ids, np.arange(len(ids)), SIZES)
    got = jamba.JambaModel(CFG).apply({"params": params}, ids[None])[0]
    # float32 both; the reference at `highest` precision
    np.testing.assert_allclose(got, want, atol=2e-4)


@pytest.mark.parametrize("burst", [0, 4])
def test_the_engines_tokens_are_the_references(params, burst):
    """Prompts of 37, 5 and 20 tokens through a budget of 16: chunks of one
    sequence beside decode rows of others, then single steps or bursts.
    Float32 engine, float32 cache: every token is the reference's argmax."""
    prompts = prompts_of([37, 5, 20])
    produced = engine(params, burst=burst).generate(prompts,
                                                    max_new_tokens=12)
    assert worst_gap(params, prompts, produced) == (0.0, 1.0)
    assert min(len(set(t)) for t in produced) >= 8      # no repeated token


def test_the_engines_logits_are_the_references(params):
    """The same, step by step, on LOGITS: the row of every sequence a step
    finishes (its last chunk, or a decode row) against the reference's full
    forward over the tokens the cache then holds, to float32 rounding in
    another order (1e-3 where the logits' spread is ~0.05 to 0.5 ... a
    fiftieth of it at worst)."""
    eng = engine(params, burst=0)
    inner, seen = eng._step_fn, []

    def spy(*args, **kw):
        out = inner(*args, **kw)
        seen.append(np.asarray(out[0]))
        return out

    eng._step_fn = spy
    eng.put([0, 1, 2], prompts_of([37, 5, 20]))
    compared = 0
    for _ in range(10):
        out = eng.schedule_step()
        for uid, tok in out.items():
            seq = eng.state_manager.get_sequence(uid)
            ids = np.asarray(seq.tokens[:seq.seen_tokens], np.int32)
            want = reference.logits_at(params, ids, [len(ids) - 1], SIZES)[0]
            np.testing.assert_allclose(seen[-1][seq.slot], want, atol=1e-3)
            assert tok == int(np.argmax(want))
            seq.tokens.append(tok)
            compared += 1
    assert compared >= 20


def test_a_bfloat16_cache_reads_within_the_stated_types(params):
    """Pages and state rows in bfloat16 (the stated types), arithmetic in
    float32: h is rounded once a step, where a run leaves it.  The gap stays
    under a tenth of a standard deviation of the logits and the argmax at
    nine positions of ten, over contexts of up to 100 tokens."""
    eng = engine(params, dtype="bfloat16",
                 cfg=jamba.jamba_tiny(dtype="float32"))
    prompts = prompts_of([37, 5, 20], seed=1)
    produced = eng.generate(prompts, max_new_tokens=60)
    gap, share = worst_gap(params, prompts, produced)
    assert gap < 0.1 and share >= 0.9, (gap, share)


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_where_the_numbers_are_rounded_is_the_configs(params, seed):
    """``activation_dtype``: by default what lies BETWEEN the matrix products
    (the residual stream, ``u`` and ``z``, the gate, the MLP's products) is
    held in the model's dtype, where the published implementations round;
    ``"float32"`` keeps it float32.  Both run through the same engine (a
    chunked prompt beside another's rows, then bursts); measured over these
    three seeds the published rounding reads 1.49-1.64 times as far from the
    float32 reference on the dense forward's logits (2.7-3.0 % of their
    spread against 1.7-2.0 % at six tiny layers), and either engine's tokens
    miss the reference's argmax at one position of 24 at most, by at most
    0.043 of the spread (both have the cache's state rows in bfloat16)."""
    prompts, err = prompts_of([37, 20], seed=seed), {}
    ids = np.asarray(prompts[0])
    want = reference.logits_at(params, ids, np.arange(len(ids)), SIZES)
    for act in ("", "float32"):
        cfg = jamba.jamba_tiny(dtype="bfloat16", activation_dtype=act)
        assert cfg.act_dtype == (jnp.float32 if act else jnp.bfloat16)
        dense = jamba.JambaModel(cfg).apply({"params": params}, ids[None])[0]
        err[act] = float(jnp.sqrt(jnp.mean((dense - want) ** 2))
                         / jnp.std(want))
        produced = engine(params, dtype="bfloat16", cfg=cfg).generate(
            prompts, max_new_tokens=12)
        gap, share = worst_gap(params, prompts, produced)
        assert gap < 0.1 and share >= 0.85, (act, gap, share)
    assert 1.2 * err["float32"] < err[""] < 0.06, err


# ------------------------------------------------------- the cache's two kinds
def test_the_cache_holds_pages_and_state_rows(params):
    eng = engine(params, dtype="bfloat16")
    kv = eng.kv_cache
    assert kv.kinds == ("state", "state", "pages", "state", "state", "state")
    assert kv.page_layers == 1
    k, v = kv.layers[2]
    assert kv.token_pairs == 1 and k.shape == v.shape == (64, 8, 1, 16)
    conv, ssm = kv.layers[0]
    assert conv.shape == (3, 5, 128) and ssm.shape == (5, 16, 128)
    per_token, per_seq = kv.bytes_by_kind()
    assert per_token == 2 * 16 * 2                     # K and V, one layer
    assert per_seq == 5 * (3 * 128 + 16 * 128) * 2     # five Mamba layers
    # the allocator and the claims count the attention layer's pages alone
    assert kv.blocks_for(17) == 3 and eng.state_manager.free_blocks == 63
    with pytest.raises(NotImplementedError):
        BlockedKVCache(6, 8, 8, 1, 16, kv_dtype="int8",
                       recurrent=CFG.recurrent_state, max_seqs=4)
    with pytest.raises(NotImplementedError, match="recurrent state rows"):
        InferenceEngineV2(jamba.JambaModel(CFG), params=params, config={
            "kv_cache_dtype": "int8"})


def test_a_multi_query_page_of_16_bits_holds_two_tokens_a_row():
    """One KV head of a whole-lane head size in a 16-bit cache: a page is
    ``[block_size / 2, 2, Dh]``, the bytes of ``[block_size, 1, Dh]``; the
    scatter writes the rows where the plain layout has them."""
    paired = BlockedKVCache(1, 4, 8, 1, 128, dtype=jnp.bfloat16)
    assert paired.token_pairs == 2
    assert paired.layers[0][0].shape == (4, 4, 2, 128)
    for kw in (dict(dtype=jnp.float32), dict(dtype=jnp.float16),
               dict(kv_dtype="int8")):
        assert BlockedKVCache(1, 4, 8, 1, 128, **kw).token_pairs == 1
    assert BlockedKVCache(1, 4, 8, 2, 128,
                          dtype=jnp.bfloat16).token_pairs == 1
    # ONE predicate states the format (the kernels' module): the cache lays
    # its pages out by it, and the run-tiled kernel takes bfloat16
    # multi-query because a page of it is held so; a cache that cannot hold
    # pairs says so instead of handing the kernel a page it cannot copy
    from deepspeed_tpu.ops.pallas.paged_attention import (
        page_kv_heads, page_row_tokens, paged_attention, run_tiled)
    assert page_row_tokens(1, 128, jnp.bfloat16) == 2 == paired.token_pairs
    assert run_tiled(1, 128, jnp.bfloat16)
    assert page_kv_heads(paired.layers[0][0].shape, 8) == 1
    for shape in ((1, 64, jnp.bfloat16), (1, 128, jnp.float32),
                  (1, 128, jnp.float16), (2, 128, jnp.bfloat16)):
        assert page_row_tokens(*shape) == 1
    with pytest.raises(NotImplementedError, match="two tokens a row"):
        BlockedKVCache(1, 4, 7, 1, 128, dtype=jnp.bfloat16)
    with pytest.raises(NotImplementedError, match="two tokens a row"):
        BlockedKVCache(1, 8, 32, 1, 128, dtype=jnp.bfloat16, window_size=64,
                       chunk_size=1)
    plain = jnp.zeros((4, 8, 1, 128), jnp.bfloat16)     # a row a token
    with pytest.raises(ValueError, match="page_row_tokens"):
        paged_attention(jnp.zeros((8, 4, 128), jnp.bfloat16), plain, plain,
                        jnp.zeros((2, 2), jnp.int32), jnp.zeros(8, jnp.int32),
                        jnp.zeros(8, jnp.int32))
    rng = np.random.default_rng(0)
    k, v = (jnp.asarray(rng.standard_normal((5, 1, 128)), jnp.bfloat16)
            for _ in "kv")
    blk, off = jnp.asarray([1, 1, 2, 3, 3]), jnp.asarray([0, 5, 7, 2, 3])
    got = rf._kv_scatter(paired.layers[0], k, v, blk, off)
    want = tuple(plain.at[blk, off].set(rows) for rows in (k, v))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g).reshape(w.shape),
                                      np.asarray(w))


def test_the_steps_counts_are_the_recurrent_layers(params):
    """A chunk of 16 rows of one sequence (from position 0), then the rest
    beside another's: state rows read and written and scan tokens, summed
    over the five Mamba layers."""
    eng = engine(params)
    eng.put([7, 8], prompts_of([20, 3]))
    eng.schedule_step()
    counts = eng.last_step_counts          # 3 rows of uid 8, 13 of uid 7
    assert (counts["state_rows_read"], counts["state_rows_written"],
            counts["scan_tokens"]) == (0, 2 * 5, 16 * 5)
    eng.schedule_step()                    # uid 7's last 7 prefill rows
    counts = eng.last_step_counts
    assert (counts["state_rows_read"], counts["state_rows_written"],
            counts["scan_tokens"]) == (5, 5, 7 * 5)
    assert counts["state_row_bytes"] == eng.kv_cache.bytes_by_kind()[1]


@pytest.mark.parametrize("slot_rows", [False, True])
def test_the_mixers_scopes_reach_the_compiled_program(slot_rows):
    """``ds.ssm_proj``, ``ds.ssm_conv`` and ``ds.ssm_scan`` inside ``ds.ssm``,
    ``ds.kv_cache`` inside the attention layer's ``ds.attn``: the scope paths
    of the compiled step of either layout (the device trace carries the
    same), and the burst hands its layout on."""
    import re
    from deepspeed_tpu.telemetry import names
    model = jamba.JambaModel(CFG)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    cache = jax.eval_shape(lambda: BlockedKVCache(
        6, 6, 8, 1, 16, dtype=jnp.float32, recurrent=CFG.recurrent_state,
        max_seqs=3).layers)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    rows = 3 if slot_rows else 16
    text = rf.jamba_ragged_step.lower(
        shapes, cache, i32(rows), i32(rows), i32(rows), i32(3, 4), i32(3),
        cfg=CFG, block_size=8, slot_rows=slot_rows).compile().as_text()
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    assert any(p.startswith("jit(" + names.PROGRAM_RAGGED_STEP + "jamba)")
               for p in paths)
    inside = lambda outer, scope: any(
        f"/{outer}/{scope}/" in p or p.endswith(f"/{outer}/{scope}")
        for p in paths)
    for scope in (names.SCOPE_SSM_PROJ, names.SCOPE_SSM_CONV,
                  names.SCOPE_SSM_SCAN):
        assert inside(names.SCOPE_SSM, scope), scope
    assert inside(names.SCOPE_ATTENTION, names.SCOPE_KV_CACHE)
    assert rf.jamba_ragged_step.slot_rows and \
        not rf.llama_ragged_step.slot_rows


# ------------------------------------------------------------- planted faults
def _nothing_carried(orig_run, orig_slot):
    def run_plan(slots, positions, n):
        plan = orig_run(slots, positions, n)
        zero = plan["flags"] & 1
        return dict(plan, flags=plan["flags"] - zero + 2 * zero,
                    fresh=jnp.ones_like(plan["fresh"]))

    def slot_plan(slots, positions):
        return dict(orig_slot(slots, positions),
                    fresh=jnp.ones_like(slots, dtype=bool))
    return dict(_run_plan=run_plan, _slot_plan=slot_plan)


def _slots_crossed(orig_block):
    def block(mp, h, state, plan, **kw):
        conv, ssm = state
        crossed = (jnp.roll(conv, 1, axis=1), jnp.roll(ssm, 1, axis=0))
        out, _ = orig_block(mp, h, crossed, plan, **kw)
        return out, orig_block(mp, h, state, plan, **kw)[1]
    return dict(_ssm_block=block)


def _dirty_row_read(orig_run):
    """A run at position 0 takes what its slot's row holds (no zeros)."""
    def run_plan(slots, positions, n):
        plan = orig_run(slots, positions, n)
        zero = plan["flags"] & 2
        return dict(plan, flags=plan["flags"] - zero + zero // 2,
                    fresh=jnp.zeros_like(plan["fresh"]))
    return dict(_run_plan=run_plan)


def _scan_restarted_inside_a_run(orig_run, every=8):
    def run_plan(slots, positions, n):
        plan = orig_run(slots, positions, n)
        again = plan["live"] & (plan["idx"] > 0) & (plan["idx"] % every == 0)
        return dict(plan, flags=plan["flags"] | jnp.where(again, 2, 0))
    return dict(_run_plan=run_plan)


def _rotary_applied(orig_attn):
    """The attention layer turns q and k by rotary (Jamba applies none)."""
    from deepspeed_tpu.models.llama import _rope_freqs
    cos, sin = (jnp.asarray(a, jnp.float32)
                for a in _rope_freqs(CFG.head_dim, 512, 100.0, None))

    def attn(lp, h, kv, blk, off, tables, slots, pos, _cos, _sin, **kw):
        return orig_attn(lp, h, kv, blk, off, tables, slots, pos, cos, sin,
                         **dict(kw, rotary=True))
    return dict(_ragged_attention_block=attn)


def _conv_rows_dropped(orig_conv):
    def conv_runs(x, conv_state, conv, plan):
        u, new = orig_conv(x, jnp.zeros_like(conv_state), conv, plan)
        return u, new
    return dict(_conv_runs=conv_runs)


def _state_in_8_bits(orig_block):
    def block(mp, h, state, plan, **kw):
        out, (conv, ssm) = orig_block(mp, h, state, plan, **kw)
        scale = jnp.max(jnp.abs(ssm.astype(jnp.float32)), axis=(1, 2),
                        keepdims=True) / 127 + 1e-30
        ssm8 = (jnp.round(ssm.astype(jnp.float32) / scale) * scale)
        return out, (conv, ssm8.astype(ssm.dtype))
    return dict(_ssm_block=block)


def _recurrence_in_bfloat16():
    """Every product and sum of the recurrence rounded to bfloat16 (the
    state it carries too), in both kinds of step."""
    bf = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)

    def scan_runs(dt, u, B, Cm, A, state, plan, use_kernel):
        def token(carry, row):
            st, h = carry
            dt_t, u_t, B_t, C_t, slot, flag = row
            h = jnp.where((flag & 1) != 0, st[slot].astype(jnp.float32), h)
            h = jnp.where((flag & 2) != 0, 0, h)
            h = bf(bf(jnp.exp(bf(dt_t[None, :] * A))) * h
                   + bf(bf(dt_t * u_t)[None, :] * bf(B_t)[:, None]))
            st = st.at[slot].set(jnp.where(
                (flag & 4) != 0, h.astype(st.dtype), st[slot]))
            return (st, h), jnp.sum(bf(h * bf(C_t)[:, None]), axis=0)

        (state, _), y = jax.lax.scan(
            token, (state, jnp.zeros(A.shape, jnp.float32)),
            (dt, u, B, Cm, plan["slots"], plan["flags"]))
        return y, state

    def scan_slots(dt, u, B, Cm, A, state, plan):
        h = jnp.where(plan["fresh"][:, None, None], 0,
                      state.astype(jnp.float32))
        h = bf(bf(jnp.exp(bf(dt[:, None, :] * A))) * h
               + bf(bf(dt * u)[:, None, :] * bf(B)[:, :, None]))
        y = jnp.sum(bf(h * bf(Cm)[:, :, None]), axis=1)
        return y, jnp.where(plan["live"][:, None, None],
                            h.astype(state.dtype), state)
    return dict(_scan_runs=scan_runs, _scan_slots=scan_slots)


FAULTS = {
    "nothing_carried_from_step_to_step": lambda: _nothing_carried(
        rf._run_plan, rf._slot_plan),
    "slots_crossed": lambda: _slots_crossed(rf._ssm_block),
    "the_convolutions_rows_dropped_at_a_steps_edge": lambda:
        _conv_rows_dropped(rf._conv_runs),
    "rotary_applied_to_the_attention_layer": lambda:
        _rotary_applied(rf._ragged_attention_block),
    "the_scan_restarted_from_zeros_inside_a_run": lambda:
        _scan_restarted_inside_a_run(rf._run_plan),
}


def _serve(params, prompts, new, patch=None, monkeypatch=None, **kw):
    """The prompts through a fresh engine, optionally with a fault planted
    in the step's functions (the compiled programs are dropped on both sides
    of it: they were traced with other functions)."""
    programs = (rf.jamba_ragged_step, rf.decode_burst)
    if patch:
        for name, fn in patch.items():
            monkeypatch.setattr(rf, name, fn)
        [p.clear_cache() for p in programs]
    try:
        return engine(params, **kw).generate(prompts, max_new_tokens=new)
    finally:
        if patch:
            monkeypatch.undo()
            [p.clear_cache() for p in programs]


@pytest.mark.parametrize("name", list(FAULTS))
def test_a_fault_in_carrying_the_state_is_caught(params, name, monkeypatch):
    """Sound, the engine's tokens are the reference's argmax at every
    position; with the fault they are not, by more than half a standard
    deviation of the logits somewhere."""
    prompts = prompts_of([37, 5, 20], seed=2)
    sound = _serve(params, prompts, 24)
    assert worst_gap(params, prompts, sound) == (0.0, 1.0)
    bad = _serve(params, prompts, 24, FAULTS[name](), monkeypatch)
    gap, share = worst_gap(params, prompts, bad)
    assert gap > 0.5 and share < 0.95, (name, gap, share)
    # and the programs traced with the fault are gone
    assert _serve(params, prompts, 24) == sound


def test_a_freed_slots_next_owner_starts_from_zeros(params, monkeypatch):
    """One slot: the second request takes the row the first left dirty, and
    reads none of it; with position 0 taking the row as it lies, it does."""
    prompts = prompts_of([30, 21], seed=3)

    def one_after_the_other(patch=None):
        programs = (rf.jamba_ragged_step, rf.decode_burst)
        if patch:
            for name, fn in patch.items():
                monkeypatch.setattr(rf, name, fn)
            [p.clear_cache() for p in programs]
        try:
            eng = engine(params, seqs=2)
            return [eng.generate([p], max_new_tokens=10)[0] for p in prompts]
        finally:
            if patch:
                monkeypatch.undo()
                [p.clear_cache() for p in programs]

    produced = one_after_the_other()
    assert worst_gap(params, prompts, produced) == (0.0, 1.0)
    dirty = one_after_the_other(_dirty_row_read(rf._run_plan))
    assert dirty[0] == produced[0]             # the first found zeros anyway
    # what the first request left decays over the second's 21-token prompt:
    # a smaller fault than a lost state, and still no sound run's reading
    gap, share = worst_gap(params, prompts[1:], dirty[1:])
    assert gap > 0.1 and share < 0.9, (gap, share)


def test_a_preempted_request_is_recomputed_from_its_first_token(params):
    """A pool too small for the three requests: the scheduler evicts and
    requeues, the slot's row is taken by whoever comes next, and the
    requeued request recomputes from position 0.  Every stream is the
    reference's."""
    sched = build_serving_engine(
        jamba.JambaModel(CFG), params=params, engine_config={
            "dtype": "float32", "decode_burst": 4, "state_manager": {
                "max_tracked_sequences": 8, "max_ragged_sequence_count": 5,
                "max_context": 160, "block_size": 8, "num_blocks": 13,
                "max_ragged_batch_size": 16}},
        serving_config={"max_concurrent": 4, "kv_admit_reserve_tokens": 0})
    prompts = prompts_of([30, 28, 26], seed=4)
    produced = sched.serve(prompts, max_new_tokens=24)
    assert sched.preemptions >= 1
    assert worst_gap(params, prompts, produced) == (0.0, 1.0)


def _logit_errors(params, prompts, steps, patch=None, monkeypatch=None, **kw):
    """Step by step through a fresh engine (single steps, no burst), the
    row of every sequence a step finishes against the reference's full
    forward over the tokens the cache then holds: rms of the difference /
    the reference logits' std, a row."""
    programs = (rf.jamba_ragged_step, rf.decode_burst)
    if patch:
        for name, fn in patch.items():
            monkeypatch.setattr(rf, name, fn)
        [p.clear_cache() for p in programs]
    try:
        eng = engine(params, burst=0, **kw)
        inner, seen, errors = eng._step_fn, [], []

        def spy(*args, **kwargs):
            out = inner(*args, **kwargs)
            seen.append(np.asarray(out[0]))
            return out

        eng._step_fn = spy
        eng.put(list(range(len(prompts))), prompts)
        for _ in range(steps):
            for uid, tok in eng.schedule_step().items():
                seq = eng.state_manager.get_sequence(uid)
                ids = np.asarray(seq.tokens[:seq.seen_tokens], np.int32)
                want = np.asarray(reference.logits_at(
                    params, ids, [len(ids) - 1], SIZES)[0])
                errors.append(float(np.sqrt(np.mean(
                    (seen[-1][seq.slot] - want) ** 2)) / want.std()))
                seq.tokens.append(tok)
        return errors
    finally:
        if patch:
            monkeypatch.undo()
            [p.clear_cache() for p in programs]


@pytest.mark.parametrize("seed", [5, 6, 7])
@pytest.mark.parametrize("name", ["state_in_8_bits",
                                  "recurrence_in_bfloat16"])
def test_narrower_types_drift_where_the_stated_types_do_not(
        params, name, seed, monkeypatch):
    """h rounded to 8 bits a row where a run leaves it; and the recurrence's
    every product in bfloat16: each against the stated types (h in bfloat16
    BETWEEN steps, float32 inside one), over a float32 engine and 60
    decoded tokens of a state that remembers hundreds, on LOGITS (the tokens
    of six tiny layers barely move: whether one flips is the draw's luck).
    Measured over these seeds, mean error a row in units of the logits'
    spread: stated 0.0052-0.0058, the recurrence in bfloat16 0.0072-0.0085
    (1.26-1.47 times), h in 8 bits 0.042-0.060 (8-10 times)."""
    prompts = prompts_of([20, 9], seed=seed)
    cfg = jamba.jamba_tiny(dtype="float32")
    stated = _logit_errors(params, prompts, 64, dtype="bfloat16", cfg=cfg)
    patch = _state_in_8_bits(rf._ssm_block) if name == "state_in_8_bits" \
        else _recurrence_in_bfloat16()
    narrow = _logit_errors(params, prompts, 64, patch, monkeypatch,
                           dtype="bfloat16", cfg=cfg)
    assert len(stated) == len(narrow) >= 120
    least = 4.0 if name == "state_in_8_bits" else 1.15
    assert np.mean(stated) < 0.01 and \
        np.mean(narrow) > least * np.mean(stated), (
            np.mean(stated), np.mean(narrow))
