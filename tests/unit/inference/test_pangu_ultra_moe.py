"""openPangu-Ultra-MoE (``models/pangu_ultra_moe.py``) on the serving path, at
tiny size on the CPU: the ragged step over the LATENT cache (chunked prefill,
single decode, the burst; the absorbed form) against the dense forward (the
expanded form); the cache's layout; the absorbed products against the
expanded ones for one layer; the 32 shares of a routed layer add up; the
counts a step carries, the latent kernel's block items among them, and a
prompt long enough to take blocks through that kernel."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import ragged_forward as rf
from deepspeed_tpu.inference.v2.ragged import BlockedKVCache
from deepspeed_tpu.models import pangu_ultra_moe as pm
from deepspeed_tpu.moe import held_experts as he
from deepspeed_tpu.ops.pallas.paged_attention import (item_pages,
                                                      kernel_page_loads)
from deepspeed_tpu.serving import build_serving_engine

CFG = pm.pangu_ultra_moe_tiny()     # 1 dense + 4 routed; 16 experts, 8 held
_made = {}


def _model(cfg=CFG):
    if cfg not in _made:
        model = pm.PanguUltraMoeModel(cfg)
        params = model.init(jax.random.PRNGKey(3),
                            jnp.zeros((1, 8), jnp.int32))["params"]
        _made[cfg] = model, params
    return _made[cfg]


def _greedy(model, params, prompt, new, length=64):
    """Greedy tokens of the dense forward: one compiled shape, the sequence
    padded behind (a causal model's logits do not see what follows)."""
    forward = jax.jit(lambda ids: model.apply({"params": params}, ids[None])[0])
    ids = list(prompt)
    for _ in range(new):
        padded = jnp.asarray(ids + [0] * (length - len(ids)))
        ids.append(int(jnp.argmax(forward(padded)[len(ids) - 1])))
    return ids[len(prompt):]


def _scheduler(model, params, burst, budget=16, sessions=2, context=64):
    return build_serving_engine(
        model, params=params,
        engine_config={"dtype": "float32", "decode_burst": burst,
                       "state_manager": {
                           "max_tracked_sequences": 2 * sessions,
                           "max_ragged_sequence_count": sessions + 1,
                           "max_context": context, "block_size": 8,
                           "num_blocks": 40,
                           "max_ragged_batch_size": budget}},
        serving_config={"max_concurrent": sessions})


@pytest.mark.parametrize("burst", [0, 8], ids=["steps", "burst"])
def test_the_ragged_step_is_the_dense_forward(burst):
    """Two prompts of 40 and 21 tokens, chunked into budgets of 16 rows, then
    12 decoded tokens each through the latent cache (pages of 8: a page
    boundary inside every reply): the streamed tokens are the dense
    (expanded) forward's greedy tokens, a step at a time and through the
    burst."""
    model, params = _model()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, n).tolist() for n in (40, 21)]
    want = [_greedy(model, params, p, 12) for p in prompts]
    sched = _scheduler(model, params, burst)
    assert sched.serve(prompts, max_new_tokens=12) == want
    assert (getattr(sched.engine, "burst_steps", 0) > 0) == bool(burst)


def test_the_cache_is_one_latent_buffer_a_layer():
    """One buffer a layer, a row a token for all the heads: 576 values at the
    published sizes in a row of 640 (the next multiple of 128 lanes), no K
    and no V buffer, no head axis; the engine builds it from the model's own
    statement (``kv_latent_dim``)."""
    cache = BlockedKVCache(7, 12, 128, 128, 0, dtype=jnp.bfloat16,
                           latent_dim=pm.PanguUltraMoeConfig().kv_latent_dim)
    assert pm.PanguUltraMoeConfig().kv_latent_dim == 576
    assert (cache.latent_dim, cache.latent_row) == (576, 640)
    assert [tuple(x.shape for x in layer) for layer in cache.layers] == \
        [((12, 128, 640), )] * 7
    assert cache.layers[0][0].dtype == jnp.bfloat16
    assert cache.blocks_for(129) == 2 and cache.row_width(1024) == 8
    with pytest.raises(NotImplementedError):
        BlockedKVCache(1, 4, 8, 1, 0, latent_dim=40, kv_dtype="int8")
    model, params = _model()
    eng = _scheduler(model, params, 0).engine
    assert CFG.kv_latent_dim == 40
    assert [tuple(x.shape for x in layer) for layer in eng.kv_cache.layers] \
        == [((40, 8, 128), )] * 5


@pytest.mark.parametrize("seed", [0, 1])
def test_the_absorbed_form_is_the_expanded_form(seed):
    """One layer's attention over a sequence of 29 tokens written into a
    fresh latent cache in one step: ``_mla_block`` (q into the latent space,
    the cache rows as keys and values, the latent output through W_uv) gives
    what ``PanguAttention`` (per-head keys and values, one softmax a head)
    gives, to float32 rounding; and the cache then holds each token's
    ``(c ; k_r)`` and zeros."""
    _, params = _model()
    attn = params["layers_1"]["self_attn"]
    h = jax.random.normal(jax.random.PRNGKey(seed), (29, CFG.hidden_size))
    want = pm.PanguAttention(CFG).apply({"params": attn}, h[None])[0]
    tables = jnp.asarray([[0] * 4, [3, 1, 4, 2]], jnp.int32)
    pos = jnp.arange(29)
    pages = jnp.zeros((5, 8, 128), jnp.float32)
    got, (pages, ) = rf._mla_block(
        attn, h, (pages, ), tables[1][pos // 8], pos % 8, tables,
        jnp.ones(29, jnp.int32), pos, cfg=CFG, block_size=8,
        use_kernel=False)
    np.testing.assert_allclose(got, want, atol=2e-5 * float(
        jnp.max(jnp.abs(want))))
    _, _, latent = pm.mla_down(h, attn, pos, CFG)
    rows = pages[tables[1]].reshape(32, 128)[:29]
    np.testing.assert_allclose(rows[:, :40], latent, atol=1e-6)
    assert not np.asarray(rows[:, 40:]).any()


def _layer_inputs(seed, experts, tokens=48):
    cfg = dataclasses.replace(CFG, n_routed_experts=experts,
                              experts_held=None, num_experts_per_tok=8
                              if experts == 256 else 2)
    _, params = _model(cfg)
    moe = params["layers_2"]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(seed), (tokens, cfg.hidden_size))
    return cfg, moe, h, h @ moe["gate"]["kernel"]


@pytest.mark.parametrize("seed", [0, 1])
def test_the_32_shares_add_up_to_the_uncut_layer(seed):
    """256 experts, 8 a token, as 32 shares of 8 (the deployment's cut): the
    shares' routed parts, with the shared expert counted ONCE, are the uncut
    layer, scaling factor and all; the copies the shares count are every
    live copy."""
    cfg, moe, h, router = _layer_inputs(seed, 256)
    whole, counts = pm.moe_layer(h, router, moe, cfg)
    shared = pm.swiglu(h, *(moe[f"shared_{n}_proj"]["kernel"]
                            for n in ("gate", "up", "down")))
    topi, topw = he.route(router, 8, "sigmoid", True, scale=2.5)

    @jax.jit
    def share(chip):            # one compiled shape for the 32 of them
        stacks = [jax.lax.dynamic_slice_in_dim(moe[n], 8 * chip, 8)
                  for n in ("w1", "w2", "w3")]
        return he.held_experts_apply(h, topi, topw, *stacks,
                                     first_expert=8 * chip, experts=256)

    parts, landed = zip(*(share(chip) for chip in range(32)))
    scale = float(jnp.max(jnp.abs(whole)))
    for chip in (0, 31):        # the model's own layer, told its share
        told = dataclasses.replace(cfg, experts_held=8, first_expert=8 * chip)
        stacks = {n: moe[n][8 * chip:8 * chip + 8] for n in ("w1", "w2", "w3")}
        out, n = pm.moe_layer(h, router, {**moe, **stacks}, told)
        np.testing.assert_allclose(out - shared, parts[chip],
                                   atol=2e-5 * scale)
        np.testing.assert_array_equal(n, landed[chip])
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=2e-5 * scale)
    np.testing.assert_array_equal(np.concatenate(landed), counts)
    assert int(counts.sum()) == 48 * 8
    # the scaling factor is on the routed sum alone
    plain = dataclasses.replace(cfg, routed_scaling_factor=1.0)
    once, _ = pm.moe_layer(h, router, moe, plain)
    np.testing.assert_allclose(whole - shared, 2.5 * (once - shared),
                               atol=2e-5 * scale)


def test_the_routers_scale_multiplies_the_normalised_weights():
    logits = jax.random.normal(jax.random.PRNGKey(0), (6, 16))
    topi, topw = he.route(logits, 2, "sigmoid", True)
    topi2, topw2 = he.route(logits, 2, "sigmoid", True, scale=2.5)
    np.testing.assert_array_equal(topi, topi2)
    np.testing.assert_allclose(topw.sum(-1), 1.0, atol=1e-6)
    np.testing.assert_allclose(topw2, 2.5 * topw, atol=1e-6)


def test_a_step_carries_the_latent_counts():
    """13 live rows of a 32-row buffer at positions 0..12: ``latent_keys`` is
    the (row, key) pairs times the layers, every live row absorbed, the page
    counts the latent kernel's (one layer's call), the expert counts over the
    four routed layers only."""
    model, params = _model()
    prompt = np.random.default_rng(1).integers(0, 256, 13).tolist()
    sched = _scheduler(model, params, 0, budget=32, sessions=1)
    sched.submit(prompt, max_new_tokens=1)     # so the turn fetches its step
    sched.step()
    counts = sched.engine.last_step_counts
    assert counts["live_tokens"] == 13 and counts["token_budget"] == 32
    assert counts["latent_keys"] == sum(range(1, 14)) * 5
    assert (counts["absorbed_rows"], counts["expanded_rows"]) == (13, 0)
    assert (counts["grid_pages"], counts["row_pages"]) == (2, 8 + 5 * 2)
    assert 0 < counts["expert_copies"] <= 13 * 2 * 4
    assert 0 < counts["expert_active"] <= 8 * 4


def test_a_latent_caches_page_counts_carry_the_kernels_block_pages():
    """``_page_counts`` of a latent cache is ``kernel_page_loads(latent=
    True)``'s: a prefill run of more than ``item_pages`` pages has whole
    blocks among its loads, a burst's ``[k, rows]`` calls (every item a slab)
    have none."""
    model, params = _model()
    eng = _scheduler(model, params, 8, budget=32, context=128).engine
    row, P = eng.kv_cache.latent_row, 8
    assert item_pages(1, row, eng.kv_cache.dtype, 8) == P
    shapes = dict(heads=CFG.num_attention_heads, kv_heads=1, head_dim=row,
                  kv_dtype=eng.kv_cache.dtype, block_size=8,
                  maxb=eng.state_manager.block_table.shape[1], latent=True)
    # rows 2..25 a chunk at positions 70..93 (pages 0-11: a block and four
    # pages), decode rows beside it, the rest dead
    slots, pos = np.zeros(32, np.int32), np.zeros(32, np.int32)
    slots[0], pos[0] = 2, 17
    slots[2:26], pos[2:26] = 1, np.arange(70, 94)
    slots[27], pos[27] = 3, 66
    counts = eng._page_counts(pos, slots)
    grid, _, short, block = kernel_page_loads(slots, pos, **shapes)
    assert (counts["grid_pages"], counts["short_pages"],
            counts["block_pages"]) == (grid, short, block) == (
                3 + 12 + 9, 3 + 9, P)
    assert counts["latent_keys"] == (18 + sum(range(71, 95)) + 67) * 5
    # a burst of three iterations over the two decoding slots
    slots = np.tile(np.array([0, 0, 2, 3], np.int32), (3, 1))
    pos = np.where(slots != 0, np.array([0, 0, 18, 67])[None]
                   + np.arange(3)[:, None], 0)
    counts = eng._page_counts(pos, slots)
    assert counts["block_pages"] == 0 == kernel_page_loads(
        slots, pos, **shapes)[3]
    assert counts["grid_pages"] == counts["short_pages"] == 3 * (3 + 9)


@pytest.mark.parametrize("burst", [0, 8], ids=["steps", "burst"])
def test_a_prompt_long_enough_to_take_blocks_streams_generates_tokens(
        monkeypatch, burst):
    """A prompt of 90 tokens in chunks of 16 rows (pages of 8: its later
    chunks' runs hold a block of 8 pages and a rest) beside a short one,
    through ``ds_paged_latent`` itself (interpret mode): the scheduler's
    streams are ``generate()``'s tokens on the gather, and steps carried
    ``block_pages``.  Each engine gets its own jit of the step, so the
    suite's cached programs (traced without the kernel gate) are neither
    used nor replaced."""
    monkeypatch.setenv("DS_TPU_FORCE_PALLAS", "1")
    inner = rf.pangu_ultra_moe_ragged_step.__wrapped__
    model, params = _model()
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 256, n).tolist() for n in (90, 13)]

    def engine(use_kernel):
        sched = _scheduler(model, params, burst, context=128)

        def step(*a, **kw):
            return inner(*a, **{**kw, "use_kernel": use_kernel})

        sched.engine._step_fn = jax.jit(
            step, static_argnames=("cfg", "block_size", "use_kernel",
                                   "kv_dtype"), donate_argnums=(1, ))
        return sched

    want = engine(False).engine.generate(prompts, max_new_tokens=10)
    sched = engine(True)
    blocks, build = [], sched.engine._build_batch

    def counted(*a, **kw):
        out = build(*a, **kw)
        blocks.append(sched.engine.last_step_counts["block_pages"])
        return out

    monkeypatch.setattr(sched.engine, "_build_batch", counted)
    assert sched.serve(prompts, max_new_tokens=10) == want
    assert all(len(w) == 10 for w in want)
    # the three chunks whose run ends past page 7 hold one block each
    assert set(blocks) == {0, 8} and blocks.count(8) == 3


def _forced(sched, inner, use_kernel):
    """``sched`` with its own jit of the step ``inner`` (the suite's cached
    programs were traced without the kernel gate)."""
    def step(*a, **kw):
        return inner(*a, **{**kw, "use_kernel": use_kernel})

    sched.engine._step_fn = jax.jit(
        step, static_argnames=("cfg", "block_size", "use_kernel", "kv_dtype",
                               "slot_rows"), donate_argnums=(1, ))
    return sched


@pytest.mark.parametrize("burst,stretch", [(0, 256), (8, 256), (0, 16)],
                         ids=["steps", "burst", "stretches_of_16_rows"])
def test_a_long_chunks_rows_take_the_expanded_kernel(monkeypatch, burst,
                                                     stretch):
    """A budget of 48 rows at the tiny widths (even at 32 rows: a run of 33
    is expanded): a prompt of 100 tokens goes in chunks of 47-48 rows
    through ``ds_paged_mla_chunk`` (interpret mode) beside a short prompt's
    rows and the decode rows on ``ds_paged_latent``; the streams are
    ``generate()``'s tokens on the gather, and every step's counts add up to
    its live rows and (row, key) pairs.  The absorbed form's products run a
    stretch of the buffer at a time and are skipped where a stretch holds
    none of its rows (``_ABSORBED_STRETCH_ROWS``: the buffer is one stretch,
    or three)."""
    monkeypatch.setenv("DS_TPU_FORCE_PALLAS", "1")
    monkeypatch.setattr(rf, "_ABSORBED_STRETCH_ROWS", stretch)
    inner = rf.pangu_ultra_moe_ragged_step.__wrapped__
    model, params = _model()
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 256, n).tolist() for n in (100, 13)]
    engine = lambda use_kernel: _forced(
        _scheduler(model, params, burst, budget=48, context=128), inner,
        use_kernel)
    want = engine(False).engine.generate(prompts, max_new_tokens=10)
    sched = engine(True)
    seen, build = [], sched.engine._build_batch

    def counted(*a, **kw):
        out = build(*a, **kw)
        if out is not None:
            _, pos, slots, *_ = out
            seen.append((dict(sched.engine.last_step_counts),
                         int((pos + 1)[slots != 0].sum())))
        return out

    monkeypatch.setattr(sched.engine, "_build_batch", counted)
    assert sched.serve(prompts, max_new_tokens=10) == want
    assert all(len(w) == 10 for w in want)
    for counts, pairs in seen:
        assert counts["absorbed_rows"] + counts["expanded_rows"] \
            == counts["live_tokens"]
        assert counts["latent_keys"] + counts["expanded_keys"] == pairs * 5
        assert (counts["expanded_pages"] > 0) == (counts["expanded_rows"] > 0)
    # the first step: the short prompt's 13 rows, 35 of the long one's
    assert (seen[0][0]["absorbed_rows"], seen[0][0]["expanded_rows"]) == (
        13, 35)
    assert seen[0][0]["grid_pages"] == 2            # positions 0..12
    assert seen[1][0]["expanded_rows"] >= 47
    # the last chunk (100 - 35 - 48 = 17 rows) and every decode row: absorbed
    assert sum(c["expanded_rows"] > 0 for c, _ in seen) == 2


def test_a_burst_holds_no_call_of_the_expanded_kernel(monkeypatch):
    """The ragged step of a buffer that can hold a long run calls both
    kernels; the burst (one row a slot, ``slot_rows``) and a step of a
    buffer too short for one long run call ``ds_paged_latent`` alone,
    whatever the number of slots."""
    monkeypatch.setenv("DS_TPU_FORCE_PALLAS", "1")
    model, params = _model()
    eng = _scheduler(model, params, 8, budget=48, sessions=40,
                     context=64).engine
    n = eng.state_manager.max_seqs
    assert n > 33                       # the tiny widths' rule
    i32 = lambda *s: jnp.zeros(s, jnp.int32)
    tables = i32(n, eng.state_manager.block_table.shape[1])
    inner = rf.pangu_ultra_moe_ragged_step.__wrapped__
    # (an interpreted kernel is inlined where it is lowered: read the jaxpr)
    step = lambda T: str(jax.make_jaxpr(functools.partial(
        inner, cfg=CFG, block_size=8))(
        params, eng._kv, i32(T), i32(T), i32(T), tables, i32(n)))
    assert "ds_paged_mla_chunk" in step(48) and "ds_paged_latent" in step(48)
    assert "ds_paged_mla_chunk" not in step(32)
    burst = str(jax.make_jaxpr(functools.partial(
        rf.decode_burst.__wrapped__, step_fn=rf.pangu_ultra_moe_ragged_step,
        cfg=CFG, block_size=8, k=4))(
        params, eng._kv, i32(n), i32(n), jnp.ones(n, bool), tables))
    assert "ds_paged_latent" in burst and "ds_paged_mla_chunk" not in burst
    # and the batch builder counts a burst's [k, rows] so
    slots = np.tile(np.arange(n, dtype=np.int32), (3, 1))
    counts = eng._page_counts(np.where(slots != 0, 20 + slots, 0), slots)
    assert (counts["absorbed_rows"], counts["expanded_rows"]) == (
        3 * (n - 1), 0)


def test_the_dense_layers_lead():
    cfg = pm.pangu_ultra_moe_tiny(first_k_dense_replace=2)
    assert [cfg.routed(i) for i in range(5)] == [False, False, True, True,
                                                 True]
    _, params = _model(cfg)
    assert "mlp" in params["layers_1"] and "moe" not in params["layers_1"]
    assert "moe" in params["layers_2"] and "mlp" not in params["layers_2"]
    with pytest.raises(ValueError, match="sandwich"):
        pm.pangu_ultra_moe_tiny(sandwich_norm=False)
    with pytest.raises(ValueError, match="outside the router"):
        pm.pangu_ultra_moe_tiny(first_expert=12)
