"""Cohere2-MoE (``models/cohere2_moe.py``) on the serving path, at tiny size on
the CPU: the ragged step (chunked prefill, single decode, the burst) against
the dense forward over contexts two to three windows long; the expert layer
that is told which experts it holds (the shares add up, dead rows reach no
expert, the counts are the live copies, the worst case takes the long
buffer); the page counts by layer kind."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import cohere2_moe as cm
from deepspeed_tpu.moe import held_experts as he
from deepspeed_tpu.serving import build_serving_engine

CFG = cm.cohere2_moe_tiny()            # window 16; 16 experts, 8 held, top 2
_made = {}


def _model(cfg=CFG):
    if cfg not in _made:
        model = cm.Cohere2MoeModel(cfg)
        params = model.init(jax.random.PRNGKey(3),
                            jnp.zeros((1, 8), jnp.int32))["params"]
        _made[cfg] = model, params
    return _made[cfg]


def _greedy(model, params, prompt, new):
    ids = list(prompt)
    for _ in range(new):
        logits = model.apply({"params": params}, jnp.asarray([ids]))
        ids.append(int(jnp.argmax(logits[0, -1])))
    return ids[len(prompt):]


def _scheduler(model, params, burst, budget=16, sessions=2):
    return build_serving_engine(
        model, params=params,
        engine_config={"dtype": "float32", "decode_burst": burst,
                       "state_manager": {
                           "max_tracked_sequences": 2 * sessions,
                           "max_ragged_sequence_count": sessions + 1,
                           "max_context": 64, "block_size": 8,
                           "num_blocks": 40,
                           "max_ragged_batch_size": budget}},
        serving_config={"max_concurrent": sessions})


@pytest.mark.parametrize("burst", [0, 8], ids=["steps", "burst"])
def test_the_ragged_step_is_the_dense_forward(burst):
    """Two prompts of 2.5 and 1.3 windows, chunked into budgets of 16 rows,
    then 12 decoded tokens each (46-52 tokens: three windows): the streamed
    tokens are the dense forward's greedy tokens, a step at a time and
    through the burst."""
    model, params = _model()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, n).tolist() for n in (40, 21)]
    want = [_greedy(model, params, p, 12) for p in prompts]
    sched = _scheduler(model, params, burst)
    assert sched.serve(prompts, max_new_tokens=12) == want
    assert (getattr(sched.engine, "burst_steps", 0) > 0) == bool(burst)


def test_a_step_counts_the_live_copies_on_the_device():
    """``expert_copies`` of each step is the count of (live row, expert)
    pairs whose expert is held, over all layers, as the model's own router
    decides them; ``expert_active`` follows.  (One new token: nothing is left
    to launch after the step, so the turn that launched it fetches it.)"""
    model, params = _model()
    prompt = np.random.default_rng(1).integers(0, 256, 13).tolist()
    sched = _scheduler(model, params, 0, budget=32, sessions=1)
    sched.submit(prompt, max_new_tokens=1)
    sched.step()
    counts = sched.engine.last_step_counts
    assert counts["live_tokens"] == 13 and counts["token_budget"] == 32
    per_layer = _routing_counts(model, params, prompt)
    assert counts["expert_copies"] == sum(c.sum() for c in per_layer)
    assert counts["expert_active"] == sum((c > 0).sum() for c in per_layer)
    assert 0 < counts["expert_copies"] < 13 * 2 * 4


@pytest.mark.parametrize("burst", [0, 8], ids=["steps", "burst"])
def test_a_step_that_fetches_nothing_waits_for_nothing(burst, monkeypatch):
    """A prompt's middle chunks finish no sequence: no request waits for a
    token of theirs, so the host fetches NOTHING after them (it goes on to
    build the next batch while the device runs) and their counts stay on the
    device until the next tokens carry them back, added to that step's: a
    ragged step's or a burst's.  The scheduler runs one step ahead: the
    tokens of the prompt's last chunk are fetched in the turn AFTER the one
    that launched it, and the counts they carry stand on the span of the step
    launched in that turn."""
    model, params = _model()
    prompt = np.random.default_rng(2).integers(0, 256, 40).tolist()
    sched = _scheduler(model, params, burst, budget=16, sessions=1)
    sched.submit(prompt, max_new_tokens=12)   # more than 1 + a burst of 8
    fetches = []
    real = np.asarray
    monkeypatch.setattr(
        np, "asarray", lambda a, *args, **kw: (
            fetches.append(1) if isinstance(a, jax.Array) else None,
            real(a, *args, **kw))[1])
    for turn in range(4):          # 16 + 16 + 8 rows, then a decode launch
        before = len(fetches)
        sched.step()
        counts = sched.engine.last_step_counts
        assert (len(fetches) - before, "expert_copies" in counts) == \
            ((0, False) if turn < 3 else (1, True)), turn
    monkeypatch.undo()
    per_layer = _routing_counts(model, params, prompt)
    assert counts["expert_copies"] == sum(c.sum() for c in per_layer)
    assert counts["burst_k"] == burst       # a decode step or a burst
    live = counts["live_tokens"]
    sched.step()                            # fetches THAT step's tokens
    counts = sched.engine.last_step_counts
    assert 0 < counts["expert_copies"] <= live * 2 * 4


def _routing_counts(model, params, prompt):
    """Copies on each held expert, a layer: the dense forward a layer at a
    time, routed by ``held_experts.route`` on its own hidden states."""
    cfg = model.config
    x = params["embed_tokens"]["weight"][jnp.asarray(prompt)][None]
    out = []
    for i, window in enumerate(cfg.layer_windows):
        lp = params[f"layers_{i}"]
        h = cm.layer_norm(x, lp["input_layernorm"]["weight"],
                          cfg.layer_norm_eps)
        topi, _ = he.route(h[0] @ lp["moe"]["gate"]["kernel"],
                           cfg.num_experts_per_tok, "sigmoid")
        local = np.asarray(topi) - cfg.first_expert
        out.append(np.bincount(local[(local >= 0) & (local < cfg.held)],
                               minlength=cfg.held))
        x = cm.Cohere2MoeLayer(cfg, window).apply({"params": lp}, x)
    return out


def _layer_inputs(seed, tokens=48):
    cfg = dataclasses.replace(CFG, experts_held=None)         # all 16 held
    _, params = _model(cfg)
    moe = params["layers_1"]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(seed), (tokens, cfg.hidden_size))
    return cfg, moe, h, h @ moe["gate"]["kernel"]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_shares_add_up_to_the_uncut_layer(seed):
    """16 experts as two shares of 8: the shares' routed parts, with the
    shared experts counted ONCE, are the uncut layer; the copies counted by
    the two shares are every live copy."""
    cfg, moe, h, router = _layer_inputs(seed)
    stacks = lambda lo, hi: [moe[n][lo:hi] for n in ("w1", "w2", "w3")]
    shared = [moe[n] for n in ("shared_w1", "shared_w2", "shared_w3")]
    whole, counts = cm.moe_layer(h, router, *stacks(0, 16), *shared, cfg)
    c = cm.shared_experts(h, *shared)
    parts, landed = [], []
    for chip in (0, 1):
        share = dataclasses.replace(cfg, experts_held=8, first_expert=8 * chip)
        out, n = cm.moe_layer(h, router, *stacks(8 * chip, 8 * chip + 8),
                              *shared, share)
        parts.append(out - c)
        landed.append(n)
    scale = float(jnp.max(jnp.abs(whole)))
    np.testing.assert_allclose(sum(parts) + c, whole, atol=2e-5 * scale)
    assert all(float(jnp.max(jnp.abs(p + c - whole))) > 0.02 * scale
               for p in parts)
    np.testing.assert_array_equal(np.concatenate(landed), counts)
    assert int(counts.sum()) == 48 * cfg.num_experts_per_tok


def test_dead_rows_reach_no_expert():
    cfg, moe, h, router = _layer_inputs(5)
    topi, topw = he.route(router, 2, "sigmoid")
    live = jnp.arange(48) % 3 != 0
    args = (topi, topw, moe["w1"][:8], moe["w2"][:8], moe["w3"][:8])
    out, counts = he.held_experts_apply(h, *args, experts=16, live=live)
    every, all_counts = he.held_experts_apply(h, *args, experts=16)
    assert not np.asarray(out)[~np.asarray(live)].any()
    np.testing.assert_allclose(np.asarray(out)[np.asarray(live)],
                               np.asarray(every)[np.asarray(live)], atol=1e-6)
    held = np.asarray(topi) < 8
    assert int(counts.sum()) == held[np.asarray(live)].sum()
    assert int(all_counts.sum()) == held.sum()


def test_the_buffer_is_sized_by_the_mean_and_the_worst_case_is_exact():
    """The short buffer holds the mean share of a step's copies and a
    quarter more; a routing that sends EVERY copy to held experts takes the
    long one and is still exact."""
    assert he.tier_rows(2048, 8, 16, 128) == 2560      # the cell's step
    assert he.tier_rows(33, 8, 16, 128) is None        # its burst: small
    assert he.tier_rows(768, 2, 8, 8) is None          # every expert held
    rng = np.random.default_rng(0)
    T, D, k = 640, 32, 2
    w1, w3 = (jnp.asarray(rng.standard_normal((8, D, D)) * 0.2, jnp.float32)
              for _ in range(2))
    w2 = jnp.asarray(rng.standard_normal((8, D, D)) * 0.2, jnp.float32)
    x = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    topw = jnp.asarray(rng.random((T, k)), jnp.float32)
    assert he.tier_rows(T, k, 8, 64) == 256 < T * k
    for topi in (rng.integers(0, 8, (T, k)),           # all land: 1280 rows
                 rng.integers(0, 64, (T, k))):         # an eighth lands
        topi = jnp.asarray(topi, jnp.int32)
        out, counts = he.held_experts_apply(x, topi, topw, w1, w2, w3,
                                            experts=64)
        dense = sum(
            (jax.nn.silu(x @ w1[e]) * (x @ w3[e])) @ w2[e]
            * jnp.sum(jnp.where(topi == e, topw, 0), axis=1)[:, None]
            for e in range(8))
        np.testing.assert_allclose(out, dense, atol=1e-4)
        assert int(counts.sum()) == int((topi < 8).sum())


def test_mixtrals_layer_routes_no_dead_row():
    from deepspeed_tpu.models.mixtral import moe_apply
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((24, 16)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((24, 4)), jnp.float32)
    w = [jnp.asarray(rng.standard_normal(s) * 0.3, jnp.float32)
         for s in ((4, 16, 32), (4, 32, 16), (4, 16, 32))]
    live = jnp.arange(24) < 17
    out = moe_apply(x, router, *w, 2, live=live)
    np.testing.assert_array_equal(np.asarray(out)[17:], 0)
    np.testing.assert_allclose(np.asarray(out)[:17],
                               np.asarray(moe_apply(x, router, *w, 2))[:17],
                               atol=1e-6)


def test_page_counts_are_a_layer_kinds():
    """A context past the window: a window layer's call loads the pages of
    its window, a full layer's all of them; the model's counts are summed
    over its four layers."""
    model, params = _model()
    eng = _scheduler(model, params, 0, budget=16, sessions=1).engine
    pos, slots = np.arange(40, 48), np.full(8, 1)      # 8 rows at 40..47
    counts = eng._page_counts(pos, slots)
    one = lambda window: eng._kind_page_counts(pos, slots, window)
    assert counts["grid_pages_window"] == 3 * one(16)["grid_pages"]
    assert counts["grid_pages_full"] == one(0)["grid_pages"]
    assert counts["grid_pages"] == counts["grid_pages_window"] \
        + counts["grid_pages_full"]
    # rows at 40..47 see positions 25..47 through a window of 16: pages 3-5
    # of 8 tokens; with no window pages 0-5
    assert one(16)["row_pages"] == 8 * 3 - 1 and one(0)["row_pages"] == 8 * 6
    assert counts["row_pages"] == 3 * one(16)["row_pages"] + one(0)["row_pages"]


def test_a_one_kind_model_keeps_its_counts_of_one_call():
    from deepspeed_tpu.models.llama import LlamaModel, llama_tiny
    cfg = llama_tiny(sliding_window=16)
    model = LlamaModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    eng = _scheduler(model, params, 0, sessions=1).engine
    pos, slots = np.arange(40, 48), np.full(8, 1)
    counts = eng._page_counts(pos, slots)
    assert set(counts) == {"grid_pages", "row_pages", "short_pages",
                           "block_pages"}
    assert counts == eng._kind_page_counts(pos, slots, 16)
