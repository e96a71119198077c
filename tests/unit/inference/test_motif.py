"""Motif-3 (``models/motif.py``) on the serving path, at tiny size on the CPU:
the ragged step over the LATENT cache with a window on three layers of four
(chunked prefill, single decode, the burst) against the dense forward; mHC
against a loop written out and Sinkhorn's output doubly stochastic; PolyNorm
grouped by expert against a per-expert loop; the windowed latent read on the
gather and through both kernels (interpret mode) against a masked dense read;
the counts a step carries by layer kind; and the other latent models'
programs as they were."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import ragged_forward as rf
from deepspeed_tpu.models import motif as mm
from deepspeed_tpu.moe import held_experts as he
from deepspeed_tpu.ops.pallas import paged_attention as pa
from deepspeed_tpu.serving import build_serving_engine

#: one dense layer and three routed ones: one period of a window of 16 on
#: three layers and a full one
CFG = mm.motif_tiny(num_hidden_layers=4, n_dense_first_layers=1)
#: widths both kernels take in interpret mode (8 heads in 2 groups of 4), a
#: dense window layer and a routed full one
KERNEL_CFG = dataclasses.replace(
    CFG, num_attention_heads=8, num_key_value_heads=2, num_noise_heads=2,
    num_hidden_layers=2, sliding_window_period=2)
_made = {}


def _model(cfg=CFG):
    """The model with seeded weights whose mHC biases and PolyNorm
    coefficients are DRAWN (flax starts them at zeros and thirds, which a
    mapping left out or exchanged would not move)."""
    if cfg not in _made:
        model = mm.MotifModel(cfg)
        params = model.init(jax.random.PRNGKey(3),
                            jnp.zeros((1, 8), jnp.int32))["params"]
        keys = iter(jax.random.split(jax.random.PRNGKey(5), 1000))

        def drawn(path, x):
            name = jax.tree_util.keystr(path)
            if name.endswith(("['bias']", "['poly']", "['shared_poly']")):
                return jax.random.normal(next(keys), x.shape, x.dtype)
            return x
        _made[cfg] = model, jax.tree_util.tree_map_with_path(drawn, params)
    return _made[cfg]


def _greedy(model, params, prompt, new, length=64):
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


# --------------------------------------------------------------- the model
def test_the_configuration_states_the_layer_kinds():
    cfg = mm.MotifConfig()
    assert cfg.layer_windows[:8] == (128, 128, 128, 0) * 2
    assert len(cfg.layer_windows) == 53 and cfg.layer_windows.count(0) == 13
    assert (cfg.qk_nope_head_dim, cfg.kv_latent_dim, cfg.signal_heads) == (
        128, 576, 64)
    assert cfg.softmax_scale == 192 ** -0.5
    assert [cfg.routed(l) for l in range(4)] == [False, False, True, True]
    assert CFG.layer_windows == (16, 16, 16, 0)
    with pytest.raises(ValueError):
        mm.MotifConfig(hidden_act="silu")
    with pytest.raises(ValueError):
        mm.MotifConfig(num_noise_heads=8)


@pytest.mark.parametrize("burst", [8], ids=["burst"])
def test_the_ragged_step_is_the_dense_forward(burst):
    """Two prompts of 40 and 21 tokens, chunked into budgets of 16 rows, then
    10 decoded tokens each through the latent cache (pages of 8, a window of
    16: every context passes the window and crosses page edges), the
    prefill by ragged steps and the replies by bursts: the streamed tokens
    are the dense forward's greedy tokens.  (A step at a time, on logits:
    ``tests/unit/perfbench/test_perfbench_motif.py``.)"""
    model, params = _model()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, n).tolist() for n in (40, 21)]
    want = [_greedy(model, params, p, 10) for p in prompts]
    sched = _scheduler(model, params, burst)
    assert sched.serve(prompts, max_new_tokens=10) == want
    assert (getattr(sched.engine, "burst_steps", 0) > 0) == bool(burst)
    assert [tuple(x.shape for x in layer)
            for layer in sched.engine.kv_cache.layers] == [((40, 8, 128), )] * 4


def test_a_step_counts_its_pages_by_layer_kind():
    """``last_step_counts`` of a latent cache with a window a layer: the
    loads and the pairs summed over the layers, a window layer's rows seeing
    their window alone."""
    model, params = _model()
    eng = _scheduler(model, params, 0).engine
    slots = np.array([1] * 10 + [2] + [0] * 5, np.int32)
    pos = np.array(list(range(30, 40)) + [50] + [0] * 5, np.int32)
    counts = eng._page_counts(pos, slots)
    assert counts["absorbed_rows"] == 11 and counts["expanded_rows"] == 0
    full = int((pos + 1)[slots != 0].sum())
    window = int(np.minimum(pos + 1, 16)[slots != 0].sum())
    assert counts["latent_keys"] == full + 3 * window
    assert counts["expanded_keys"] == counts["expanded_pages"] == 0
    assert counts["grid_pages"] == counts["grid_pages_window"] \
        + counts["grid_pages_full"]
    # (row, page) pairs: a full layer's row spans its context's pages, a
    # window layer's the pages its 16 positions lie in
    row_pages = lambda w: int(np.where(
        slots != 0, pos // 8 + 1 - (np.maximum(pos - w + 1, 0) // 8
                                    if w else 0), 0).sum())
    assert counts["row_pages"] == row_pages(0) + 3 * row_pages(16)


# --------------------------------------------------------------------- mHC
def test_sinkhorn_makes_the_mixing_doubly_stochastic():
    m = jnp.exp(jax.random.normal(jax.random.PRNGKey(0), (4, 4, 50)))
    out = mm.sinkhorn(m, 20)
    np.testing.assert_allclose(out.sum(0), 1.0, atol=1e-3)      # columns
    np.testing.assert_allclose(out.sum(1), 1.0, atol=1e-3)      # rows
    once = mm.sinkhorn(m, 1)
    assert float(jnp.max(jnp.abs(once.sum(1) - 1.0))) > 0.05
    assert float(jnp.min(out)) > 0


@pytest.mark.parametrize("seed", [0, 1])
def test_a_wrapped_sublayer_is_the_loop_written_out(seed):
    """``mhc_sublayer`` against the equations token by token in numpy
    float64: the three mappings, twenty sweeps, the read, the write."""
    _, params = _model()
    p = jax.tree_util.tree_map(lambda x: np.asarray(x, np.float64),
                               params["layers_3"]["attn_mhc"])
    norm_w = np.asarray(params["layers_3"]["input_layernorm"]["weight"],
                        np.float64)
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(7, 4, 64))
    W = rng.normal(size=(64, 64)) / 8
    sub = lambda h: jnp.tanh(h @ jnp.asarray(W, jnp.float32))
    got = mm.mhc_sublayer(jnp.asarray(X, jnp.float32), params["layers_3"][
        "attn_mhc"], params["layers_3"]["input_layernorm"]["weight"], sub,
        CFG)
    eps = CFG.rms_norm_eps
    sig = lambda z: 1 / (1 + np.exp(-z))
    want = np.zeros_like(X)
    for t in range(7):
        x = X[t].reshape(-1)
        x = x / np.sqrt((x ** 2).mean() + eps) * p["norm"]["weight"]
        m = x @ p["proj"]["kernel"]
        a, b = p["alpha"], p["bias"][0]
        h_pre = sig(a[0] * m[:4] + b[:4])
        h_post = 2 * sig(a[1] * m[4:8] + b[4:8])
        res = np.exp(a[2] * m[8:].reshape(4, 4) + b[8:].reshape(4, 4))
        for _ in range(20):
            res = res / res.sum(1, keepdims=True)
            res = res / res.sum(0, keepdims=True)
        h = h_pre @ X[t]
        h = h / np.sqrt((h ** 2).mean() + eps) * norm_w
        y = np.tanh(h @ W)
        want[t] = res @ X[t] + h_post[:, None] * y[None, :]
    np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max())


# ---------------------------------------------------------------- PolyNorm
def test_polynorm_is_the_formula():
    z = jax.random.normal(jax.random.PRNGKey(0), (5, 32))
    coef = jnp.asarray([[0.3, -1.2, 0.7, 0.9]])
    got = mm.poly_norm(z, coef, CFG)
    z64 = np.asarray(z, np.float64)
    rms = lambda p: np.sqrt((p ** 2).mean(-1, keepdims=True) + CFG.rms_norm_eps)
    want = 0.5 * (0.3 * z64 ** 3 / rms(z64 ** 3) - 1.2 * z64 ** 2 / rms(
        z64 ** 2) + 0.7 * z64 / rms(z64) + 0.5)              # b clipped
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("tokens,held", [(24, 8), (700, 8), (700, 32)],
                         ids=["one_buffer", "tier", "every_expert_held"])
def test_the_held_experts_polynorm_is_each_experts_own(tokens, held):
    """``held_experts_apply`` with the row-wise activation, told each copy's
    expert, against a loop over the held experts (each with ITS four
    numbers) on the grouped path and in padded blocks."""
    cfg = dataclasses.replace(CFG, experts_held=held)
    rng = jax.random.split(jax.random.PRNGKey(tokens), 8)
    D, I, E, k = 64, 32, 32, 2
    h = jax.random.normal(rng[0], (tokens, D))
    w1, w3 = (jax.random.normal(r, (held, D, I)) / 8 for r in rng[1:3])
    w2 = jax.random.normal(rng[3], (held, I, D)) / 6
    coef = jax.random.normal(rng[4], (held, 4))
    topi, topw = he.route(jax.random.normal(rng[5], (tokens, E)), k,
                          "sigmoid", True, scale=2.0)
    live = jnp.arange(tokens) % 7 != 3
    got, counts = he.held_experts_apply(
        h, topi, topw, w1, w2, w3, first_expert=0, experts=E, live=live,
        act=lambda z, c: mm.poly_norm(z, c, cfg), act_coef=coef)
    want = jnp.zeros_like(h)
    for e in range(held):
        weight = jnp.sum(jnp.where((topi == e) & live[:, None], topw, 0), 1)
        y = (mm.poly_norm(h @ w1[e], coef[e:e + 1], cfg) * (h @ w3[e])) @ w2[e]
        want = want + weight[:, None] * y
    np.testing.assert_allclose(got, want, atol=3e-5 * float(
        jnp.max(jnp.abs(want))))
    assert int(counts.sum()) == int(((topi < held) & live[:, None]).sum())
    # another expert's coefficients are another result
    other, _ = he.held_experts_apply(
        h, topi, topw, w1, w2, w3, first_expert=0, experts=E, live=live,
        act=lambda z, c: mm.poly_norm(z, c, cfg),
        act_coef=jnp.roll(coef, 1, 0))
    assert float(jnp.max(jnp.abs(other - want))) > 1e-2 * float(
        jnp.max(jnp.abs(want)))


def test_the_shares_add_up_to_the_uncut_layer():
    """Four shares of 8 of the 32 experts: the shares' routed parts plus the
    shared expert counted ONCE are the uncut layer."""
    model, params = _model()
    moe = params["layers_2"]["moe"]
    uncut = dataclasses.replace(CFG, experts_held=32)
    rng = jax.random.split(jax.random.PRNGKey(1), 4)
    stack = lambda r, shape, fan: jax.random.normal(r, shape) / fan ** 0.5
    full = dict(moe, w1=stack(rng[0], (32, 64, 32), 64),
                w3=stack(rng[1], (32, 64, 32), 64),
                w2=stack(rng[2], (32, 32, 64), 32),
                poly=jax.random.normal(rng[3], (32, 4)))
    h = jax.random.normal(jax.random.PRNGKey(2), (60, 64))
    logits = h @ moe["gate"]["kernel"]
    whole, _ = mm.moe_layer(h, logits, full, uncut)
    none = dict(full, w2=jnp.zeros_like(full["w2"]))
    shared, _ = mm.moe_layer(h, logits, none, uncut)
    parts = []
    for chip in range(4):
        cut = dict(full, **{n: full[n][8 * chip:8 * chip + 8]
                            for n in ("w1", "w2", "w3", "poly")})
        cfg = dataclasses.replace(CFG, experts_held=8, first_expert=8 * chip)
        parts.append(mm.moe_layer(h, logits, cut, cfg)[0] - shared)
    scale = float(jnp.max(jnp.abs(whole - shared)))
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=2e-5 * scale)
    assert all(float(jnp.max(jnp.abs(p))) > 0.05 * scale for p in parts)


# ----------------------------------------------------- the windowed latent read
def _latent_case(seed, heads, T=29, rank=32, rope=8, row=128):
    """A sequence of ``T`` tokens in a paged latent cache (pages of 8 in a
    shuffled table) and absorbed queries for all of them."""
    rng = jax.random.split(jax.random.PRNGKey(seed), 3)
    rows = jax.random.normal(rng[0], (T, rank + rope))
    pages = jnp.zeros((6, 8, row)).at[
        jnp.asarray([3, 1, 4, 2])[jnp.arange(T) // 8], jnp.arange(T) % 8,
        :rank + rope].set(rows)
    q = jnp.pad(jax.random.normal(rng[1], (T, heads, rank + rope)),
                ((0, 0), (0, 0), (0, row - rank - rope)))
    tables = jnp.asarray([[0] * 4, [3, 1, 4, 2]], jnp.int32)
    return rows, pages, q, tables


def _masked_dense(q, rows, window, rank, scale):
    T = rows.shape[0]
    s = jnp.einsum("thl,cl->thc", q[..., :rows.shape[1]], rows) * scale
    at = jnp.arange(T)
    mask = at[None, :] <= at[:, None]
    if window:
        mask &= at[None, :] > at[:, None] - window
    p = jax.nn.softmax(jnp.where(mask[:, None], s, -jnp.inf), -1)
    return jnp.einsum("thc,cr->thr", p, rows[:, :rank])


@pytest.mark.parametrize("window", [0, 5, 16])
@pytest.mark.parametrize("kernel", [False, True], ids=["gather", "kernel"])
def test_the_absorbed_read_sees_its_window(window, kernel, monkeypatch):
    """``_latent_attention`` with a window, on the gather and through
    ``ds_paged_latent`` (interpret mode; 16 heads), against a masked dense
    read; a run in the middle of a sequence and single decode rows."""
    if kernel:
        monkeypatch.setenv("DS_TPU_FORCE_PALLAS", "1")
    rows, pages, q, tables = _latent_case(0, 16)
    want = _masked_dense(q, rows, window, 32, 0.2)
    # rows 9..28 as one run, then rows 3 and 6 as decode rows, a dead row
    pick = np.array(list(range(9, 29)) + [3, 6, 0])
    slots = jnp.asarray([1] * 22 + [0], jnp.int32)
    got = rf._latent_attention(
        q[pick], pages, tables, slots, jnp.asarray(pick, jnp.int32), 8,
        rank=32, scale=0.2, use_kernel=kernel, window=window)
    np.testing.assert_allclose(got[:22], want[pick[:22]], atol=2e-5)
    if kernel:
        assert float(jnp.max(jnp.abs(got[22]))) == 0.0


@pytest.mark.parametrize("window", [0, 5, 16])
def test_the_expanded_kernel_sees_its_window_and_its_group(window,
                                                           monkeypatch):
    """``ds_paged_mla_chunk`` (interpret mode) with a window and TWO K/V
    groups read by four query heads each, against a masked dense read in the
    expanded form, in blocks of 16 keys: the first run starts at position 22
    (under a window of 5 its first block is the second), the second at 3."""
    monkeypatch.setenv("DS_TPU_FORCE_PALLAS", "1")
    monkeypatch.setattr(pa, "_CHUNK_BLOCK_KEYS", 16)
    T, H, G, rank, nope, rope, dv, row = 29, 8, 2, 32, 16, 8, 16, 128
    rng = jax.random.split(jax.random.PRNGKey(window), 4)
    rows, pages, _, tables = _latent_case(1, H)
    w_uk = jax.random.normal(rng[0], (rank, G, nope)) / 4
    w_uv = jax.random.normal(rng[1], (rank, G, dv)) / 4
    q = jax.random.normal(rng[2], (T, H, nope + rope))
    k = jnp.concatenate([jnp.einsum("tc,cgn->tgn", rows[:, :rank], w_uk),
                         jnp.broadcast_to(rows[:, None, rank:],
                                          (T, G, rope))], -1)
    v = jnp.einsum("tc,cgv->tgv", rows[:, :rank], w_uv)
    s = jnp.einsum("sgqe,tge->gqst", q.reshape(T, G, H // G, -1), k) * 0.2
    at = jnp.arange(T)
    mask = at[None, :] <= at[:, None]
    if window:
        mask &= at[None, :] > at[:, None] - window
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
    want = jnp.einsum("gqst,tgv->sgqv", p, v).reshape(T, H, dv)
    # runs of 7 and 8 rows, a short one, a dead row
    pick = np.array(list(range(22, 29)) + list(range(3, 11)) + [1, 0])
    slots = jnp.asarray([1] * 16 + [0], jnp.int32)
    qk = jnp.pad(q, ((0, 0), (0, 0), (0, row - rank - rope)))
    got = pa.paged_mla_chunk_attention(
        qk[pick], pages, w_uk, w_uv, tables, slots,
        jnp.asarray(pick, jnp.int32), rank=rank, scale=0.2, min_rows=6,
        window=window)
    np.testing.assert_allclose(got[:15], want[pick[:15]], atol=2e-5)
    assert float(jnp.max(jnp.abs(got[15:]))) == 0.0    # the absorbed form's
    expanded, keys, loads = pa.chunk_page_loads(
        np.asarray(slots), pick, heads=H, block_size=8, min_rows=6,
        window=window)
    assert expanded.sum() == 15
    assert keys == int((np.minimum(pick[:15] + 1, window) if window
                        else pick[:15] + 1).sum())
    # blocks of 2 pages, once a head: positions 0-28 and 0-10 with no window,
    # 18-28 and 0-10 under one of 5, 7-28 and 0-10 under one of 16
    assert loads == {0: 2 + 1, 5: 1 + 1, 16: 2 + 1}[window] * 2 * H


def test_both_kernels_serve_the_gathers_tokens(monkeypatch, burst=0):
    """A budget that holds a long run (the tiny widths' rule is 33 rows), a
    prompt of 70 tokens beside one of 13, through BOTH kernels (interpret
    mode) with the window, the groups and the subtraction: the scheduler's
    streams are ``generate()``'s tokens on the gather.  (A burst's program
    holds the absorbed kernel alone, as the other latent models':
    ``slot_rows``.)"""
    monkeypatch.setenv("DS_TPU_FORCE_PALLAS", "1")
    inner = rf.motif_ragged_step.__wrapped__
    model, params = _model(KERNEL_CFG)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 256, n).tolist() for n in (70, 13)]

    def engine(use_kernel):
        sched = _scheduler(model, params, burst, budget=48, context=128)
        sched.engine._step_fn = jax.jit(
            lambda *a, **kw: inner(*a, **{**kw, "use_kernel": use_kernel}),
            static_argnames=("cfg", "block_size", "use_kernel", "kv_dtype",
                             "slot_rows"), donate_argnums=(1, ))
        sched.engine._step_fn.step_counts = rf.motif_ragged_step.step_counts
        sched.engine._step_fn.slot_rows = True
        return sched

    want = engine(False).engine.generate(prompts, max_new_tokens=4)
    sched = engine(True)
    seen, build = [], sched.engine._build_batch

    def counted(*a, **kw):
        out = build(*a, **kw)
        if out is not None:
            seen.append(dict(sched.engine.last_step_counts))
        return out

    monkeypatch.setattr(sched.engine, "_build_batch", counted)
    assert sched.serve(prompts, max_new_tokens=4) == want
    assert all(len(w) == 4 for w in want)
    assert sum(c["expanded_rows"] > 0 for c in seen) == 2
    assert all((c["expanded_pages"] > 0) == (c["expanded_rows"] > 0)
               for c in seen)


# ------------------------------------------- the expanded kernel's blocks
@pytest.mark.parametrize("window, pos0, n_rows", [
    (0, 700, 40), (128, 0, 9), (128, 1000, 300), (128, 2047, 2),
    (16, 5000, 0)])
def test_a_run_walks_the_blocks_its_rows_see(window, pos0, n_rows):
    """``_chunk_blocks``: a run of ``n_rows`` rows whose first stands at
    ``pos0`` walks the blocks of 1024 keys that hold a key one of its rows
    sees, under a window from the block of the first row's first key; a run
    of no rows walks none."""
    P, blocks = pa._chunk_blocks(np, np.asarray([n_rows]), np.asarray([pos0]),
                                 128, window)
    keys = P * 128
    assert keys == pa._CHUNK_BLOCK_KEYS
    seen = set()
    for p in range(pos0, pos0 + n_rows):
        first = max(p - window + 1, 0) if window else 0
        seen.update(range(first // keys, p // keys + 1))
    assert int(blocks[0]) == len(seen)
    assert seen == set(range(min(seen, default=0), max(seen, default=-1) + 1))
