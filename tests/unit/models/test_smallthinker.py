"""``models/smallthinker.py`` and what it forced: the held-expert layer under a
gradient (``moe/held_experts.py``), per-layer window and rotary, and counts
made on the device reaching a ``ds:train.micro`` span with no wait."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models import smallthinker as st
from deepspeed_tpu.moe import held_experts as he
from deepspeed_tpu.telemetry import names


# ------------------------------------------------- the layer under a gradient
def dense_loop(x, topi, topw, w1, w2, w3, first, live, act):
    """The held experts' part, one expert at a time over every row."""
    out = jnp.zeros(x.shape, jnp.float32)
    for e in range(w1.shape[0]):
        on = (topi == first + e) & live[:, None]
        weight = jnp.sum(jnp.where(on, topw, 0.0), axis=1)
        y = (act(x @ w1[e]) * (x @ w3[e])) @ w2[e]
        out = out + y * weight[:, None]
    return out


def layer_case(tokens, k, held, experts, dead, seed=0):
    key = jax.random.PRNGKey(seed)
    part = lambda i, *shape: jax.random.normal(jax.random.fold_in(key, i),
                                               shape, jnp.float32)
    D, I = 16, 8
    x = part(0, tokens, D)
    logits = part(1, tokens, experts)
    w1, w3, w2 = part(2, held, D, I) / 4, part(3, held, D, I) / 4, \
        part(4, held, I, D) / 3
    live = jnp.arange(tokens) % 5 != 0 if dead else jnp.ones(tokens, bool)
    return x, logits, w1, w2, w3, live


@pytest.mark.parametrize("act", [jax.nn.relu, jax.nn.silu],
                         ids=["relu", "silu"])
@pytest.mark.parametrize("tokens,skew,dead,branch", [
    (40, 0.0, False, "one buffer"),         # T * k under the one-tier rows
    (600, 0.0, True, "tier"),               # even routing: the short buffer
    (600, 8.0, True, "worst case"),         # every copy on the held experts
], ids=["one_buffer", "tier_buffer_dead_rows", "worst_case_buffer_dead_rows"])
def test_held_experts_gradients_are_the_dense_loops(tokens, skew, dead,
                                                    branch, act):
    """Both buffer lengths behind the ``lax.cond`` and the one buffer of a
    small step: value and the gradient of every input against a dense
    per-expert loop, dead rows among them.  Tolerance 2e-5 of the largest
    entry: float32 sums in another order."""
    k, held, experts, first = 2, 4, 8, 2
    x, logits, w1, w2, w3, live = layer_case(tokens, k, held, experts, dead)
    # skew: push the router onto the held experts
    logits = logits.at[:, first:first + held].add(skew)
    tier = he.tier_rows(tokens, k, held, experts)
    topi, _ = he.route(logits, k)
    landed = int(jnp.sum(((topi >= first) & (topi < first + held))
                         & live[:, None]))
    assert {"one buffer": tier is None,
            "tier": tier is not None and landed <= tier,
            "worst case": tier is not None and landed > tier}[branch]

    def ours(x, logits, w1, w2, w3):
        topi, topw = he.route(logits, k)
        out, counts = he.held_experts_apply(
            x, topi, topw, w1, w2, w3, first_expert=first, experts=experts,
            live=live, act=act)
        return out, counts

    def theirs(x, logits, w1, w2, w3):
        topi, topw = he.route(logits, k)
        return dense_loop(x, topi, topw, w1, w2, w3, first, live, act)

    cot = jax.random.normal(jax.random.PRNGKey(9), x.shape, jnp.float32)
    args = (x, logits, w1, w2, w3)
    out, counts = ours(*args)
    want = theirs(*args)
    assert int(jnp.sum(counts)) == landed
    np.testing.assert_allclose(out, want, atol=2e-5 * float(
        jnp.max(jnp.abs(want))))
    grad = lambda f: jax.grad(
        lambda *a: jnp.sum(f(*a) * cot), argnums=(0, 1, 2, 3, 4))(*args)
    grads = grad(lambda *a: ours(*a)[0])
    for got, ref in zip(grads, grad(theirs)):
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(got, ref, atol=2e-5 * float(
            jnp.max(jnp.abs(ref))))
    # a dead row reaches no expert and takes no gradient
    if dead:
        assert float(jnp.max(jnp.abs(grads[0][~live]))) == 0.0


def test_the_serving_callers_activation_is_unchanged_to_the_bit():
    """``grouped_swiglu`` and ``held_experts_apply`` with no ``act`` are what
    they were: SiLU, operation for operation (the serving steps pass none)."""
    x, logits, w1, w2, w3, live = layer_case(600, 2, 8, 8, True)
    sizes = jnp.asarray([100, 0, 50, 150, 75, 25, 60, 40], jnp.int32)
    dot = jax.lax.ragged_dot
    before = dot(jax.nn.silu(dot(x, w1, sizes)) * dot(x, w3, sizes), w2,
                 sizes)
    np.testing.assert_array_equal(
        he.grouped_swiglu(x, sizes, w1, w2, w3), before)
    topi, topw = he.route(logits, 2)
    default = he.held_experts_apply(x, topi, topw, w1, w2, w3, live=live)
    silu = he.held_experts_apply(x, topi, topw, w1, w2, w3, live=live,
                                 act=jax.nn.silu)
    np.testing.assert_array_equal(default[0], silu[0])
    relu = he.held_experts_apply(x, topi, topw, w1, w2, w3, live=live,
                                 act=jax.nn.relu)
    assert float(jnp.max(jnp.abs(relu[0] - silu[0]))) > 1e-3


# ------------------------------------------------ per-layer window and rotary
def test_window_binds_and_the_full_layer_has_no_positions():
    """S = 48 against a window of 16.  A window layer's last row does not see
    a token 17 back, a full layer's does.  The full layer without rotary has
    NO positions: its last row depends on the earlier tokens as a set, so
    putting them in another order (other positions, same tokens) leaves it
    unchanged to float32 rounding (2e-6: sums in another order), while a
    rotary layer's moves."""
    cfg = st.smallthinker_tiny(dtype="float32")
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 48, cfg.hidden_size),
                          jnp.float32)

    def last(window, rotary, x):
        mod = st.SmallThinkerAttention(cfg, window, rotary)
        params = mod.init(jax.random.PRNGKey(0), x)
        return mod.apply(params, x)[0, -1]

    far = x.at[0, 48 - 1 - 17].add(1.0)         # outside the last row's window
    assert float(jnp.max(jnp.abs(last(16, 1, far) - last(16, 1, x)))) == 0.0
    assert float(jnp.max(jnp.abs(last(0, 0, far) - last(0, 0, x)))) > 1e-3
    near = x.at[0, 48 - 1 - 15].add(1.0)        # the window's oldest key
    assert float(jnp.max(jnp.abs(last(16, 1, near) - last(16, 1, x)))) > 1e-4

    order = np.r_[np.random.default_rng(0).permutation(47), 47]
    moved = x[:, order]
    assert float(jnp.max(jnp.abs(last(0, 0, moved) - last(0, 0, x)))) < 2e-6
    assert float(jnp.max(jnp.abs(last(0, 1, moved) - last(0, 1, x)))) > 1e-3


def test_the_published_layouts_are_the_default_and_are_read_by_layer():
    cfg = st.SmallThinkerConfig(num_hidden_layers=8)
    assert cfg.windows == (0, 4096, 4096, 4096) * 2
    assert cfg.rotaries == (0, 1, 1, 1) * 2
    cfg = st.smallthinker_tiny(sliding_window_layout=(1, 0, 1, 0, 1),
                               rope_layout=(1, 1, 0, 0, 1))
    assert cfg.windows == (16, 0, 16, 0) and cfg.rotaries == (1, 1, 0, 0)
    with pytest.raises(ValueError, match="entries"):
        st.smallthinker_tiny(rope_layout=(0, 1))
    with pytest.raises(ValueError, match="outside the router"):
        st.smallthinker_tiny(experts_held=4, first_expert=6)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """One block of the program at four shares of 2 of 8 experts: attention
    (the same on every share, counted once) plus the four expert parts is
    the block with every expert held.  float32, 1e-5 of the largest entry."""
    whole = st.smallthinker_tiny(dtype="float32", remat=False)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 32, whole.hidden_size),
                          jnp.float32)
    block = st.SmallThinkerBlock(whole, 16, 1)
    params = block.init(jax.random.PRNGKey(3), x)["params"]
    full, counts = block.apply({"params": params}, x)
    assert int(jnp.sum(counts)) == 32 * 2
    attention = x + st.SmallThinkerAttention(whole, 16, 1).apply(
        {"params": params["self_attn"]},
        st.RMSNorm(whole.rms_norm_eps, jnp.float32).apply(
            {"params": params["input_layernorm"]}, x))
    total, landed = attention, 0
    for chip in range(4):
        cfg = st.smallthinker_tiny(dtype="float32", remat=False,
                                   experts_held=2, first_expert=2 * chip)
        mine = dict(params, moe=dict(params["moe"], **{
            k: params["moe"][k][2 * chip:2 * chip + 2]
            for k in ("w1", "w2", "w3")}))
        part, counts = st.SmallThinkerBlock(cfg, 16, 1).apply(
            {"params": mine}, x)
        total = total + (part - attention)
        landed += int(jnp.sum(counts))
    assert landed == 32 * 2
    np.testing.assert_allclose(total, full, atol=1e-5 * float(
        jnp.max(jnp.abs(full))))


# --------------------------------------------------- counted on the device
class Spans:
    """Stands where ``telemetry.scope`` stands and keeps what it was given."""

    def __init__(self, real):
        self.real, self.seen = real, []

    def __call__(self, name, **kw):
        self.seen.append((name, dict(kw)))
        return self.real(name, **kw)


def test_device_counts_reach_a_later_micro_span_and_add_up(monkeypatch):
    """The engine books what the model counted on the device on the
    ``ds:train.micro`` span of a LATER call, once the array has reached the
    host, and never waits for it: every micro-step's counts arrive exactly
    once (``micro_steps_covered`` adds up) and unchanged."""
    from deepspeed_tpu.runtime import engine as engine_mod
    spans = Spans(engine_mod._telemetry.scope)
    monkeypatch.setattr(engine_mod._telemetry, "scope", spans)
    cfg = st.smallthinker_tiny(experts_held=4)
    model = st.SmallThinkerModel(cfg)
    assert model.device_counts == (
        names.COUNT_EXPERT_COPIES, names.COUNT_EXPERT_ACTIVE,
        names.COUNT_EXPERT_ROWS_MAX, names.COUNT_EXPERT_PADDED_CALLS)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, tp_rules=st.tp_rules(cfg), config={
            "train_micro_batch_size_per_gpu": 1,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "fusedadam", "params": {"lr": 1e-3}},
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 3},
            "mesh": {"dp": jax.device_count()}})
    # 2 x 160 copies a row: more than one tier's worth (1024), so a step
    # whose fullest expert fits its block runs in padded blocks
    rows = jax.device_count()
    ids = np.random.default_rng(0).integers(0, 256, (rows, 160)).astype(
        np.int32)
    assert he.block_rows(ids.size, 2, cfg.held, 8) == 512
    engine.initialize_parameters(jax.random.PRNGKey(0), ids, ids)
    want = []
    for _ in range(4):
        loss = engine(ids, ids)
        assert loss.shape == ()                 # the loss alone, as ever
        want.append(np.asarray(engine._pending_counts[-1]))
        engine.backward(loss)
        engine.step()
        jax.block_until_ready(loss)             # the test's wait, not the
        #                                         engine's: the next call
        #                                         finds the counts ready
    micro = [kw for name, kw in spans.seen if name == names.TRAIN_MICRO]
    assert len(micro) == 4
    assert names.COUNT_MICROS_COVERED not in micro[0]   # nothing earlier
    booked = [kw for kw in micro if names.COUNT_MICROS_COVERED in kw]
    covered = sum(kw[names.COUNT_MICROS_COVERED] for kw in booked)
    assert covered == 3 and len(engine._pending_counts) == 1
    for i, key in enumerate(model.device_counts):
        assert sum(kw[key] for kw in booked) == sum(w[i] for w in want[:3])
    # the fourth step's counts wait for a fifth call; asked for, they come
    assert engine._ready_device_counts()[names.COUNT_EXPERT_COPIES] == \
        want[3][0]
    assert engine._ready_device_counts() == {}
    # the fullest experts hold at least the layers' means, at most every copy
    copies, _, fullest, padded = want[0]
    assert copies / cfg.held <= fullest <= copies
    # near-even routing: every layer's copies fitted their blocks, and the
    # spans say so of the three micro-steps they cover
    assert padded == cfg.num_hidden_layers
    assert sum(kw[names.COUNT_EXPERT_PADDED_CALLS] for kw in booked) == \
        3 * cfg.num_hidden_layers


def test_a_model_without_counts_books_none(monkeypatch):
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.runtime import engine as engine_mod
    spans = Spans(engine_mod._telemetry.scope)
    monkeypatch.setattr(engine_mod._telemetry, "scope", spans)
    cfg = llama.llama_tiny()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=llama.LlamaModel(cfg), tp_rules=llama.tp_rules(cfg), config={
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "fusedadam", "params": {"lr": 1e-3}},
            "bf16": {"enabled": True}, "zero_optimization": {"stage": 3},
            "mesh": {"dp": jax.device_count()}})
    ids = np.zeros((jax.device_count(), 32), np.int32)
    engine.initialize_parameters(jax.random.PRNGKey(0), ids, ids)
    for _ in range(2):
        loss = engine(ids, ids)
        engine.backward(loss)
        engine.step()
    assert not engine._pending_counts
    assert all(set(kw) <= {"phase", "step", "micro_step"}
               for name, kw in spans.seen if name == names.TRAIN_MICRO)
