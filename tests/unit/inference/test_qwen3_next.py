"""Qwen3-Next on the serving path at tiny size on the CPU: the delta rule's
chunk form and its one-token form against the recurrence; ``InferenceEngineV2``
(chunked prefill over chunks of 64, decode through the cache, several
sequences in one ragged buffer, a run of one token beside a chunk, bursts)
against the plain reference's full forward (``perfbench/reference/
qwen3_next.py``) on LOGITS; the cache's float32 state leaves beside Jamba's;
the shared attention block's defaults; the 16 shares of an expert layer.

``A_log`` and ``dt_bias`` are drawn AS PUBLISHED in the tests that carry a
state far (``A`` uniform in 0 .. 16, ``softplus(dt_bias)`` log-uniform in
0.001 .. 0.1, a small ``in_proj_ba``): a state then remembers for hundreds of
tokens, and a fault in carrying or in holding it shows."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2 import ragged_forward as rf
from deepspeed_tpu.inference.v2.ragged import BlockedKVCache
from deepspeed_tpu.models import jamba, qwen3_next as qn
from deepspeed_tpu.telemetry import names

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)
from perfbench.reference import qwen3_next as reference  # noqa: E402

CFG = qn.qwen3_next_tiny(num_hidden_layers=4)
SIZES = dict(full_attention_interval=4, linear_num_key_heads=2,
             linear_num_value_heads=4, linear_key_head_dim=16,
             linear_value_head_dim=16, rms_norm_eps=1e-6, head_dim=16,
             partial_rotary_factor=0.5, rope_theta=10000.0,
             num_experts_per_tok=4, norm_topk_prob=True, num_hidden_layers=4,
             experts_held=8, first_expert=0)


def as_published(params, seed=0):
    """``A_log``, ``dt_bias`` and the norms' weights as a trained model has
    them, none of them constant."""
    rng = np.random.default_rng(seed)

    def change(path, x):
        name = jax.tree_util.keystr(path)
        if "A_log" in name:
            return jnp.asarray(np.log(rng.uniform(0.5, 16, x.shape)),
                               x.dtype)
        if "dt_bias" in name:
            dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), x.shape))
            return jnp.asarray(np.log(np.expm1(dt)), x.dtype)
        if "in_proj_ba" in name:
            return x * 0.1
        if "norm" in name:          # flax starts the gated norm's at ones
            return x + jnp.asarray(rng.normal(0, 0.1, x.shape), x.dtype)
        return x
    return jax.tree_util.tree_map_with_path(change, params)


def shapes_of(cfg):
    return jax.eval_shape(qn.Qwen3NextModel(cfg).init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))["params"]


def params_of(cfg):
    # drawn leaf by leaf from the shapes (an init would run the forward)
    from perfbench import weights
    return as_published(weights.seeded_weights(
        shapes_of(cfg), jax.random.PRNGKey(0), jnp.float32))


@pytest.fixture(scope="module")
def params():
    return params_of(CFG)


def engine(params, dtype="float32", budget=96, burst=4, blocks=64, seqs=5,
           cfg=CFG):
    return InferenceEngineV2(qn.Qwen3NextModel(cfg), params=params, config={
        "dtype": dtype, "decode_burst": burst, "state_manager": {
            "max_tracked_sequences": 8, "max_ragged_sequence_count": seqs,
            "max_context": 192, "block_size": 8, "num_blocks": blocks,
            "max_ragged_batch_size": budget}})


def prompts_of(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, size=n).tolist() for n in lengths]


def worst_gap(params, prompts, produced, sizes=SIZES):
    """The benchmark's measure: (largest reference logit - reference logit of
    the engine's token) / std, the worst over the generated positions, and
    the share of positions where the engine's token is the argmax."""
    gaps, hits, n = [], 0, 0
    for p, toks in zip(prompts, produced):
        ids = np.asarray(p + toks[:-1], np.int32)
        at = np.arange(len(p) - 1, len(p) - 1 + len(toks))
        lg = np.asarray(reference.logits_at(params, ids, at, sizes))
        chosen = lg[np.arange(len(toks)), toks]
        gaps.append(float(((lg.max(-1) - chosen) / lg.std(-1)).max()))
        hits += int((lg.argmax(-1) == np.asarray(toks)).sum())
        n += len(toks)
    return max(gaps), hits / n


# ------------------------------------------------- the two forms = the rule
def rule_inputs(tokens, heads=4, d=16, seed=3):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = qn.l2_norm(jax.random.normal(k[0], (tokens, heads, d))) * d ** -0.5
    kk = qn.l2_norm(jax.random.normal(k[1], (tokens, heads, d)))
    v = jax.random.normal(k[2], (tokens, heads, d))
    g = -jnp.exp(jax.random.normal(k[3], (tokens, heads))) * 0.3
    beta = jax.nn.sigmoid(jax.random.normal(k[4], (tokens, heads)))
    return q, kk, v, g, beta, jax.random.normal(k[5], (heads, d, d))


@pytest.mark.parametrize("tokens", [1, 63, 64, 65, 150])
def test_the_chunk_form_is_the_recurrence(tokens):
    """Float32, to 1e-5: outputs and the state a run leaves, over runs that
    end inside a chunk of 64, at its edge and past two of them."""
    *rows, state = rule_inputs(tokens)
    want, s_want = qn.delta_rule_recurrence(*rows, state)
    # the loop that serves, over a buffer that holds ONE run (of slot 1, past
    # position 0: it starts from the slot's row); a run of one token takes
    # the one-token form there
    plan = rf._run_plan(jnp.ones(tokens, jnp.int32),
                        5 + jnp.arange(tokens, dtype=jnp.int32), 2)
    got, s_got = rf._rule_runs(*rows, jnp.stack([0 * state, state]), plan)
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(s_got[1], s_want, atol=1e-5)
    assert not np.any(s_got[0])


def test_the_one_token_form_is_a_step_of_the_recurrence():
    """Over a batch of slots at once, each from its own state."""
    q, k, v, g, beta, state = rule_inputs(5)
    states = jnp.stack([state * (i + 1) for i in range(5)])
    got, s_got = qn.delta_rule_token(q, k, v, g, beta, states)
    for i in range(5):
        want, s_want = qn.delta_rule_recurrence(
            q[i:i + 1], k[i:i + 1], v[i:i + 1], g[i:i + 1], beta[i:i + 1],
            states[i])
        np.testing.assert_allclose(got[i], want[0], atol=1e-5)
        np.testing.assert_allclose(s_got[i], s_want, atol=1e-5)


def test_a_padded_row_moves_nothing():
    """Rows with ``k = v = beta = g = 0`` behind a run's end (what the step's
    loop cuts a neighbour's rows to) leave the state as the run left it."""
    *rows, state = rule_inputs(40)
    pad = lambda x: jnp.pad(x, ((0, 24), ) + ((0, 0), ) * (x.ndim - 1))
    _, want = qn.delta_rule_recurrence(*rows, state)
    _, got = qn.delta_rule_chunk(*map(pad, rows), state)
    np.testing.assert_allclose(got, want, atol=1e-5)


# --------------------------------------------------- the engine = the reference
def test_the_dense_forward_is_the_reference(params):
    ids = np.asarray(prompts_of([24])[0])
    want = reference.logits_at(params, ids, np.arange(len(ids)), SIZES)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(qn.Qwen3NextModel(CFG).apply)({"params": params},
                                                    ids[None])[0]
    np.testing.assert_allclose(got, want, atol=2e-4)


@pytest.fixture
def chunks_of_8(monkeypatch):
    """The chunk form over chunks of 8 rows: a prompt of tens of tokens then
    crosses many chunks (the step's programs are traced anew)."""
    jax.clear_caches()
    monkeypatch.setattr(qn, "GDN_CHUNK", 8)
    yield
    jax.clear_caches()


def _logits_step_by_step(params, lengths, budget, cfg=CFG, sizes=SIZES,
                         steps=12):
    """Every row a step finishes (a sequence's last chunk, or a decode row)
    against the reference's full forward over the whole sequence at that
    position (a causal model: what the cache held then); returns how many
    were compared and the kinds of runs met."""
    eng = engine(params, burst=0, budget=budget, cfg=cfg)
    inner, seen = eng._step_fn, []

    def spy(*args, **kw):
        out = inner(*args, **kw)
        seen.append(np.asarray(out[0]))
        return out

    eng._step_fn = spy
    eng.put(list(range(len(lengths))), prompts_of(lengths))
    rows, forms = {uid: {} for uid in range(len(lengths))}, set()
    for _ in range(steps):
        out = eng.schedule_step()
        c = eng.last_step_counts
        forms |= {f for f in ("rule_slot_tokens", "rule_chunk_tokens")
                  if c[f]}
        if c["rule_slot_tokens"] and c["rule_chunk_tokens"]:
            forms.add("both in one buffer")
        for uid, tok in out.items():
            seq = eng.state_manager.get_sequence(uid)
            rows[uid][seq.seen_tokens - 1] = (seen[-1][seq.slot], tok)
            seq.tokens.append(tok)
    for uid, got in rows.items():
        ids = np.asarray(eng.state_manager.get_sequence(uid).tokens[:-1],
                         np.int32)
        at = sorted(got)
        want = np.asarray(reference.logits_at(params, ids, at, sizes))
        np.testing.assert_allclose(np.stack([got[p][0] for p in at]), want,
                                   atol=2e-3)
        assert [got[p][1] for p in at] == np.argmax(want, -1).tolist()
    return sum(map(len, rows.values())), forms


def test_the_engines_logits_are_the_references(params):
    """A prompt of 150 tokens in chunks of 96 and 54 rows (runs that cross
    chunks of 64, steps and block edges) beside prompts of 5 and 20: a run
    of ONE token beside a chunk in one buffer, then single decode steps.
    Float32 engine, float32 cache: logits to float32 rounding in another
    order."""
    compared, forms = _logits_step_by_step(params, [5, 20, 150], 96)
    assert compared >= 25
    assert forms == {"rule_slot_tokens", "rule_chunk_tokens",
                     "both in one buffer"}


def test_runs_cross_many_chunks(params, chunks_of_8):
    compared, _ = _logits_step_by_step(params, [37, 5, 20], 24)
    assert compared >= 25


@pytest.mark.parametrize("burst", [0, 4])
def test_the_engines_tokens_are_the_references(params, burst):
    """Through bursts (every live row one update of its slot's row) and
    without them: every token is the reference's argmax."""
    prompts = prompts_of([70, 5, 20], seed=1)
    produced = engine(params, burst=burst, budget=48).generate(
        prompts, max_new_tokens=12)
    assert worst_gap(params, prompts, produced) == (0.0, 1.0)
    assert min(len(set(t)) for t in produced) >= 6      # no repeated token


# ------------------------------------- the one-token form through its kernel
#: heads of 128 x 128, 8 value heads on 2 key heads, ONE Gated DeltaNet layer
#: and one of attention: the smallest model of ``ds_gated_delta_slot``'s rule
#: (the tiny configuration's heads of 16 stay on XLA whatever the gate says)
WIDE = dict(linear_key_head_dim=128, linear_value_head_dim=128,
            linear_num_key_heads=2, linear_num_value_heads=8,
            num_hidden_layers=2, full_attention_interval=2)
WIDE_CFG = qn.qwen3_next_tiny(**WIDE)


@pytest.fixture(scope="module")
def wide_params():
    return params_of(WIDE_CFG)


@pytest.fixture
def kernels_on(monkeypatch):
    """The step's kernels, interpreted on the CPU (the programs are traced
    anew: the gate is read when they are)."""
    jax.clear_caches()
    monkeypatch.setenv("DS_TPU_FORCE_PALLAS", "1")
    yield
    jax.clear_caches()


def step_args(cfg, slot_rows):
    """A step's abstract arguments: 3 slots, 16 rows (3 in a burst's
    layout)."""
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    cache = jax.eval_shape(lambda: BlockedKVCache(
        cfg.num_hidden_layers, 6, 8, 2, 16, dtype=jnp.float32,
        recurrent=cfg.recurrent_state,
        max_seqs=3).layers)
    rows = 3 if slot_rows else 16
    return (shapes_of(cfg), cache, i32(rows), i32(rows), i32(rows),
            i32(3, 4), i32(3))


def _kernel_in_step(slot_rows):
    return "ds_gated_delta_slot" in str(jax.make_jaxpr(
        lambda *a: rf.qwen3_next_ragged_step(
            *a, cfg=WIDE_CFG, block_size=8, slot_rows=slot_rows))(
        *step_args(WIDE_CFG, slot_rows)))


def test_a_ragged_steps_decode_rows_take_the_kernel(wide_params, kernels_on):
    """A run of ONE token beside a chunk goes through ``ds_gated_delta_slot``
    (behind the step's ``lax.cond``), the chunk through the chunk form:
    logits of every finished row against the reference's full forward."""
    assert _kernel_in_step(slot_rows=False)
    compared, forms = _logits_step_by_step(wide_params, [5, 20, 100], 64,
                                           WIDE_CFG, dict(SIZES, **WIDE),
                                           steps=6)
    assert compared >= 15
    assert "both in one buffer" in forms


def test_a_bursts_rows_take_the_kernel(wide_params, kernels_on):
    """Bursts of 4 (every live row one call of the kernel on its slot's row,
    the state carried in the buffer): every token is the reference's
    argmax."""
    assert _kernel_in_step(slot_rows=True)
    prompts = prompts_of([70, 5, 20], seed=1)
    produced = engine(wide_params, burst=4, budget=48,
                      cfg=WIDE_CFG).generate(prompts, max_new_tokens=12)
    assert worst_gap(wide_params, prompts, produced,
                     dict(SIZES, **WIDE)) == (0.0, 1.0)
    assert min(len(set(t)) for t in produced) >= 6


def test_a_state_held_in_bfloat16_drifts_where_float32_does_not(params,
                                                                monkeypatch):
    """The state's stated type is float32.  The same float32 engine with the
    rule's state HELD in bfloat16 between steps (the model's statement of the
    leaf's type changed, nothing else) leaves the reference ON LOGITS: over a
    reply of 40 tokens behind a context of 70, decoded a step at a time, the
    error of a step's row against the reference's full forward (root mean
    square over the vocabulary, in units of the logits' spread) is a
    thousandth of a percent with the stated type and tenths of a percent and
    more with bfloat16.  (Beside a bfloat16 engine's other roundings, 2.6 % at
    this size, that is not seen over a hundred tokens, as-published decays or
    not: what the benchmark's cell can and cannot tell is in its
    configuration file, ``measured_worst``.)"""
    prompt = prompts_of([70], seed=2)[0]
    stated = dict(CFG.recurrent_state)
    errors = {}
    for held in ("float32", "bfloat16"):
        monkeypatch.setattr(
            qn.Qwen3NextConfig, "recurrent_state",
            property(lambda self: dict(stated, dtypes={"ssm": held})))
        eng = engine(params, burst=0)
        assert eng.kv_cache.layers[0][1].dtype == jnp.dtype(held)
        assert eng.kv_cache.layers[0][0].dtype == jnp.float32
        inner, seen = eng._step_fn, []

        def spy(*args, **kw):
            out = inner(*args, **kw)
            seen.append(np.asarray(out[0][1]))      # the one sequence's slot
            return out

        eng._step_fn = spy
        toks = eng.generate([prompt], max_new_tokens=40)[0]
        ids = np.asarray(prompt + toks[:-1], np.int32)
        at = np.arange(len(prompt) - 1, len(ids))
        want = np.asarray(reference.logits_at(params, ids, at, SIZES))
        got = np.stack(seen[-len(at):])
        errors[held] = float(np.sqrt(np.mean((got - want) ** 2))
                             / np.std(want))
    assert errors["float32"] < 1e-4, errors
    assert errors["bfloat16"] > 20 * errors["float32"], errors


# ------------------------------------------------------- the cache's two kinds
def test_the_cache_holds_pages_and_float32_state_rows(params):
    eng = engine(params, dtype="bfloat16")
    kv = eng.kv_cache
    assert kv.kinds == ("state", "state", "state", "pages")
    k, v = kv.layers[3]
    assert k.shape == v.shape == (64, 8, 2, 16) and k.dtype == jnp.bfloat16
    conv, rule = kv.layers[0]
    assert conv.shape == (3, 5, 128) and conv.dtype == jnp.bfloat16
    assert rule.shape == (5, 4, 16, 16) and rule.dtype == jnp.float32
    per_token, per_seq = kv.bytes_by_kind()
    assert per_token == 2 * 2 * 16 * 2                 # K and V, one layer
    assert per_seq == 3 * (3 * 128 * 2 + 4 * 16 * 16 * 4)
    assert eng._state_row_bytes == per_seq
    with pytest.raises(NotImplementedError):
        BlockedKVCache(4, 8, 8, 2, 16, kv_dtype="int8",
                       recurrent=CFG.recurrent_state, max_seqs=4)
    for other in (dict(window_size=64, chunk_size=8), dict(latent_dim=24),
                  dict(entries_a_buffer=2)):
        with pytest.raises(NotImplementedError):
            BlockedKVCache(4, 8, 8, 2, 16, recurrent=CFG.recurrent_state,
                           max_seqs=4, **other)
    with pytest.raises(NotImplementedError, match="recurrent state rows"):
        InferenceEngineV2(qn.Qwen3NextModel(CFG), params=params, config={
            "kv_cache_dtype": "int8"})


def test_jambas_state_leaves_are_the_caches_type():
    """A model that states no leaf types (Jamba) keeps both leaves in the
    cache's type, byte for byte what it held before."""
    cfg = jamba.jamba_tiny()
    kv = BlockedKVCache(6, 8, 8, 1, 16, dtype=jnp.bfloat16,
                        recurrent=cfg.recurrent_state, max_seqs=5)
    conv, ssm = kv.layers[0]
    assert conv.dtype == ssm.dtype == jnp.bfloat16
    assert conv.shape == (3, 5, 128) and ssm.shape == (5, 16, 128)
    assert kv.bytes_by_kind()[1] == 5 * (3 * 128 + 16 * 128) * 2


def test_the_steps_counts_are_the_tokens_by_form(params):
    """A chunk of 16 rows beside another sequence's 3 (both runs of several
    tokens), then the first's last 4 rows beside the other's ONE decode row:
    tokens by form, summed over the three DeltaNet layers."""
    eng = engine(params, budget=16, burst=0)
    eng.put([7, 8], prompts_of([17, 3]))
    eng.schedule_step()
    counts = eng.last_step_counts          # 3 rows of uid 8, 13 of uid 7
    assert (counts["rule_chunk_tokens"], counts["rule_slot_tokens"]) \
        == (16 * 3, 0)
    assert (counts["state_rows_read"], counts["state_rows_written"]) \
        == (0, 2 * 3)
    eng.state_manager.get_sequence(8).tokens.append(1)
    eng.schedule_step()                    # 4 rows of uid 7, 1 of uid 8
    counts = eng.last_step_counts
    assert (counts["rule_chunk_tokens"], counts["rule_slot_tokens"]) \
        == (4 * 3, 1 * 3)
    assert counts["scan_tokens"] == 5 * 3
    assert counts["state_row_bytes"] == eng.kv_cache.bytes_by_kind()[1]


# ------------------------------------------------ the shared attention block
def _block(qk_norm=None, out_gate=None, width=16):
    rng = np.random.default_rng(0)
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape) * 0.2,
                                      jnp.float32)
    lp = {"q_proj": {"kernel": draw(32, 4, width)},
          "k_proj": {"kernel": draw(32, 2, 16)},
          "v_proj": {"kernel": draw(32, 2, 16)},
          "o_proj": {"kernel": draw(64, 32)}}
    h = draw(6, 32)
    cache = tuple(jnp.zeros((4, 8, 2, 16), jnp.float32) for _ in "kv")
    pos = jnp.arange(6, dtype=jnp.int32)
    slots = jnp.ones(6, jnp.int32)
    tables = jnp.asarray([[0, 0], [1, 2]], jnp.int32)
    cos, sin = (jnp.asarray(t, jnp.float32) for t in rf._rope_freqs(
        16, 64, 10000.0, None))
    import types
    cfg = types.SimpleNamespace(num_attention_heads=4, head_dim=16,
                                sliding_window=0, dtype="float32")
    return rf._ragged_attention_block(
        lp, h, cache, tables[slots, pos // 8], pos % 8, tables, slots, pos,
        cos, sin, cfg=cfg, block_size=8, use_kernel=False, qk_norm=qk_norm,
        out_gate=out_gate)[0]


def test_the_attention_blocks_defaults_leave_it_as_it_was():
    """No norm of q and k and no gate by default: the same bits as with a
    norm that changes nothing, another number with one that does; and the
    compiled step of a model that asks for neither holds no ``ds.attn_gate``."""
    plain = _block()
    np.testing.assert_array_equal(plain, _block(qk_norm=lambda q, k: (q, k)))
    assert not np.allclose(plain, _block(qk_norm=lambda q, k: (2 * q, k)))
    gated = _block(out_gate=True, width=32)
    assert gated.shape == plain.shape
    from deepspeed_tpu.models import llama
    cfg = llama.llama_tiny()
    shapes = jax.eval_shape(llama.LlamaModel(cfg).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    cache = jax.eval_shape(lambda: BlockedKVCache(
        cfg.num_hidden_layers, 6, 8, cfg.num_key_value_heads, cfg.head_dim,
        dtype=jnp.float32).layers)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    text = rf.llama_ragged_step.lower(
        shapes, cache, i32(16), i32(16), i32(16), i32(3, 4), i32(3), cfg=cfg,
        block_size=8).as_text()
    assert names.SCOPE_ATTN_GATE not in text


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
@pytest.mark.parametrize("slot_rows", [False, True])
def test_the_mixers_scopes_reach_the_compiled_program(slot_rows, kernel,
                                                      request):
    """``ds.gdn_proj``, ``ds.gdn_conv`` and ``ds.gdn_rule`` inside ``ds.gdn``,
    the two forms inside ``ds.gdn_rule``, ``ds.attn_gate`` inside ``ds.attn``,
    ``ds.moe_shared`` inside ``ds.mlp``: the scope paths of the compiled step
    of either layout, the one-token form in XLA or through its kernel (the
    readers find ``ds_gated_delta_slot`` by ``ds.gdn_slot`` around it)."""
    cfg = CFG
    if kernel:
        request.getfixturevalue("kernels_on")
        cfg = WIDE_CFG
    text = rf.qwen3_next_ragged_step.lower(
        *step_args(cfg, slot_rows), cfg=cfg, block_size=8,
        slot_rows=slot_rows).compile().as_text()
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    inside = lambda outer, scope: any(
        f"/{outer}/{scope}/" in p or p.endswith(f"/{outer}/{scope}")
        for p in paths)
    for scope in (names.SCOPE_GDN_PROJ, names.SCOPE_GDN_CONV,
                  names.SCOPE_GDN_RULE):
        assert inside(names.SCOPE_GDN, scope), scope
    # a ragged step's one-token form sits behind a ``lax.cond``, its chunk
    # form inside a loop: the scopes are components of the path, in order
    under = lambda outer, scope: any(
        outer in p.split("/") and scope in p.split("/")[
            p.split("/").index(outer):] for p in paths)
    assert under(names.SCOPE_GDN_RULE, names.SCOPE_GDN_SLOT)
    assert slot_rows or under(names.SCOPE_GDN_RULE, names.SCOPE_GDN_CHUNK)
    assert inside(names.SCOPE_ATTENTION, names.SCOPE_ATTN_GATE)
    assert inside(names.SCOPE_MLP, names.SCOPE_MOE_SHARED)
    assert rf.qwen3_next_ragged_step.slot_rows
    assert rf.qwen3_next_ragged_step.step_counts == (
        names.COUNT_EXPERT_COPIES, names.COUNT_EXPERT_ACTIVE)


# ------------------------------------------------------------- the 16 shares
def test_the_sixteen_shares_add_up_to_the_uncut_layer(params):
    """Sixteen chips of one expert each: their routed parts plus ONE gated
    shared expert are the layer with all 16 experts held."""
    rng = np.random.default_rng(0)
    uncut = qn.qwen3_next_tiny(num_hidden_layers=4, experts_held=0)
    D, I = uncut.hidden_size, uncut.moe_intermediate_size
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape) * 0.2,
                                      jnp.float32)
    moe = dict(params["layers_0"]["moe"], w1=draw(16, D, I),
               w3=draw(16, D, I), w2=draw(16, I, D))
    h = draw(40, D) * 5
    whole, counts = qn.moe_layer(h, moe, uncut)
    assert int(counts.sum()) == 40 * 4
    shared = qn.gated_shared_expert(h, moe, jnp.float32)
    parts = []
    for chip in range(16):
        cfg = qn.qwen3_next_tiny(num_hidden_layers=4, experts_held=1,
                                 first_expert=chip)
        cut = dict(moe, **{n: moe[n][chip:chip + 1]
                           for n in ("w1", "w2", "w3")})
        parts.append(qn.moe_layer(h, cut, cfg)[0] - shared)
    scale = float(jnp.max(jnp.abs(whole - shared)))
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=1e-5 * scale)
    assert sum(float(jnp.max(jnp.abs(p))) > 0.01 * scale for p in parts) >= 12
    # dropping the shared expert's gate is another layer
    ungated = dict(moe, shared_gate={"kernel": 0 * moe["shared_gate"][
        "kernel"]})
    assert not np.allclose(qn.gated_shared_expert(h, ungated, jnp.float32),
                           shared, atol=1e-3)
