"""Fused chunked head+loss parity vs the dense logits path.

Reference role: chunked logits loss (``deepspeed/sequence/fpdt_layer.py:1137``
chunks the sequence dim); so does this: a chunk of ROWS finishes its softmax,
the gradient is formed in the pass that computes the loss, and the [N, V]
logits never materialize — values AND gradients must match the dense
computation.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.sequence.cross_entropy import (
    _chunk_rows, fused_linear_cross_entropy,
    softmax_cross_entropy_with_logits)


def _dense_loss(x, w, labels):
    return softmax_cross_entropy_with_logits(x @ w, labels)


def _assert_trees_close(got, ref):
    for (kp, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(got),
                               jax.tree_util.tree_leaves_with_path(ref)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5,
                                   err_msg=jax.tree_util.keystr(kp))


def _inputs(seed, n, d, v, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((n, d)), dtype)
    w = jnp.asarray(rng.standard_normal((d, v)) * 0.3, dtype)
    labels = jnp.asarray(rng.integers(0, v, size=n), jnp.int32)
    return x, w, labels


@pytest.mark.parametrize("v,chunk", [(64, 16), (60, 16), (64, 64), (64, 128)])
def test_fused_ce_matches_dense(v, chunk):
    """Even / uneven splits, chunk ≥ V (one chunk)."""
    x, w, labels = _inputs(0, 24, 32, v)
    ref = jnp.mean(_dense_loss(x, w, labels))
    got = fused_linear_cross_entropy(x, w, labels, chunk)
    assert got.shape == () and got.dtype == jnp.float32
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_fused_ce_grads_match_dense():
    x, w, labels = _inputs(1, 16, 24, 48)
    gd = jax.grad(lambda x, w: jnp.mean(_dense_loss(x, w, labels)),
                  argnums=(0, 1))(x, w)
    gc = jax.grad(
        lambda x, w: fused_linear_cross_entropy(x, w, labels, 16),
        argnums=(0, 1))(x, w)
    for a, b in zip(gc, gd):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n,v,chunk,rows", [
    (7, 64, 16, 7),         # fewer rows than one chunk of 8: one chunk
    (33, 60, 16, 16),       # 33 x 16 / 60 = 8.8 -> 9 -> 16; padded to 48
    (4095, 320, 64, 824),   # the cells' N = S - 1: 819 -> 824, padded to 4120
    (24, 64, 64, 24),       # chunk_size = V
    (24, 64, 128, 24),      # chunk_size > V
])
def test_fused_ce_rows_not_a_multiple_of_the_chunk(n, v, chunk, rows):
    """Loss and both gradients where ``N`` is padded to whole chunks with
    rows of weight 0, and where one chunk holds every row."""
    assert _chunk_rows(n, v, chunk) == rows
    x, w, labels = _inputs(n, n, 16, v)
    ref, gd = jax.value_and_grad(
        lambda x, w: jnp.mean(_dense_loss(x, w, labels)),
        argnums=(0, 1))(x, w)
    got, gc = jax.value_and_grad(
        lambda x, w: fused_linear_cross_entropy(x, w, labels, chunk),
        argnums=(0, 1))(x, w)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    # called without differentiation: the primal, the loss alone
    np.testing.assert_allclose(
        fused_linear_cross_entropy(x, w, labels, chunk), ref,
        rtol=1e-5, atol=1e-5)
    for a, b in zip(gc, gd):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6)


def test_the_cells_chunk_is_2048_rows():
    """``loss_chunk_vocab`` keeps its meaning, the bound on the logits alive
    at once: 8191 rows at 9496 of 37 984 columns are four chunks of 2048."""
    assert _chunk_rows(8191, 37984, 9496) == 2048
    assert 2048 * 37984 <= 8192 * 9496
    assert _chunk_rows(4095, 32000, 0) == 4095      # 0: every row at once


def test_fused_ce_row_weights_from_a_mask():
    """A mask with zeros, divided by its sum, weighs rows as a masked mean
    does; the cotangent of ``row_weights`` is the per-row loss."""
    x, w, labels = _inputs(6, 21, 16, 48)
    mask = jnp.asarray(np.random.default_rng(6).integers(0, 2, size=21),
                       jnp.float32).at[0].set(1.0)
    weights = mask / jnp.sum(mask)

    def dense(x, w, weights):
        return jnp.sum(_dense_loss(x, w, labels) * weights)

    def fused(x, w, weights):
        return fused_linear_cross_entropy(x, w, labels, 16,
                                          row_weights=weights)

    ref, gd = jax.value_and_grad(dense, argnums=(0, 1, 2))(x, w, weights)
    got, gc = jax.value_and_grad(fused, argnums=(0, 1, 2))(x, w, weights)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
    for a, b in zip(gc, gd):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(gc[2], _dense_loss(x, w, labels),
                               rtol=1e-5, atol=1e-5)
    # a row the mask drops moves no gradient
    assert not np.any(np.asarray(gc[0])[np.asarray(mask) == 0])


def test_fused_ce_scaled_cotangent_float16():
    """An fp16 run's loss scale (1024) arrives as the cotangent: it multiplies
    the float32 gradients BEFORE they are rounded to float16, so gradients
    that float16 would hold as a few subnormal steps unscaled come through
    at full precision."""
    n = 24
    x, w, labels = _inputs(7, n, 16, 96, jnp.float16)
    weights = jnp.full((n,), 1e-6, jnp.float32)     # a mean over 1M tokens
    scale = 1024.0

    def fused(x, w):
        return scale * fused_linear_cross_entropy(x, w, labels, 32,
                                                  row_weights=weights)

    gc = jax.grad(fused, argnums=(0, 1))(x, w)
    gd = jax.grad(lambda x, w: jnp.sum(_dense_loss(x, w, labels) * weights),
                  argnums=(0, 1))(x.astype(jnp.float32),
                                  w.astype(jnp.float32))
    for a, b in zip(gc, gd):
        assert a.dtype == jnp.float16 and np.all(np.isfinite(a))
        np.testing.assert_allclose(a.astype(jnp.float32) / scale, b,
                                   rtol=2e-2, atol=1e-9)
        # the same numbers rounded to float16 unscaled: subnormal, coarse
        coarse = np.asarray(b.astype(jnp.float16), np.float32)
        assert np.max(np.abs(coarse - b) / (np.abs(b) + 1e-9)) > 5e-2


def _sub_jaxprs(jaxpr):
    yield jaxpr
    for eqn in jaxpr.eqns:
        for p in eqn.params.values():
            for cand in (p if isinstance(p, (list, tuple)) else (p,)):
                inner = getattr(cand, "jaxpr", None)
                if inner is not None:
                    yield from _sub_jaxprs(getattr(inner, "jaxpr", inner))


def _products_and_sizes(fn, *args):
    """``dot_general``s of the traced program (a loop's body counts once: a
    chunk's) and the largest array any equation makes."""
    dots, largest = 0, 0
    for jaxpr in _sub_jaxprs(jax.make_jaxpr(fn)(*args).jaxpr):
        for eqn in jaxpr.eqns:
            dots += eqn.primitive.name == "dot_general"
            for var in eqn.outvars:
                largest = max(largest, int(np.prod(var.aval.shape)))
    return dots, largest


@pytest.mark.parametrize("chunk,rows", [(64, 8), (128, 16), (512, 56)])
def test_fused_ce_three_products_a_chunk_and_no_full_logits(chunk, rows):
    """The point of the feature: under ``value_and_grad`` a chunk runs
    exactly THREE products (logits, dx, dW: none of them twice), the primal
    alone ONE, and no array of N x V elements exists in either."""
    n, d, v = 56, 16, 512
    x = jnp.zeros((n, d), jnp.float32)
    w = jnp.zeros((d, v), jnp.float32)
    labels = jnp.zeros((n,), jnp.int32)
    assert _chunk_rows(n, v, chunk) == rows

    def f(x, w):
        return fused_linear_cross_entropy(x, w, labels, chunk)

    dots, largest = _products_and_sizes(
        jax.value_and_grad(f, argnums=(0, 1)), x, w)
    assert dots == 3
    # the largest array: a chunk's logits, or dW where that is larger
    assert largest == max(rows * v, d * v)
    dots, largest = _products_and_sizes(f, x, w)
    assert dots == 1 and largest == max(rows * v, n * d)
    if rows < n:
        assert largest < n * v, "full logits materialized"


def test_fused_ce_chunks_take_rows_from_every_shard():
    """Rows sharded over devices (a batch over "dp"): chunk ``c`` holds the
    rows ``c, c + chunks, ...``, so every device computes its own quarter of
    each chunk; a chunk of neighbouring rows would lie on one device and be
    computed by all four."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    n, d, v, chunk = 256, 16, 128, 32           # 4 chunks of 64 rows
    assert _chunk_rows(n, v, chunk) == 64
    x, w, labels = _inputs(10, n, d, v)
    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    rows = NamedSharding(mesh, P("dp"))
    whole = NamedSharding(mesh, P())
    fn = jax.jit(jax.value_and_grad(
        lambda x, w, labels: fused_linear_cross_entropy(x, w, labels, chunk),
        argnums=(0, 1)), in_shardings=(rows, whole, rows),
        out_shardings=(whole, (rows, whole)))
    got, gc = fn(x, w, labels)
    ref, gd = jax.value_and_grad(
        lambda x, w: jnp.mean(_dense_loss(x, w, labels)),
        argnums=(0, 1))(x, w)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    for a, b in zip(gc, gd):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6)
    hlo = fn.lower(x, w, labels).compile().as_text()
    assert "f32[16,128]" in hlo and "f32[64,128]" not in hlo, \
        "a device computes whole chunks"


def test_smallthinker_chunked_loss_parity():
    """A tiny SmallThinker (``perfbench/configs/tiny_smallthinker.json``'s
    sizes) reads the same loss and gradients chunked and dense."""
    from deepspeed_tpu.models import smallthinker

    sizes = dict(vocab_size=256, hidden_size=64, num_hidden_layers=4,
                 num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                 sliding_window_size=16, moe_num_primary_experts=4,
                 moe_num_active_primary_experts=2, moe_ffn_hidden_size=32,
                 rope_theta=100.0, dtype="float32", remat=False)
    m_d = smallthinker.SmallThinkerModel(
        smallthinker.smallthinker_tiny(**sizes))
    m_c = smallthinker.SmallThinkerModel(
        smallthinker.smallthinker_tiny(**sizes, loss_chunk_vocab=64))
    ids = np.random.default_rng(8).integers(
        0, 256, size=(1, 64)).astype(np.int32)
    params = m_d.init(jax.random.PRNGKey(0), ids, ids)["params"]
    loss = lambda m: lambda p: m.apply({"params": p}, ids, ids)[0]
    ld, gd = jax.value_and_grad(loss(m_d))(params)
    lc, gc = jax.value_and_grad(loss(m_c))(params)
    np.testing.assert_allclose(lc, ld, rtol=1e-5, atol=1e-5)
    _assert_trees_close(gc, gd)


def test_llama_chunked_loss_masked_parity():
    """Model-level, with an attention mask that has zeros: the mask and its
    denominator become the rows' weights."""
    from deepspeed_tpu.models import llama

    base = llama.llama_tiny(dtype="float32", remat=False)
    cfg_c = llama.LlamaConfig(**{**base.__dict__, "loss_chunk_vocab": 16})
    rng = np.random.default_rng(9)
    ids = rng.integers(0, base.vocab_size, size=(2, 16)).astype(np.int32)
    mask = np.ones((2, 16), np.int32)
    mask[0, 11:] = 0
    mask[1, 5:] = 0
    m_d, m_c = llama.LlamaModel(base), llama.LlamaModel(cfg_c)
    params = m_d.init(jax.random.PRNGKey(0), ids, ids)["params"]
    loss = lambda m: lambda p: m.apply({"params": p}, ids, ids, mask)
    ld, gd = jax.value_and_grad(loss(m_d))(params)
    lc, gc = jax.value_and_grad(loss(m_c))(params)
    np.testing.assert_allclose(lc, ld, rtol=1e-5, atol=1e-5)
    _assert_trees_close(gc, gd)


def test_llama_chunked_loss_parity():
    """Model-level: loss_chunk_vocab path == dense path on the same params
    (same param tree layout, so the same init works for both)."""
    from deepspeed_tpu.models import llama

    base = llama.llama_tiny(dtype="float32", remat=False)
    cfg_d = base
    cfg_c = llama.LlamaConfig(
        **{**base.__dict__, "loss_chunk_vocab": max(16, base.vocab_size // 4)})
    rng = np.random.default_rng(2)
    ids = rng.integers(0, base.vocab_size, size=(2, 16)).astype(np.int32)

    m_d = llama.LlamaModel(cfg_d)
    m_c = llama.LlamaModel(cfg_c)
    params = m_d.init(jax.random.PRNGKey(0), ids, ids)["params"]
    # identical param trees (lm_head/{kernel} layout preserved)
    pc = m_c.init(jax.random.PRNGKey(0), ids, ids)["params"]
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(pc))

    ld = m_d.apply({"params": params}, ids, ids)
    lc = m_c.apply({"params": params}, ids, ids)
    np.testing.assert_allclose(lc, ld, rtol=1e-5, atol=1e-5)

    gd = jax.grad(lambda p: m_d.apply({"params": p}, ids, ids))(params)
    gc = jax.grad(lambda p: m_c.apply({"params": p}, ids, ids))(params)
    _assert_trees_close(gc, gd)


def test_llama_chunked_loss_tied_embeddings():
    from deepspeed_tpu.models import llama

    base = llama.llama_tiny(dtype="float32", remat=False)
    kw = {**base.__dict__, "tie_word_embeddings": True}
    cfg_d = llama.LlamaConfig(**kw)
    cfg_c = llama.LlamaConfig(**{**kw, "loss_chunk_vocab": 16})
    rng = np.random.default_rng(3)
    ids = rng.integers(0, base.vocab_size, size=(2, 12)).astype(np.int32)
    m_d = llama.LlamaModel(cfg_d)
    m_c = llama.LlamaModel(cfg_c)
    params = m_d.init(jax.random.PRNGKey(0), ids, ids)["params"]
    ld = m_d.apply({"params": params}, ids, ids)
    lc = m_c.apply({"params": params}, ids, ids)
    np.testing.assert_allclose(lc, ld, rtol=1e-5, atol=1e-5)


def test_gpt2_chunked_loss_parity():
    from deepspeed_tpu.models import gpt2

    base = gpt2.gpt2_tiny(dtype="float32", remat=False)
    cfg_c = gpt2.GPT2Config(**{**base.__dict__, "loss_chunk_vocab": 64})
    rng = np.random.default_rng(4)
    ids = rng.integers(0, base.vocab_size, size=(2, 16)).astype(np.int32)
    m_d = gpt2.GPT2Model(base)
    m_c = gpt2.GPT2Model(cfg_c)
    params = m_d.init(jax.random.PRNGKey(0), ids, ids)["params"]
    ld = m_d.apply({"params": params}, ids, ids)
    lc = m_c.apply({"params": params}, ids, ids)
    np.testing.assert_allclose(lc, ld, rtol=1e-5, atol=1e-5)


def test_mixtral_chunked_loss_parity():
    from deepspeed_tpu.models import mixtral

    base = mixtral.mixtral_tiny(dtype="float32", remat=False)
    cfg_c = mixtral.MixtralConfig(**{**base.__dict__, "loss_chunk_vocab": 32})
    rng = np.random.default_rng(5)
    ids = rng.integers(0, base.vocab_size, size=(2, 16)).astype(np.int32)
    m_d = mixtral.MixtralModel(base)
    m_c = mixtral.MixtralModel(cfg_c)
    params = m_d.init(jax.random.PRNGKey(0), ids, ids)["params"]
    ld = m_d.apply({"params": params}, ids, ids)
    lc = m_c.apply({"params": params}, ids, ids)
    np.testing.assert_allclose(lc, ld, rtol=1e-5, atol=1e-5)


def test_chunked_loss_composes_with_zero3_tp():
    """loss_chunk_vocab under ZeRO-3 × tp2 on the 8-device mesh — the
    scanned head must shard (AutoTP dataflow rules derive through the
    scan) and train without involuntary gathers blowing up."""
    import deepspeed_tpu
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.utils import groups
    import deepspeed_tpu.comm as dist

    cfg = llama.llama_tiny(dtype="bfloat16", remat=False,
                           loss_chunk_vocab=32)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=llama.LlamaModel(cfg),
        config={"train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "fusedadam", "params": {"lr": 1e-3}},
                "bf16": {"enabled": True},
                "zero_optimization": {"stage": 3},
                "mesh": {"dp": 4, "sp": 1, "tp": 2}})
    rows = 2 * engine.dp_world_size
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(rows, 32)).astype(np.int32)
    engine.initialize_parameters(0, ids, ids)
    losses = []
    for _ in range(3):
        loss = engine(ids, ids)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    assert np.isfinite(losses[-1]) and losses[-1] < losses[0]
    groups.reset_mesh()
    dist.destroy_process_group()


def test_gpt2_chunked_loss_fp16_zero1_engine():
    """The gpt2 on-chip sweep-leg combination at tiny scale: fp16 dynamic
    loss scaling + ZeRO-1 + chunked CE must compile and train (the scaled
    loss flows through the scanned head's backward)."""
    import deepspeed_tpu
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.utils import groups
    import deepspeed_tpu.comm as dist

    cfg = gpt2.gpt2_tiny(dtype="float16", remat=False, loss_chunk_vocab=64)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=gpt2.GPT2Model(cfg),
        config={"train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "fusedadam", "params": {"lr": 1e-3}},
                "fp16": {"enabled": True, "initial_scale_power": 8},
                "zero_optimization": {"stage": 1},
                "mesh": {"dp": 8}})
    rows = 2 * engine.dp_world_size
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(rows, 24)).astype(np.int32)
    engine.initialize_parameters(0, ids, ids)
    losses = []
    for _ in range(4):
        loss = engine(ids, ids)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    assert np.isfinite(losses[-1]) and losses[-1] < losses[0]
    groups.reset_mesh()
    dist.destroy_process_group()
