"""The paged cache as one buffer a layer for K and one for V (ISSUE 28).

(a) the compiled step programs alias every cache buffer input to output and
    hold less temporary memory than ONE layer's K pages: no layer is taken
    out of, or written back into, a larger array;
(c) every ``RAGGED_FORWARDS`` entry gives, through slabs, single tokens and a
    burst, fp and int8, the logits and the cache contents of the parent's
    storage rebuilt around the same step: ONE array ``[L, 2, num_blocks, bs,
    Hkv, Dh]`` a layer is sliced out of and stacked back into.
"""

import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2 import ragged_forward as rf
from deepspeed_tpu.inference.v2.ragged import BlockedKVCache
from deepspeed_tpu.models import (cohere2_moe, evabyte, falcon, jamba, llama,
                                  longcat_flash, mixtral, motif, opt, ouro,
                                  pangu_ultra_moe, phi, qwen3_next)

_spec = importlib.util.spec_from_file_location(
    "serve_hlo_check", os.path.join(
        os.path.dirname(__file__), "..", "..", "..", "tools",
        "serve_hlo_check.py"))
hlo_check = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(hlo_check)

STATICS = ("cfg", "block_size", "use_kernel", "kv_dtype")
ZOO = {
    "LlamaModel": (llama.LlamaModel, lambda: llama.llama_tiny(
        dtype="float32", remat=False, num_key_value_heads=2)),
    "MixtralModel": (mixtral.MixtralModel, lambda: mixtral.mixtral_tiny(
        dtype="float32", remat=False)),
    "FalconModel": (falcon.FalconModel, lambda: falcon.falcon_tiny(
        dtype="float32", remat=False)),
    "OPTModel": (opt.OPTModel, lambda: opt.opt_tiny(
        dtype="float32", remat=False)),
    "PhiModel": (phi.PhiModel, lambda: phi.phi_tiny(
        dtype="float32", remat=False)),
    "EvaByteModel": (evabyte.EvaByteModel, lambda: evabyte.evabyte_tiny(
        dtype="float32")),
    "Cohere2MoeModel": (cohere2_moe.Cohere2MoeModel,
                        cohere2_moe.cohere2_moe_tiny),
    "PanguUltraMoeModel": (pangu_ultra_moe.PanguUltraMoeModel,
                           pangu_ultra_moe.pangu_ultra_moe_tiny),
    "JambaModel": (jamba.JambaModel, jamba.jamba_tiny),
    "LongcatFlashModel": (longcat_flash.LongcatFlashModel,
                          longcat_flash.longcat_flash_tiny),
    "OuroModel": (ouro.OuroModel, lambda: ouro.ouro_tiny(dtype="float32")),
    "MotifModel": (motif.MotifModel, lambda: motif.motif_tiny(
        num_hidden_layers=4, n_dense_first_layers=1)),
    "Qwen3NextModel": (qwen3_next.Qwen3NextModel,
                       lambda: qwen3_next.qwen3_next_tiny(
                           num_hidden_layers=4)),
}
#: the models whose cache is ONE latent buffer a layer: it never was a K and
#: a V in one stacked array, so (c) has nothing to rebuild for them
LATENT = ("PanguUltraMoeModel", "LongcatFlashModel", "MotifModel")
#: the models whose cache holds entries of two kinds (pages, and state rows
#: a sequence slot): no stacked array ever held them either
RECURRENT = ("JambaModel", "Qwen3NextModel")
#: the models whose stack runs several times a token: the passes' entries of
#: one layer share ONE K and ONE V buffer, pass-major, which no stacked array
#: of a layer's K and V ever held (tests/unit/inference/test_ouro.py holds
#: every entry to the reference's K and V)
LOOPED = ("OuroModel", )


# a recording step and a storage-rebuilding step wrap the engine's step
pytestmark = pytest.mark.usefixtures("no_disk_cache")


def _model(name):
    cls, tiny = ZOO[name]
    model = cls(tiny())
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


# ------------------------------------------- (a) aliased, and no layer copy
def _programs(name, num_blocks=2048):
    """Lowered ``(step, burst)`` of ``name`` at a shape whose cache (a
    layer's K pages: 2048 x 8 x Hkv x Dh floats) is far larger than its
    activations and than the context the CPU path gathers (8 rows x 2
    blocks of a block-table row)."""
    model, params = _model(name)
    cfg = model.config
    eva = name == "EvaByteModel"
    bs, rows, seqs, maxb = 8, 8, 4, 6 if eva else 2
    cache = BlockedKVCache(
        BlockedKVCache.entries_of(cfg), num_blocks, bs,
        cfg.num_key_value_heads,
        getattr(cfg, "head_dim", 0), dtype=jnp.float32,
        window_size=cfg.window_size if eva else 0,
        chunk_size=cfg.chunk_size if eva else 0,
        latent_dim=getattr(cfg, "kv_latent_dim", 0)).layers
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)
    step_fn = rf.RAGGED_FORWARDS[name]
    kw = dict(cfg=cfg, block_size=bs)
    step = step_fn.lower(params, cache, i32(rows), i32(rows), i32(rows),
                         i32(seqs, maxb), i32(seqs), **kw)
    burst = rf.decode_burst.lower(
        params, cache, i32(seqs), i32(seqs), jnp.ones(seqs, bool),
        i32(seqs, maxb), step_fn=step_fn, k=4, **kw)
    page = cache[0][0]
    return (step, burst), len(jax.tree.leaves(params)), \
        len(jax.tree.leaves(cache)), page.size * page.dtype.itemsize


@pytest.mark.parametrize("name,which", [
    ("LlamaModel", 0), ("EvaByteModel", 0), ("LlamaModel", 1),
    ("EvaByteModel", 1), ("PanguUltraMoeModel", 0),
    ("PanguUltraMoeModel", 1), ("LongcatFlashModel", 0),
    ("LongcatFlashModel", 1)], ids=[
        "llama_step", "evabyte_step", "llama_burst", "evabyte_burst",
        "latent_step", "latent_burst", "two_entries_a_layer_step",
        "two_entries_a_layer_burst"])
def test_compiled_program_updates_the_cache_in_place(name, which):
    lowered, n_params, n_cache, page_bytes = _programs(name)
    compiled = lowered[which].compile()
    text = compiled.as_text()
    cache = set(range(n_params, n_params + n_cache))
    assert cache <= hlo_check.aliased_parameters(text)
    ma = compiled.memory_analysis()
    if ma is not None:
        # the parent's step took a layer [2, ...] out as a value: 2 pages
        assert ma.temp_size_in_bytes < page_bytes, \
            (ma.temp_size_in_bytes, page_bytes)
    else:
        moved = [v for v in hlo_check.page_sized_values(text, page_bytes)
                 if v[1] not in ("scatter", "fusion")]
        assert not moved, moved[:4]


def test_comparable_hlo_drops_what_names_the_source_and_nothing_else():
    """``--dump``: two checkouts whose programs are the same give the same
    text, whatever their paths and line numbers; another shape does not."""
    def hlo(path, line, body, shape="bf16[8,128]"):
        return "\n".join([
            "HloModule jit_step, input_output_alias={ {0}: (1, {}, may-alias) }",
            "", "FileNames", f'1 "{path}/ragged_forward.py"', "",
            "FileLocations", f"1 {{file_name_id=1 line={line}}}", "",
            "ENTRY %main (p0: s32[4], p1: bf16[8,128]) -> bf16[8,128] {",
            f"  %c = {shape} custom-call(%p1), custom_call_target="
            f'"tpu_custom_call", backend_config={{"custom_call_config": '
            f'{{"body": "{body}", "needs_hlo_passes": true}}}}, '
            f'metadata={{op_name="jit(step)/x" stack_frame_id={line}}}',
            f"  ROOT %r = {shape} add(%c, %c), metadata={{op_name=\"y\"}}",
            "}"])

    a = hlo_check.comparable(hlo("/root/parent", 301, "TUxJUg=="))
    assert a == hlo_check.comparable(hlo("/root/repo", 317, "TUxJUnh4"))
    assert "parent" not in a and "301" not in a and "TUxJ" not in a
    assert "needs_hlo_passes" in a and "ROOT %r = bf16[8,128] add" in a
    assert a != hlo_check.comparable(
        hlo("/root/repo", 317, "TUxJUnh4", shape="bf16[8,256]"))


def test_hlo_reader_finds_a_copy_of_a_layer_and_knows_a_scatter():
    text = """HloModule jit_x, input_output_alias={ {1}: (3, {}, may-alias), {2}: (4, {}, may-alias) }, entry_computation_layout={()->()}

%fused_computation.1 (p: bf16[560,128,8,128], i: s32[65]) -> bf16[560,128,8,128] {
  %p = bf16[560,128,8,128]{3,2,1,0} parameter(0)
  ROOT %scatter.1 = bf16[560,128,8,128]{3,2,1,0} scatter(%p, %i, %u), to_apply=%r
}

%fused_computation.2 (p: bf16[16,2,560,128,8,128]) -> bf16[2,560,128,8,128] {
  ROOT %ds.1 = bf16[2,560,128,8,128]{4,3,2,1,0} dynamic-slice(%p, %c), dynamic_slice_sizes={1,2,560,128,8,128}
}

ENTRY %main.1 (a: bf16[560,128,8,128]) -> bf16[560,128,8,128] {
  %a = bf16[560,128,8,128]{3,2,1,0} parameter(0)
  %fusion.1 = bf16[560,128,8,128]{3,2,1,0} fusion(%a, %i), kind=kInput, calls=%fused_computation.1
  %fusion.2 = bf16[2,560,128,8,128]{4,3,2,1,0} fusion(%kv), kind=kLoop, calls=%fused_computation.2
  %copy-start.1 = (bf16[560,128,8,128]{3,2,1,0}, bf16[560,128,8,128]{3,2,1,0}, u32[]) copy-start(%a)
  %small = bf16[768,8,128]{2,1,0} copy(%k)
  ROOT %t = (bf16[560,128,8,128]{3,2,1,0}) tuple(%fusion.1)
}
"""
    assert hlo_check.aliased_parameters(text) == {3, 4}
    page = 560 * 128 * 8 * 128 * 2
    found = hlo_check.page_sized_values(text, page)
    assert [(op, name) for _, op, _, name in found] == [
        ("fusion", "%fusion.1"), ("fusion", "%fusion.2"),
        ("copy-start", "%copy-start.1")]
    assert hlo_check.in_place_scatter(text, "%fusion.1")
    assert not hlo_check.in_place_scatter(text, "%fusion.2")


# --------------------- (c) the same logits and cache as the parent's storage
def _stack(kv):
    """Per-layer buffers -> the parent's ``[L, 2, ...]`` array (with scales,
    the pair of arrays)."""
    parts = [jnp.stack([jnp.stack(layer[i:i + 2]) for layer in kv])
             for i in range(0, len(kv[0]), 2)]
    return parts[0] if len(parts) == 1 else tuple(parts)


def _stacked_storage(engine):
    """Serve from the parent's storage: a layer is sliced out of ONE array
    as a value, goes through the step, and is stacked back."""
    inner = engine._step_fn.__wrapped__

    def step(params, kv, *args, **kw):
        arrays = kv if isinstance(kv, tuple) else (kv, )
        layers = tuple(tuple(a[l, i] for a in arrays for i in (0, 1))
                       for l in range(arrays[0].shape[0]))
        logits, layers, *counted = inner(params, layers, *args, **kw)
        return (logits, _stack(layers), *counted)

    engine._kv = _stack(engine._kv)
    engine._step_fn = jax.jit(step, static_argnames=STATICS,
                              donate_argnums=(1, ))


def _record_logits(engine):
    inner, sink = engine._step_fn, []

    def step(*args, **kw):
        logits, *rest = inner(*args, **kw)   # kv and, of some, their counts
        sink.append(np.asarray(logits))
        return (logits, *rest)

    step.__wrapped__ = inner.__wrapped__
    engine._step_fn = step
    return sink


def _engine(name, kv_dtype):
    model, params = _model(name)
    sm = dict(max_tracked_sequences=8, max_ragged_batch_size=12,
              max_ragged_sequence_count=5, max_context=96, block_size=8,
              num_blocks=40)
    return InferenceEngineV2(model, params=params, config=dict(
        dtype="float32", decode_burst=4, kv_cache_dtype=kv_dtype,
        state_manager=sm))


@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["fp", "int8"])
@pytest.mark.parametrize("name", list(rf.RAGGED_FORWARDS))
def test_layout_serves_what_the_stacked_array_served(name, kv_dtype):
    if name in ("EvaByteModel", ) + LATENT + RECURRENT + LOOPED \
            and kv_dtype is not None:
        with pytest.raises(NotImplementedError, match="kv_cache_dtype"):
            _engine(name, kv_dtype)
        return
    if name in LATENT:
        eng = _engine(name, kv_dtype)
        cfg = eng.model_config      # one buffer an ENTRY (two a layer
        # where the model states kv_cache_entries)
        assert [len(layer) for layer in eng._kv] == \
            [1] * BlockedKVCache.entries_of(cfg)
        assert eng._kv[0][0].shape == (40, 8, 128)
        return
    if name in LOOPED:
        eng = _engine(name, kv_dtype)
        cfg = eng.model_config      # a K and a V buffer a LAYER, each the
        # pages of all its passes
        assert (len(eng._kv), cfg.kv_cache_entries) == (2, 6)
        assert [leaf.shape for leaf in eng._kv[0]] == [(3 * 40, 8, 4, 16)] * 2
        assert eng.kv_cache.page_layers == 6
        return
    if name in RECURRENT:
        eng = _engine(name, kv_dtype)
        kinds = eng.model_config.layer_kinds
        assert eng.kv_cache.kinds == kinds and kinds.count("pages") == 1
        # a state entry as the model states it: Jamba's vector state a
        # channel, Qwen3-Next's float32 matrix a value head
        heads = eng.model_config.num_key_value_heads
        state = eng.model_config.recurrent_state
        for entry, kind in zip(eng._kv, kinds):
            shapes = [leaf.shape for leaf in entry]
            assert shapes == ([(40, 8, heads, 16)] * 2 if kind == "pages" else
                              [(3, 5, 128), (5, ) + tuple(state["ssm"])]), \
                (kind, shapes)
        return
    rng = np.random.default_rng(28)
    vocab = ZOO[name][1]().vocab_size
    # 29 + 12 tokens cross EvaByte's first window's end (32); a budget of
    # 12 rows cuts the prompts into slabs (single tokens of the short prompt
    # beside them), then bursts: since ISSUE 55 every decode-only turn is one
    prompts = [rng.integers(1, vocab, size=n).tolist() for n in (29, 5, 17)]
    runs = []
    for stacked in (False, True):
        eng = _engine(name, kv_dtype)
        if stacked:
            _stacked_storage(eng)
        logits = _record_logits(eng)
        toks = eng.generate(prompts, max_new_tokens=12)
        kv = eng._kv if stacked else _stack(eng._kv)
        runs.append((logits, toks, jax.tree.map(np.asarray, kv),
                     getattr(eng, "burst_steps", 0)))
    (logits, toks, kv, bursts), (logits_s, toks_s, kv_s, bursts_s) = runs
    assert bursts == bursts_s >= 1
    assert len(logits) == len(logits_s) >= 5       # slabs and single tokens
    for got, want in zip(logits, logits_s):
        np.testing.assert_array_equal(got, want)
    assert toks == toks_s
    for got, want in zip(jax.tree.leaves(kv), jax.tree.leaves(kv_s)):
        np.testing.assert_array_equal(got, want)
        assert np.abs(got.astype(np.float32)).sum() > 0
