"""Atom-tiled prefill layout: builder invariants, kernel parity, and
end-to-end greedy parity with the flat layout (reference atom_builder)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import llama
from deepspeed_tpu.inference.v2 import InferenceEngineV2


def _engine(atom, n_blocks=40, budget=64):
    cfg = llama.llama_tiny(dtype="float32", remat=False)
    model = llama.LlamaModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    sm = dict(max_tracked_sequences=8, max_ragged_batch_size=budget,
              max_ragged_sequence_count=8, max_context=128,
              block_size=16, num_blocks=n_blocks, prefill_atom_size=atom)
    return cfg, InferenceEngineV2(model, params=params,
                                  config=dict(dtype="float32",
                                              state_manager=sm))


def test_builder_atom_alignment():
    cfg, eng = _engine(atom=8)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 96, size=n).tolist() for n in (20, 5, 1)]
    eng.put(range(3), prompts)
    batch = eng._build_batch()
    toks, pos, slots, last_idx, finishing, layout = batch
    decode_cap, atom = layout
    assert atom == 8 and decode_cap == 8  # min(max_seq_count, budget//2)
    # every atom tile in the prefill region holds at most one sequence
    region = slots[decode_cap:]
    for i in range(0, len(region), atom):
        tile = region[i:i + atom]
        live = tile[tile != 0]
        assert len(set(live.tolist())) <= 1, tile
    eng.flush(range(3))


def test_decode_heavy_keeps_flat_layout():
    cfg, eng = _engine(atom=8)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 96, size=6).tolist() for _ in range(4)]
    out = eng.generate(prompts, max_new_tokens=4)
    assert all(len(o) == 4 for o in out)
    # now all sequences are decoding (1 pending each) → flat layout
    eng.put(range(4), [[1]] * 4)
    assert eng._pick_layout() == (0, 0)
    eng.flush(range(4))


_GEN_SNIPPET = """
import os
import jax
jax.config.update("jax_platforms", "cpu")
# share the suite's persistent compile cache — a cold subprocess would
# otherwise recompile for minutes
jax.config.update("jax_compilation_cache_dir",
                  os.environ.get("DS_TPU_TEST_CACHE",
                                 os.path.join("tests", ".jax_cache")))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
import numpy as np
from tests.unit.inference.test_atom_prefill import _engine
rng = np.random.default_rng(2)
prompts = [rng.integers(0, 96, size=n).tolist() for n in (23, 9, 2, 17)]
outs = []
for atom in (0, 8):
    cfg, eng = _engine(atom=atom)
    outs.append(eng.generate(prompts, max_new_tokens=6))
    eng.flush(range(len(prompts)))
assert outs[0] == outs[1], (outs[0], outs[1])
print("ATOM_PARITY_OK", outs[0])
"""


def test_atom_generate_matches_flat_xla():
    """Greedy generation identical with atoms on/off through the XLA
    fallback — in-process (the suite's default env has no interpret gate,
    so no subprocess boot is needed for this leg)."""
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 96, size=n).tolist() for n in (23, 9, 2, 17)]
    outs = []
    for atom in (0, 8):
        cfg, eng = _engine(atom=atom)
        outs.append(eng.generate(prompts, max_new_tokens=6))
        eng.flush(range(len(prompts)))
    assert outs[0] == outs[1], (outs[0], outs[1])


def test_atom_generate_matches_flat_pallas_interpret():
    """Same A/B through the real Pallas kernels (interpret mode).  The
    interpret-mode env gate is read at trace time, so this variant runs in
    a fresh subprocess — flipping it in-process would poison the suite's
    jit caches."""
    import subprocess
    import sys
    repo = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        "..", "..", ".."))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env["DS_TPU_FORCE_PALLAS"] = "1"
    proc = subprocess.run([sys.executable, "-c", _GEN_SNIPPET], env=env,
                          capture_output=True, text=True, timeout=300,
                          cwd=repo)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "ATOM_PARITY_OK" in proc.stdout


def test_decode_overflow_does_not_collide():
    """Decode tokens beyond the decode region spill into atom tiles without
    overwriting each other (regression: boundary token advanced d_cur)."""
    cfg = llama.llama_tiny(dtype="float32", remat=False)
    model = llama.LlamaModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    sm = dict(max_tracked_sequences=16, max_ragged_batch_size=16,
              max_ragged_sequence_count=16, max_context=64,
              block_size=16, num_blocks=60, prefill_atom_size=8)
    eng = InferenceEngineV2(model, params=params,
                            config=dict(dtype="float32", state_manager=sm))
    rng = np.random.default_rng(3)
    # 10 decoding sequences (decode region only fits budget//2 = 8) + one
    # long prefill so the atom layout is chosen
    uids = list(range(11))
    eng.put(uids[:10], [[int(t)] for t in rng.integers(1, 96, size=10)])
    eng.put([10], [rng.integers(1, 96, size=12).tolist()])
    before = {u: eng.state_manager.get_sequence(u).seen_tokens
              for u in uids}
    batch = eng._build_batch()
    toks, pos, slots, last_idx, finishing, layout = batch
    decode_cap, atom = layout
    assert atom > 0
    placed = sum(eng.state_manager.get_sequence(u).seen_tokens - before[u]
                 for u in uids)
    live = int((slots != 0).sum())
    # an overwrite would lose a row: every scheduled token must own one
    assert live == placed, (decode_cap, placed, slots.tolist())
    eng.flush(uids)


def test_atom_kernel_matches_per_token():
    """Direct kernel parity (interpret mode) incl. GQA and intra-atom pads."""
    from deepspeed_tpu.ops.pallas.paged_attention import (
        paged_attention, paged_attention_atoms)
    bs, Hkv, H, Dh, nb = 8, 2, 4, 16, 10
    rng = np.random.default_rng(0)
    kc = jnp.asarray(rng.standard_normal((nb, bs, Hkv, Dh)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((nb, bs, Hkv, Dh)), jnp.float32)
    atom, T = 4, 12
    q = jnp.asarray(rng.standard_normal((T, H, Dh)), jnp.float32)
    tables = np.zeros((T, 5), np.int32)
    tables[:8] = [1, 2, 3, 0, 0]
    tables[8:] = [4, 5, 0, 0, 0]
    pos = np.array([8, 9, 10, 11, 12, 13, 0, 0, 0, 1, 2, 3], np.int32)
    out_atom = paged_attention_atoms(q, kc, vc, jnp.asarray(tables),
                                     jnp.asarray(pos), atom)
    # the flat kernel takes the table by slot: rows 0-7 are slot 1's,
    # rows 8-11 slot 2's (the pads too: they are masked out below)
    out_tok = paged_attention(
        q, kc, vc, jnp.asarray(tables[[0, 0, 8]]),
        jnp.asarray([1] * 8 + [2] * 4, jnp.int32), jnp.asarray(pos))
    real = np.ones(T, bool)
    real[6:8] = False  # intra-atom pads
    np.testing.assert_allclose(np.asarray(out_atom)[real],
                               np.asarray(out_tok)[real], atol=1e-5)


@pytest.mark.parametrize("atom", [1, 4])
def test_paged_kernel_sliding_window(atom):
    """Windowed paged attention (Mistral serving) matches the XLA gather
    fallback, per-token and atom-tiled."""
    from deepspeed_tpu.inference.v2.ragged_forward import _paged_attention
    from deepspeed_tpu.ops.pallas.paged_attention import (
        paged_attention_atoms)
    bs, Hkv, H, Dh, nb = 8, 2, 4, 16, 12
    rng = np.random.default_rng(7)
    kc = jnp.asarray(rng.standard_normal((nb, bs, Hkv, Dh)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((nb, bs, Hkv, Dh)), jnp.float32)
    T, W = 8, 11
    q = jnp.asarray(rng.standard_normal((T, H, Dh)), jnp.float32)
    tables = np.zeros((T, 6), np.int32)
    tables[:] = [1, 2, 3, 4, 5, 0]          # one sequence, positions 28..35
    pos = np.arange(28, 36).astype(np.int32)
    out_k = paged_attention_atoms(q, kc, vc, jnp.asarray(tables),
                                  jnp.asarray(pos), atom, window=W)
    ref = _paged_attention(q, kc, vc, jnp.asarray(tables[:2]),
                           jnp.ones(T, jnp.int32), jnp.asarray(pos),
                           block_size=bs, window=W)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_prefill_overflow_uses_free_decode_rows():
    """When the prefill region fills, remaining work advances through spare
    decode rows instead of being skipped (round-2 advisor finding)."""
    cfg, eng = _engine(atom=8, budget=32)  # decode_cap=8, prefill=24
    rng = np.random.default_rng(5)
    # three long prompts: 24-token prefill region fits at most 24 tokens;
    # no decoding sequences, so all 8 decode rows are spare
    uids = [0, 1, 2]
    eng.put(uids, [rng.integers(1, 96, size=20).tolist() for _ in uids])
    before = {u: eng.state_manager.get_sequence(u).seen_tokens for u in uids}
    batch = eng._build_batch()
    toks, pos, slots, last_idx, finishing, layout = batch
    decode_cap, atom = layout
    assert atom > 0
    placed = sum(eng.state_manager.get_sequence(u).seen_tokens - before[u]
                 for u in uids)
    # one 20-token prompt fills the 24-slot prefill region (3 atom tiles
    # with pads); the other two sequences each advance 1 token through
    # spare decode rows instead of being skipped
    assert placed == 22, (placed, slots.tolist())
    assert int((slots[:decode_cap] != 0).sum()) == 2
    eng.flush(uids)
