"""Pipeline × Ulysses sequence parallelism (BASELINE.json config 5 shape:
PP + ZeRO-1 + SP).  The Ulysses a2a shard_map must nest inside the fused
pipeline's partial-manual region by targeting the CONTEXT abstract mesh —
and sp must be a pure layout choice: identical trajectory to the same model
at sp=1 (where DistributedAttention reduces to local attention)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import flax.linen as nn

import deepspeed_tpu
from deepspeed_tpu.runtime.pipe import LayerSpec, PipelineModule
from deepspeed_tpu.utils import groups
import deepspeed_tpu.comm as dist

D, VOCAB, S, H = 32, 128, 32, 4


class Embed(nn.Module):
    @nn.compact
    def __call__(self, ids):
        return nn.Embed(VOCAB, D)(ids)


class UlyssesBlock(nn.Module):
    @nn.compact
    def __call__(self, x):
        from deepspeed_tpu.sequence.layer import DistributedAttention
        B, S_, _ = x.shape
        qkv = nn.DenseGeneral(features=(3, H, D // H))(x)
        out = DistributedAttention()(qkv[:, :, 0], qkv[:, :, 1],
                                     qkv[:, :, 2], causal=True)
        out = out.reshape(B, S_, D)
        h = nn.Dense(4 * D)(out + x)
        return x + nn.Dense(D)(jnp.tanh(h))


class Head(nn.Module):
    @nn.compact
    def __call__(self, x):
        return nn.Dense(VOCAB)(x)


def xent(logits, labels):
    lp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.mean(jnp.take_along_axis(lp, labels[..., None], -1))


def _run(sp):
    groups.reset_mesh()
    dist.destroy_process_group()
    model = PipelineModule(
        layers=[LayerSpec(Embed)] + [LayerSpec(UlyssesBlock)
                                     for _ in range(2)] +
        [LayerSpec(Head)], loss_fn=xent)
    # CONSTANT global batch across sp values (sp takes devices from dp, so
    # the per-dp-rank micro batch must grow to keep the data stream equal):
    # 8 devices, pp=2 → dp = 4/sp; bs = 8 rows either way.
    bs = 8
    dp = 4 // sp
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        config={"train_micro_batch_size_per_gpu": bs // dp,
                "gradient_accumulation_steps": 2,
                "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 1},
                "mesh": {"pp": 2, "sp": sp, "dp": -1}})
    assert engine.dp_world_size == dp
    rng = np.random.default_rng(0)
    ids = rng.integers(0, VOCAB, size=(bs, S)).astype(np.int32)
    engine.initialize_parameters(0, ids, ids)

    def gen():
        while True:
            x = rng.integers(0, VOCAB, size=(bs, S)).astype(np.int32)
            yield (x, x)

    it = gen()
    losses = [float(engine.train_batch(it)) for _ in range(3)]
    groups.reset_mesh()
    dist.destroy_process_group()
    return losses


def test_pipeline_ulysses_sp_parity():
    sp2 = _run(sp=2)
    sp1 = _run(sp=1)
    np.testing.assert_allclose(sp2, sp1, rtol=2e-4, atol=1e-5)
    assert sp2[-1] < sp2[0]
