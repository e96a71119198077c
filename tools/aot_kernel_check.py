#!/usr/bin/env python
"""AOT compile-check the Pallas kernel suite for a TPU target — the sandbox's
Mosaic gate.

The CPU test suite runs Pallas kernels in interpreter mode, so a Mosaic-only
lowering error (bad block shape, unsupported op, layout mismatch) would
otherwise surface on the chip.  With libtpu installed,
``jax.experimental.topologies.get_topology_desc`` describes a v5e with no chip
attached, and each kernel compiles ahead of time against it.

It says a kernel COMPILES for ``TPU v5 lite``.  It says nothing about whether
it runs, how fast, or whether the result is right.

Prints one PASS/FAIL line per kernel; exit 0 only if all pass, 3 if no TPU
topology can be described here (no libtpu).  ``tests/unit/ops/
test_aot_kernel_check.py`` runs it in tier-1.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# This process compiles for a TPU while its own backend is the CPU: pin the
# host accelerator, and force Mosaic (not interpreted) kernels — the kernel
# modules read the flag when they are imported.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["DS_ACCELERATOR"] = "cpu"
os.environ["DS_TPU_PALLAS_INTERPRET"] = "0"

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

NO_TOPOLOGY_RC = 3
TOPOLOGY = "v5e:2x2"


def check(name, fn, *args):
    try:
        jax.jit(fn).lower(*args).compile()
        return name, "PASS", ""
    except Exception as e:   # the report IS the failure, one line per kernel
        return name, "FAIL", f"{type(e).__name__}: {str(e)[:300]}"


def main():
    try:
        topo = topologies.get_topology_desc(TOPOLOGY, platform="tpu")
    except Exception as e:
        print(f"no TPU topology can be described here: "
              f"{type(e).__name__}: {e}")
        return NO_TOPOLOGY_RC
    mesh = Mesh(np.array(topo.devices[:1]), ("x", ))
    kind = topo.devices[0].device_kind

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P()))

    bf16 = jnp.bfloat16
    B, S, H, D = 2, 1024, 8, 128
    results = []

    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    q = sds((B, S, H, D), bf16)
    kv = sds((B, S, 2, D), bf16)
    results.append(check(
        "flash_attention(MHA causal)",
        lambda q, k, v: flash_attention(q, k, v, causal=True), q, q, q))
    results.append(check(
        "flash_attention(GQA window)",
        lambda q, k, v: flash_attention(q, k, v, causal=True, window=256),
        q, kv, kv))
    # the training shape: backward (dq; dk+dv) at D=128, blocks 512/512
    q2 = sds((2, 2048, H, D), bf16)
    results.append(check(
        "flash_attention(grad, S=2048 512/512)",
        jax.grad(lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=512, block_k=512
        ).astype(jnp.float32).sum(), argnums=(0, 1, 2)), q2, q2, q2))

    from deepspeed_tpu.ops.pallas.flash_bias import flash_attention_bias
    bias = sds((B, H, S, S), bf16)
    results.append(check(
        "flash_bias(evoformer)",
        lambda q, k, v, b: flash_attention_bias(q, k, v, bias=b),
        q, q, q, bias))

    from deepspeed_tpu.ops.pallas.optimizers import (fused_adam_step,
                                                     fused_lamb_step,
                                                     fused_lion_step)
    p = sds((1 << 16, ), jnp.float32)
    results.append(check(
        "fused_adam_step",
        lambda g, mst, m, v: fused_adam_step(
            g, mst, m, v, lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-8,
            weight_decay=0.0, count=1), p, p, p, p))
    results.append(check(
        "fused_lamb_step",
        lambda g, mst, m, v: fused_lamb_step(
            g, mst, m, v, lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-8,
            weight_decay=0.01, count=1), p, p, p, p))
    results.append(check(
        "fused_lion_step",
        lambda g, mst, m: fused_lion_step(g, mst, m, lr=1e-4, beta1=0.9,
                                          beta2=0.99, weight_decay=0.0),
        p, p, p))

    from deepspeed_tpu.ops.pallas.quantizer import (dequantize_blockwise,
                                                    quantize_blockwise)

    def qdq(x):
        qv, scales, meta = quantize_blockwise(x, num_bits=8)
        return dequantize_blockwise(qv, scales, meta)

    results.append(check("quantizer(int8 block)", qdq,
                         sds((4096, 512), jnp.float32)))

    from deepspeed_tpu.ops.pallas.paged_attention import (
        paged_attention, paged_attention_per_token)
    # serving shapes at Llama-7B width: 32 heads x 128, 128-token pages
    T, maxb = 64, 5
    pq = sds((T, 32, D), bf16)
    kc = sds((41, 128, 32, D), bf16)
    bt = sds((T, maxb), jnp.int32)
    pos = sds((T, ), jnp.int32)
    results.append(check("paged_attention_per_token",
                         paged_attention_per_token, pq, kc, kc, bt, pos))
    # the run-tiled kernel: the serving cell's own shape
    # (Mistral-7B, 768-token budget, 27-page table, window 4096), an MHA
    # shape, and head sizes of the zoo that stay on the per-token kernel
    for name, T, heads, kv_heads, head_dim, maxb, window in (
            ("GQA 32/8, the cell", 768, 32, 8, 128, 27, 4096),
            ("MHA 32/32", 768, 32, 32, 128, 16, 0),
            ("GQA 28/4, Qwen2", 256, 28, 4, 128, 16, 0),
            ("MHA 32/32 x 80: per token", 64, 32, 32, 80, 16, 0),
            ("MQA 71/1 x 64: per token", 64, 71, 1, 64, 16, 0)):
        kc = sds((64, 128, kv_heads, head_dim), bf16)
        results.append(check(
            f"paged_attention({name})",
            lambda q, k, v, t, s, l, window=window: paged_attention(
                q, k, v, t, s, l, window=window),
            sds((T, heads, head_dim), bf16), kc, kc,
            sds((65, maxb), jnp.int32),
            sds((T, ), jnp.int32), sds((T, ), jnp.int32)))

    from deepspeed_tpu.ops.pallas.grouped_matmul import gmm
    results.append(check(
        "gmm(moe grouped matmul)", lambda a, b, s: gmm(a, b, s),
        sds((512, 256), bf16), sds((4, 256, 128), bf16),
        sds((4, ), jnp.int32)))

    from deepspeed_tpu.ops.pallas.block_sparse_attention import (
        block_sparse_flash_attention)
    from deepspeed_tpu.ops.sparse_attention import FixedSparsityConfig
    blk = 64
    # the layout is static host data (it sizes the kernel's index tables)
    layout = np.asarray(FixedSparsityConfig(num_heads=H,
                                            block=blk).make_layout(S))
    results.append(check(
        "block_sparse_flash_attention(fixed)",
        lambda q, k, v: block_sparse_flash_attention(q, k, v, layout, blk),
        q, q, q))

    print(f"target: {TOPOLOGY} ({kind}), compile only")
    for name, status, err in results:
        print(f"{status:4s} {name}" + (f"  {err}" if err else ""))
    return 0 if all(r[1] == "PASS" for r in results) else 1


if __name__ == "__main__":
    sys.exit(main())
