#!/usr/bin/env python
"""comm_optimizations smoke test: a tiny ZeRO-2 train with the quantized
collectives engine ON must track the flat baseline to loss parity.

What it does (tiny MLP, 8 virtual CPU devices, ~20s):

1. trains ``steps`` ZeRO-2 steps with the default flat collectives and
   records the loss trajectory;
2. repeats the IDENTICAL run (same seed, params, data, optimizer) with the
   ``comm_optimizations`` block enabled — int8 quantized gradient
   reduce-scatter (qgZ-style manual-SPMD micro) + hierarchical dispatch —
   and records that trajectory;
3. asserts (a) the quantized run converges (final < 0.8 × first), (b) the
   final losses agree within ``tolerance`` (ISSUE-5 acceptance: 1e-2), and
   (c) the quantized wire payload for the gradient volume is genuinely
   smaller than the fp32 payload.

Run:  python tools/comm_smoke.py
Exit: 0 on PASS, 1 on any deviation.

``tests/unit/comm/test_comm_smoke.py`` drives :func:`run_smoke` in-process
(loaded via importlib, no subprocess).
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HIDDEN = 16
TOLERANCE = 1e-2

COMM_OPTS = {
    "enabled": True,
    "quantized_gradients": True,
    "hierarchical_allreduce": True,
    "wire_dtype": "int8",
    "quantization_group_size": 128,
}

# overlap-scheduler gate configs: sub-KiB bucket bound so the tiny model
# actually forms >1 bucket (a production-size bound would put the whole
# model in one bucket and the gate would be vacuous)
OVERLAP_BUCKET_MB = 0.0005
OVERLAP_OPTS = {
    "overlap": {"enabled": True, "bucket_mb": OVERLAP_BUCKET_MB,
                "max_inflight": 2},
}
OVERLAP_QUANT_OPTS = dict(COMM_OPTS, **OVERLAP_OPTS)

# gather-prefetch gate configs (forward direction, stage 3): same sub-KiB
# bucket bound so the tiny model forms >1 prefetch bucket
PREFETCH_OPTS = {
    "overlap": {"prefetch": {"enabled": True,
                             "bucket_mb": OVERLAP_BUCKET_MB,
                             "max_inflight": 2}},
}
# int8 qwZ wire + prefetch: the pipelined quantized all-gather path
PREFETCH_QWZ_OPTS = {
    "enabled": True,
    "quantized_weights": True,
    "wire_dtype": "int8",
    "quantization_group_size": 128,
    **PREFETCH_OPTS,
}


def _one_run(comm_optimizations, steps, lr, stage=2):
    import numpy as np
    import deepspeed_tpu
    from deepspeed_tpu.utils import groups

    rng = np.random.default_rng(0)
    params = {
        "w1": rng.standard_normal((HIDDEN, HIDDEN)).astype("float32") * 0.3,
        "w2": rng.standard_normal((HIDDEN, HIDDEN)).astype("float32") * 0.3,
        "b": np.zeros((HIDDEN, ), "float32"),
    }

    def apply_fn(p, x, y):
        import jax.numpy as jnp
        h = jnp.tanh(x @ p["w1"] + p["b"])
        return jnp.mean((h @ p["w2"] - y) ** 2)

    # SGD, not adam: adam's per-element normalization (first step ≈ sign
    # descent) hides small relative gradient errors, which would make this
    # smoke pass even if quantization were catastrophically wrong.  SGD
    # propagates the int8 grid error into the trajectory proportionally —
    # the parity bound actually measures something.
    # persistence threshold 0: at the default (1e5 elements) every tensor of
    # this tiny model would stay replicated, the reduction would take the
    # full-precision pmean path, and the "parity" would be vacuous
    config = {
        "train_micro_batch_size_per_gpu": 4,
        "optimizer": {"type": "sgd", "params": {"lr": lr}},
        "zero_optimization": {"stage": stage,
                              "stage3_param_persistence_threshold": 0},
    }
    if comm_optimizations:
        config["comm_optimizations"] = comm_optimizations
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=apply_fn, model_parameters=params, config=config)
    xs = rng.standard_normal((4 * engine.dp_world_size, HIDDEN)
                             ).astype("float32")
    ys = np.tanh(xs * 0.5).astype("float32")
    losses = []
    for _ in range(steps):
        loss = engine(xs, ys)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    groups.reset_mesh()
    deepspeed_tpu.comm.destroy_process_group()
    return losses


def run_smoke(steps=8, lr=0.2, tolerance=TOLERANCE):
    """Run flat vs comm_optimizations ZeRO-2 and compare.  Returns a dict
    with both trajectories, the deltas, the wire-bytes comparison, and a
    ``pass`` verdict — the CLI and the unit test both key off it."""
    from deepspeed_tpu.comm.collectives import quantized_wire_bytes

    flat = _one_run(None, steps, lr)
    quant = _one_run(COMM_OPTS, steps, lr)
    final_delta = abs(flat[-1] - quant[-1])
    grad_elems = HIDDEN * HIDDEN
    wire_fp32 = grad_elems * 4
    wire_q = quantized_wire_bytes(grad_elems, COMM_OPTS["wire_dtype"],
                                  COMM_OPTS["quantization_group_size"])
    result = {
        "flat_losses": flat,
        "quant_losses": quant,
        "final_delta": final_delta,
        "tolerance": tolerance,
        "converged": quant[-1] < quant[0] * 0.8,
        "wire_bytes_fp32_per_grad": wire_fp32,
        "wire_bytes_quant_per_grad": wire_q,
        "wire_reduced": wire_q < wire_fp32,
    }
    result["pass"] = bool(result["converged"]
                          and final_delta <= tolerance
                          and result["wire_reduced"])
    return result


def run_overlap_smoke(steps=8, lr=0.2, tolerance=TOLERANCE):
    """Overlap-scheduler loss-parity gate (ISSUE-8 acceptance).

    Four ZeRO-2 runs on identical seeds/data:

    1. flat baseline (no comm_optimizations at all);
    2. overlap block present but ``enabled: false`` — must be
       **bit-identical** to (1): disabled means the micro-step compiles to
       the same program;
    3. overlap enabled, full-precision wire (GSPMD bucket markers) — the
       per-bucket constraints reduce each leaf exactly once with unchanged
       per-leaf math, so losses must match (1) to float tolerance;
    4. overlap enabled **with** int8 quantized gradients (manual qgZ
       pipeline) — bounded divergence, the quantized parity bound.
    """
    flat = _one_run(None, steps, lr)
    disabled = _one_run({"overlap": {"enabled": False}}, steps, lr)
    fp_overlap = _one_run(OVERLAP_OPTS, steps, lr)
    q_overlap = _one_run(OVERLAP_QUANT_OPTS, steps, lr)
    fp_delta = max(abs(a - b) for a, b in zip(flat, fp_overlap))
    q_delta = abs(flat[-1] - q_overlap[-1])
    result = {
        "flat_losses": flat,
        "disabled_losses": disabled,
        "overlap_losses": fp_overlap,
        "quant_overlap_losses": q_overlap,
        "disabled_bit_identical": disabled == flat,
        "fp_overlap_max_delta": fp_delta,
        "quant_final_delta": q_delta,
        "tolerance": tolerance,
        "converged": q_overlap[-1] < q_overlap[0] * 0.8,
    }
    result["pass"] = bool(result["disabled_bit_identical"]
                          and fp_delta <= 1e-6
                          and q_delta <= tolerance
                          and result["converged"])
    return result


def run_gather_prefetch_smoke(steps=8, lr=0.2, tolerance=TOLERANCE):
    """Forward param-gather prefetch loss-parity gate (ISSUE-9 acceptance).

    Four ZeRO-**3** runs on identical seeds/data:

    1. flat stage-3 baseline (no comm_optimizations at all);
    2. prefetch block present but ``enabled: false`` — must be
       **bit-identical** to (1): disabled means the micro-step compiles
       to the same program;
    3. prefetch enabled, full-precision wire (GSPMD gather markers) — the
       per-bucket constraints gather each leaf exactly once with unchanged
       per-leaf math, so losses must match (1) to float tolerance;
    4. prefetch enabled **with** int8 qwZ quantized weights (the
       pipelined quantized all-gather) — bounded divergence, the
       quantized parity bound.
    """
    flat = _one_run(None, steps, lr, stage=3)
    disabled = _one_run({"overlap": {"prefetch": {"enabled": False}}},
                        steps, lr, stage=3)
    fp_prefetch = _one_run(PREFETCH_OPTS, steps, lr, stage=3)
    q_prefetch = _one_run(PREFETCH_QWZ_OPTS, steps, lr, stage=3)
    fp_delta = max(abs(a - b) for a, b in zip(flat, fp_prefetch))
    q_delta = abs(flat[-1] - q_prefetch[-1])
    result = {
        "flat_losses": flat,
        "disabled_losses": disabled,
        "prefetch_losses": fp_prefetch,
        "quant_prefetch_losses": q_prefetch,
        "disabled_bit_identical": disabled == flat,
        "fp_prefetch_max_delta": fp_delta,
        "quant_final_delta": q_delta,
        "tolerance": tolerance,
        "converged": q_prefetch[-1] < q_prefetch[0] * 0.8,
    }
    result["pass"] = bool(result["disabled_bit_identical"]
                          and fp_delta <= 1e-6
                          and q_delta <= tolerance
                          and result["converged"])
    return result


def main():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    sys.path.insert(0, REPO)

    r = run_smoke()
    print(f"flat  losses: {['%.5f' % x for x in r['flat_losses']]}")
    print(f"quant losses: {['%.5f' % x for x in r['quant_losses']]}")
    print(f"final delta {r['final_delta']:.2e} (tolerance {r['tolerance']})"
          f" | converged={r['converged']}")
    print(f"gradient wire bytes: fp32={r['wire_bytes_fp32_per_grad']} "
          f"int8+scales={r['wire_bytes_quant_per_grad']} "
          f"(reduced={r['wire_reduced']})")
    if not r["pass"]:
        print("FAIL: comm_optimizations run deviates from the flat baseline")
        return 1
    print("PASS: quantized-engine ZeRO-2 reaches loss parity with reduced "
          "wire bytes")

    o = run_overlap_smoke()
    print(f"overlap disabled bit-identical: {o['disabled_bit_identical']} | "
          f"fp-overlap max delta {o['fp_overlap_max_delta']:.2e} | "
          f"quant-overlap final delta {o['quant_final_delta']:.2e} "
          f"(tolerance {o['tolerance']})")
    if not o["pass"]:
        print("FAIL: overlap scheduler deviates (disabled must be "
              "bit-identical; enabled must stay within parity bounds)")
        return 1
    print("PASS: bucketed overlap scheduler holds loss parity "
          "(bit-identical off, bounded divergence with quantized wire)")

    g = run_gather_prefetch_smoke()
    print(f"gather prefetch disabled bit-identical: "
          f"{g['disabled_bit_identical']} | "
          f"fp-prefetch max delta {g['fp_prefetch_max_delta']:.2e} | "
          f"qwZ-prefetch final delta {g['quant_final_delta']:.2e} "
          f"(tolerance {g['tolerance']})")
    if not g["pass"]:
        print("FAIL: gather-prefetch scheduler deviates (disabled must be "
              "bit-identical; enabled must stay within parity bounds)")
        return 1
    print("PASS: forward param-gather prefetch holds loss parity "
          "(bit-identical off, bounded divergence with qwZ wire)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
