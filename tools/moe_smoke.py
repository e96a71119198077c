#!/usr/bin/env python
"""MoE engine smoke test: the expert-parallel subsystem must reproduce the
dense-path math and the quantized dispatch must hold loss parity.

What it does (tiny MoE regression model, 8 virtual CPU devices, ~40s):

1. **convergence sanity** — a tiny-MoE train converges (final < 0.8 ×
   first), i.e. the sparse path actually learns like the dense one;
2. **ep parity** — the IDENTICAL run (same host-initialized params, data,
   SGD) on ep=1 and ep>1 meshes reaches the same losses to ≤ 1e-6 with the
   fp (GSPMD constraint) dispatch: expert parallelism is a layout choice,
   not a math change;
3. **dispatch parity** — ``moe.quantized_dispatch`` with the fp32 wire is
   ≤ 1e-6 vs the constraint path (identical schedule, no codec), and the
   int8 wire stays within 1e-2 with a converging trajectory (ISSUE-13
   acceptance);
4. **bit-identity off** — ``moe.enabled: false`` and an absent ``moe``
   block compile to the SAME micro-step program (normalized-jaxpr
   equality), and ``quantized_dispatch: false`` adds nothing either — the
   comm_optimizations contract applied to MoE.

Params are initialized on HOST (eager ``model.init``) and passed in
explicitly: on this jaxlib, ``jax.random`` values inside a jit depend on
the output shardings, so born-sharded init would differ across meshes and
the ep-parity gate would measure the RNG, not the dispatch.

Run:  python tools/moe_smoke.py
Exit: 0 on PASS, 1 on any deviation.

``tests/unit/moe/test_moe_smoke.py`` drives the ``run_*`` functions
in-process (loaded via importlib, no subprocess).
"""

import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HIDDEN = 32
EXPERTS = 4
TOLERANCE = 1e-2
FP_TOLERANCE = 1e-6

INT8_MOE = {"enabled": True, "quantized_dispatch": True, "wire_dtype": "int8",
            "quantization_group_size": 128}
FP_MOE = {"enabled": True, "quantized_dispatch": True, "wire_dtype": "fp32"}


def _model():
    import flax.linen as nn
    import jax.numpy as jnp
    from deepspeed_tpu.moe import MoE

    class MoEModel(nn.Module):
        hidden: int = HIDDEN
        num_experts: int = EXPERTS

        @nn.compact
        def __call__(self, x, y):
            h = nn.Dense(self.hidden, name="in_proj")(x)
            moe_out, l_aux, _ = MoE(hidden_size=self.hidden,
                                    num_experts=self.num_experts, k=1,
                                    capacity_factor=2.0, name="moe")(h)
            h = h + moe_out
            out = nn.Dense(self.hidden, name="out_proj")(h)
            return jnp.mean((out - y) ** 2) + 0.01 * l_aux

    return MoEModel()


def _data():
    import numpy as np
    rng = np.random.default_rng(0)
    x = rng.standard_normal((32, HIDDEN)).astype("float32")
    y = np.tanh(x * 0.5).astype("float32")
    return x, y


def _host_params(model, x, y):
    """Eager (unjitted) init: values independent of the mesh/shardings."""
    import jax
    import numpy as np
    return jax.tree_util.tree_map(
        np.asarray, model.init(jax.random.PRNGKey(0), x, y)["params"])


def _engine(moe_block, ep, stage=2, extra=None):
    import deepspeed_tpu
    from deepspeed_tpu.utils import groups
    groups.reset_mesh()
    deepspeed_tpu.comm.destroy_process_group()
    groups.initialize_mesh(ep=ep)
    model = _model()
    x, y = _data()
    params = _host_params(model, x, y)
    config = {
        "train_micro_batch_size_per_gpu": 4,
        "optimizer": {"type": "sgd", "params": {"lr": 0.1}},
        "zero_optimization": {"stage": stage},
        "mesh": {"dp": -1, "ep": ep},
    }
    if moe_block is not None:
        config["moe"] = moe_block
    if extra:
        config.update(extra)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=config)
    return engine, x, y


def _teardown():
    import deepspeed_tpu
    from deepspeed_tpu.utils import groups
    groups.reset_mesh()
    deepspeed_tpu.comm.destroy_process_group()


def _one_run(moe_block, ep, steps=8, stage=2, extra=None):
    engine, x, y = _engine(moe_block, ep, stage=stage, extra=extra)
    try:
        losses = []
        for _ in range(steps):
            loss = engine(x, y)
            engine.backward(loss)
            engine.step()
            losses.append(float(loss))
        return losses
    finally:
        _teardown()


def run_moe_smoke(steps=8, tolerance=TOLERANCE):
    """The MoE loss-parity gate (ISSUE-13 acceptance).  Returns a dict with
    every trajectory, the deltas, the wire-bytes comparison and a ``pass``
    verdict — the CLI and the unit test both key off it."""
    from deepspeed_tpu.moe.engine import expert_dispatch_wire_bytes

    ep1 = _one_run({"enabled": True}, 1, steps)
    ep4 = _one_run({"enabled": True}, 4, steps)
    man_fp = _one_run(FP_MOE, 4, steps)
    q8 = _one_run(INT8_MOE, 4, steps)

    ep_delta = max(abs(a - b) for a, b in zip(ep1, ep4))
    fp_delta = max(abs(a - b) for a, b in zip(ep4, man_fp))
    q_delta = abs(ep4[-1] - q8[-1])
    # dispatch payload: [E, C, D] at C = T·cf/E (the gate's capacity math)
    elems = EXPERTS * (32 * 2 // EXPERTS) * HIDDEN
    wire_fp = expert_dispatch_wire_bytes(elems, "fp32", 128)
    wire_q = expert_dispatch_wire_bytes(elems, "int8", 128)
    result = {
        "ep1_losses": ep1,
        "ep4_losses": ep4,
        "manual_fp_losses": man_fp,
        "quant_losses": q8,
        "ep_parity_delta": ep_delta,
        "manual_fp_delta": fp_delta,
        "quant_final_delta": q_delta,
        "tolerance": tolerance,
        "converged": q8[-1] < q8[0] * 0.8,
        "dense_sanity": ep1[-1] < ep1[0] * 0.8,
        "wire_bytes_fp_per_dispatch": wire_fp,
        "wire_bytes_quant_per_dispatch": wire_q,
        "wire_reduced": wire_q < wire_fp,
    }
    result["pass"] = bool(result["dense_sanity"]
                          and ep_delta <= FP_TOLERANCE
                          and fp_delta <= FP_TOLERANCE
                          and q_delta <= tolerance
                          and result["converged"]
                          and result["wire_reduced"])
    return result


def _micro_jaxpr(moe_block, ep=4):
    """Normalized micro-step jaxpr for a config (program-identity probe)."""
    import jax
    engine, x, y = _engine(moe_block, ep)
    try:
        inputs = engine.shard_batch(x, y)
        micro = engine._micro_step_fn()
        jaxpr = jax.make_jaxpr(micro)(engine.params,
                                      engine.scale_state.scale, inputs)
        return re.sub(r"0x[0-9a-f]+", "0x…", str(jaxpr))
    finally:
        _teardown()


def run_disabled_identity():
    """``moe.enabled: false`` / ``quantized_dispatch: false`` compile to
    the program of an absent ``moe`` block — normalized-jaxpr equality
    (the bit-identical contract)."""
    absent = _micro_jaxpr(None)
    disabled = _micro_jaxpr({"enabled": False})
    qd_off = _micro_jaxpr({"enabled": False, "quantized_dispatch": False})
    result = {
        "disabled_identical": absent == disabled,
        "quantized_dispatch_off_identical": absent == qd_off,
    }
    result["pass"] = bool(result["disabled_identical"]
                          and result["quantized_dispatch_off_identical"])
    return result


def run_hier_smoke(steps=8, tolerance=TOLERANCE):
    """Hierarchical (2-hop) dispatch parity: the split-ep variant (forced
    via ``intra_node_size`` on the virtual mesh, like the collectives
    tests) stays within the quantized tolerance of the flat baseline."""
    flat = _one_run({"enabled": True}, 4, steps)
    hier = _one_run(dict(INT8_MOE, intra_node_size=2), 4, steps)
    delta = abs(flat[-1] - hier[-1])
    return {
        "flat_losses": flat,
        "hier_losses": hier,
        "final_delta": delta,
        "tolerance": tolerance,
        "converged": hier[-1] < hier[0] * 0.8,
        "pass": bool(delta <= tolerance and hier[-1] < hier[0] * 0.8),
    }


def main():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    sys.path.insert(0, REPO)

    r = run_moe_smoke()
    print(f"ep1   losses: {['%.5f' % x for x in r['ep1_losses']]}")
    print(f"ep4   losses: {['%.5f' % x for x in r['ep4_losses']]}")
    print(f"int8  losses: {['%.5f' % x for x in r['quant_losses']]}")
    print(f"ep parity delta {r['ep_parity_delta']:.2e} | manual-fp delta "
          f"{r['manual_fp_delta']:.2e} | int8 final delta "
          f"{r['quant_final_delta']:.2e} (tol {r['tolerance']})")
    print(f"dispatch wire bytes: fp={r['wire_bytes_fp_per_dispatch']} "
          f"int8+scales={r['wire_bytes_quant_per_dispatch']} "
          f"(reduced={r['wire_reduced']})")
    if not r["pass"]:
        print("FAIL: MoE engine deviates (ep parity / dispatch parity / "
              "convergence)")
        return 1
    print("PASS: expert-parallel MoE holds loss parity with reduced "
          "dispatch wire bytes")

    d = run_disabled_identity()
    print(f"moe disabled program-identical: {d['disabled_identical']} | "
          f"quantized_dispatch off identical: "
          f"{d['quantized_dispatch_off_identical']}")
    if not d["pass"]:
        print("FAIL: a disabled moe block changes the compiled program")
        return 1
    print("PASS: moe.enabled/quantized_dispatch off are program-identical")

    h = run_hier_smoke()
    print(f"hier int8 final delta {h['final_delta']:.2e} "
          f"(tol {h['tolerance']}) | converged={h['converged']}")
    if not h["pass"]:
        print("FAIL: hierarchical dispatch deviates")
        return 1
    print("PASS: hierarchical (2-hop) quantized dispatch holds loss parity")
    return 0


if __name__ == "__main__":
    sys.exit(main())
