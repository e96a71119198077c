#!/usr/bin/env python
"""Domino TP-overlap evidence from TPU-compiled HLO.

Compiles a tp=2 transformer block's train step for a TPU target and runs
``measure_tp_overlap`` on the optimized schedule: if XLA's latency-hiding
scheduler splits the TP all-reduces into start/done pairs with compute
inside the windows, Domino's µ-stream splitting is designed away WITH
evidence; if not, the split block becomes a to-do.

Default path: compile ahead-of-time against a multi-chip TPU *topology
description* (jax.experimental.topologies) — compile-only, needs libtpu
but no chip.  ``DS_DOMINO_REAL=1`` uses the live device set instead
(requires ≥2 TPU chips).

Measured finding (2026-07-31, v5e:2x2): TPU optimized HLO has NO async
collective start/done pairs — overlap is in-op (ring emitters in
collective_algorithm_config), so the structural criterion cannot
adjudicate on TPU; use domino_ab's wall-clock A/B on ≥2 chips.

Writes chiprun_out/domino_overlap.json; fold the table into
docs/parallelism.md.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def build_step(mesh_devices_or_topo_mesh):
    """tp=2 block: x @ W1 (col-parallel) → gelu → @ W2 (row-parallel) →
    all-reduce; loss + grad so the backward collectives appear too."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = mesh_devices_or_topo_mesh
    B, S, H, F = 8, 512, 1024, 4096
    xs = jax.ShapeDtypeStruct((B, S, H), jnp.bfloat16,
                              sharding=NamedSharding(mesh, P("dp")))
    w1 = jax.ShapeDtypeStruct((H, F), jnp.bfloat16,
                              sharding=NamedSharding(mesh, P(None, "tp")))
    w2 = jax.ShapeDtypeStruct((F, H), jnp.bfloat16,
                              sharding=NamedSharding(mesh, P("tp", None)))

    def loss_fn(w1, w2, x):
        # two stacked blocks so inter-block compute can slide into the
        # collective windows
        for _ in range(2):
            h = jax.nn.gelu(x @ w1)
            x = x + (h @ w2)
        return jnp.mean(x.astype(jnp.float32) ** 2)

    def step(w1, w2, x):
        loss, grads = jax.value_and_grad(loss_fn, argnums=(0, 1))(w1, w2, x)
        return loss, grads

    return step, (w1, w2, xs)


def main():
    import jax
    from jax.sharding import Mesh

    out_path = os.path.join(ROOT, "chiprun_out", "domino_overlap.json")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    report = None

    import numpy as np
    mesh = None
    if os.environ.get("DS_DOMINO_REAL") == "1":
        # opt-in: a live multi-chip backend
        devs = jax.devices()
        if len(devs) < 2 or devs[0].platform != "tpu":
            raise SystemExit(f"DS_DOMINO_REAL=1 needs >= 2 TPU chips, "
                             f"found {devs}")
        n = 4 if len(devs) >= 4 else 2
        mesh = Mesh(np.array(devs[:n]).reshape(n // 2, 2), ("dp", "tp"))
        source = f"real devices ({len(devs)}, mesh {n // 2}x2)"
    if mesh is None:
        # AOT against a topology description — compile-only, needs only
        # the TPU compiler, no chips owned
        from jax.experimental import topologies
        topo, last = None, None
        for name in ("v5e:2x2", "v6e:2x2", "v4:2x2x1"):
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name=name)
                source = f"topology {name}"
                break
            except Exception as e:
                last = e
        if topo is None:
            json.dump({"error": f"no TPU topology reachable: {last}"},
                      open(out_path, "w"))
            print(f"FAILED: {last}")
            return 1
        tdevs = topo.devices
        mesh = Mesh(np.array(tdevs[:4]).reshape(2, 2), ("dp", "tp"))

    step, args = build_step(mesh)
    from deepspeed_tpu.runtime.domino.overlap import analyze_hlo_overlap
    lowered = jax.jit(step).lower(*args)
    compiled = lowered.compile()
    texts = compiled.as_text()
    if isinstance(texts, (list, tuple)):
        texts = "\n".join(texts)
    report = analyze_hlo_overlap(texts)
    report["source"] = source
    report["overlapped"] = (report["async_pairs"] > 0
                            and report["overlapped_pairs"] > 0)
    if report["collectives"] and not report["async_pairs"]:
        # Measured 2026-07-31 (v5e:2x2): TPU optimized HLO keeps
        # collectives as single scheduled ops with an in-op
        # collective_algorithm_config (ring emitters + scoped-memory
        # barriers) — cross-op overlap is not expressed as async pairs on
        # this backend, so the structural criterion cannot adjudicate;
        # use the domino_ab wall-clock A/B on >=2 chips instead.
        report["note"] = ("tpu hlo has no async collective pairs; overlap "
                         "is in-op (collective_algorithm_config) — decide "
                         "via domino_ab wall-clock on >=2 chips")
    json.dump(report, open(out_path, "w"), indent=2)
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
