"""Shared by the perfbench tests: a throw-away benchmark root that holds a
copy of ``perfbench/`` and a manifest of tiny cells, run on the CPU through
the tests' door (``run_cell(..., require_tpu=False)``)."""

import io
import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def tiny_root(tmp_path, cells):
    """``cells``: [(name, config, traffic, job)].  Returns the root."""
    root = str(tmp_path / "bench_root")
    shutil.copytree(os.path.join(ROOT, "perfbench"),
                    os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    # the real metrics, with the tiny cells in place of the real ones
    e2e = [dict(m, workloads=[]) if "workloads" in m else dict(m)
           for m in real["end_to_end"]]
    per_layer = [dict(m, workloads=[]) for m in real["per_layer"]]
    for name, _, _, job in cells:
        for m in e2e:
            if "workloads" in m and m["name"].startswith(job):
                m["workloads"].append(name)
        for m in per_layer:
            if m["moves"].startswith(job):
                m["workloads"].append(name)
    configs = sorted({c for _, c, _, _ in cells})
    manifest = {
        "command": ["python3", "perfbench/run.py"], "paths": ["perfbench"],
        "run_seconds": 1,
        "configs": [{"name": c, "source": "test",
                     "file": f"perfbench/configs/{c}.json", "reduced": [],
                     "why": "test"} for c in configs],
        "workloads": [{"name": n, "config": c, "traffic": t, "chips": 1,
                       "why": "test"} for n, c, t, _ in cells],
        "end_to_end": e2e,
        "per_layer": [m for m in per_layer if m["workloads"]],
    }
    write_manifest(root, manifest)
    return root


def write_manifest(root, manifest):
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)


def read_manifest(root):
    return json.load(open(os.path.join(root, "BENCHMARK.json")))


def run(root, cell, seed=1, seconds=1.0, trace=0):
    from perfbench import harness
    out = io.StringIO()
    rc, result = harness.run_cell(cell, seed, seconds, trace, root=root,
                                  require_tpu=False, out=out)
    last = out.getvalue().strip().splitlines()[-1] if rc == 0 else None
    return rc, result, last


# ----------------------------------------------- the parts, without a run
def parts(config_name):
    """``(configuration, architecture, reference)`` of a preset, by name."""
    from perfbench import loader
    config = loader.load_json(os.path.join(
        ROOT, "perfbench", "configs", config_name + ".json"))
    arch = loader.load_part(ROOT, "models", config["arch"])
    ref = loader.load_part(ROOT, "reference", config["arch"])
    return config, arch, ref


def serve_ctx(config_name, traffic_name="tiny_chat"):
    """A context as far as the serve job's comparison reads it."""
    from perfbench import harness, loader
    config, _, ref = parts(config_name)
    traffic = loader.load_json(loader.part_path(
        ROOT, "traffic", traffic_name, "json"))
    return harness.Context(
        config=config, traffic=traffic, reference=ref,
        config_file=f"perfbench/configs/{config_name}.json")


def streamed(config_name, seed, mutate=None, traffic_name="tiny_chat"):
    """The serve job's own check requests through its own scheduler:
    ``(serve job, reference, seeded weights, sizes, prompts, tokens)``.
    ``mutate`` changes the weights the ENGINE serves, not the reference's."""
    from perfbench import harness, loader, traffic_gen, weights
    config, arch, ref = parts(config_name)
    serve = loader.load_part(ROOT, "jobs", "serve")
    model, _ = arch.build(config, "serve")
    sizes = arch.reference_sizes(config, "serve")
    params = weights.seeded_weights(arch.param_shapes(model),
                                    harness.fold_seed(seed))
    served = mutate(params) if mutate else params
    ctx = serve_ctx(config_name, traffic_name)
    sched = serve.build_scheduler(ctx, model, served)
    prompts = traffic_gen.check_requests(ctx.traffic, sizes["vocab_size"],
                                         seed)
    new = ctx.traffic["check_new_tokens"]
    produced = serve.stream(sched, [(p, new) for p in prompts])
    return serve, ref, params, sizes, prompts, produced


def rounded_to(bits):
    """Weights rounded to ``bits`` bits (one scale a matrix), norms kept."""
    import jax
    import jax.numpy as jnp
    levels = 2 ** (bits - 1) - 1

    def q(x):
        if x.ndim < 2:
            return x
        scale = jnp.max(jnp.abs(x.astype(jnp.float32))) / levels
        return (jnp.round(x.astype(jnp.float32) / scale)
                * scale).astype(x.dtype)
    return lambda params: jax.tree_util.tree_map(q, params)
