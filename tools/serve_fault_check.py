#!/usr/bin/env python
"""Would a serving cell's comparison catch a planted fault AT THE TIMED SIZE?

    python tools/serve_fault_check.py --workload longcat_flash_serve_agent \\
        --seed N [--out FILE] [--only a,b] [--root DIR]

Runs, on the attached chip, what ``perfbench/jobs/serve.py`` runs before its
window: seeded weights, the scheduler as the configuration lays it out, the
three check requests streamed through ``submit`` / ``step``.  The engine is
then let go and the plain float32 reference judges the SAME tokens by the
job's own rule and limits (``routed_logit_gaps`` / ``logit_gaps``, ``judge``,
``TOL_FACTOR`` x the configuration's ``measured_worst``): once sound, then
once more with one reading changed at a time (``FAULTS``: sizes the reference
reads; then ``CONTROLS``: the reference with its weights in 8 bits).  One JSON line a run: ``{"fault", "checks", "caught_by"}``; ``fault:
null`` is the sound reference, which must be caught by nothing.  The faults
are planted in the reference because it has the switches; the comparison is
symmetric.
"""

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: sizes a reference reads -> another value: a fault of that architecture
FAULTS = {
    "longcat_flash": {
        "a_identity_experts_left_out": {"identity_experts": False},
        "b_shortcut_from_the_second_sublayer": {"shortcut_from": 1},
        "c_kv_lora_scale_left_out": {"mla_scale_kv_lora": False},
        "d_second_attention_reads_the_firsts_cache": {
            "second_attention_reads": 0},
        "e_weights_renormalised_over_the_chosen": {"norm_topk_prob": True},
        "f_held_experts_left_out": {"held_experts_part": False},
    },
    "ouro": {
        "a_three_passes_instead_of_four": {"passes_run": 3},
        "b_final_norm_between_passes_left_out": {
            "norm_between_passes": False},
        "c_pass_reads_the_pass_befores_entries": {"pass_reads": "previous"},
        "d_all_passes_share_one_entry_a_layer": {"pass_reads": "last"},
        "e_post_sublayer_norms_left_out": {"post_sublayer_norms": False},
    },
}
#: the comparison's lower-precision control: the reference in the nearest
#: precision below the one the configuration serves in, which has to come out
#: as NOT correct (run after the faults, or alone with ``--only``)
CONTROLS = {
    "longcat_flash": {
        "control_weights_in_8_bits": {"weight_mantissa_bits": 3},
    },
    "ouro": {
        "f_control_weights_in_8_bits": {"weight_mantissa_bits": 3},
    },
}


def judged(serve, ref, params, sizes, prompts, produced, tols):
    """The comparison's rows for these tokens, as ``harness.Checks`` rows."""
    from perfbench import harness
    if "serve.router_margin" in tols:
        rows = serve.routed_logit_gaps(
            ref.logits_and_routing_at, params, sizes, prompts, produced,
            tols["serve.router_margin"])
    else:
        rows = serve.logit_gaps(ref.logits_at, params, sizes, prompts,
                                produced)
    checks = harness.Checks()
    serve.judge(checks, rows, tols)
    return checks.rows


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out")
    ap.add_argument("--only", help="comma-separated faults to run beside "
                    "the sound reference; default: all")
    ap.add_argument("--root", help="another benchmark root (the tests' "
                    "tiny cells); default: this checkout")
    opts = ap.parse_args()
    import jax
    from perfbench import harness, loader, traffic_gen, weights
    root = opts.root or loader.ROOT
    manifest = loader.load_manifest(root)
    cell = loader.find(manifest["workloads"], opts.workload, "workload")
    entry = loader.find(manifest["configs"], cell["config"], "config")
    config = loader.load_json(os.path.join(root, entry["file"]))
    traffic = loader.load_json(loader.part_path(root, "traffic",
                                                cell["traffic"], "json"))
    serve = loader.load_part(root, "jobs", "serve")
    arch = loader.load_part(root, "models", config["arch"])
    ref = loader.load_part(root, "reference", config["arch"])
    ctx = harness.Context(traffic=traffic, config=config, reference=ref,
                          config_file=entry["file"])
    tols = serve.tolerances(ctx)
    model, _ = arch.build(config, "serve")
    sizes = arch.reference_sizes(config, "serve")
    params = weights.seeded_weights(arch.param_shapes(model),
                                    harness.fold_seed(opts.seed))
    sched = serve.build_scheduler(ctx, model, params)
    prompts = traffic_gen.check_requests(traffic, sizes["vocab_size"],
                                         opts.seed)
    new = int(traffic["check_new_tokens"])
    produced = serve.stream(sched, [(p, new) for p in prompts])
    params = sched.engine.params
    sched = None                      # the cache goes; the weights stay
    gc.collect()

    runs = [(None, {})] + list(FAULTS.get(config["arch"], {}).items()) \
        + list(CONTROLS.get(config["arch"], {}).items())
    if opts.only:
        runs = [r for r in runs if r[0] is None or r[0] in
                opts.only.split(",")]
    lines = []
    for name, change in runs:
        rows = judged(serve, ref, params, dict(sizes, **change), prompts,
                      produced, tols)
        lines.append({"fault": name, "seed": opts.seed, "checks": rows,
                      "caught_by": [r["check"] for r in rows
                                    if not r["pass"]]})
        print(json.dumps(lines[-1]), flush=True)
        gc.collect()
        jax.clear_caches()
    if opts.out:
        os.makedirs(os.path.dirname(os.path.abspath(opts.out)), exist_ok=True)
        with open(opts.out, "a") as f:
            f.writelines(json.dumps(line) + "\n" for line in lines)
    return 0


if __name__ == "__main__":
    sys.exit(main())
