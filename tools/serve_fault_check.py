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

``ENGINE_FAULTS`` (ISSUE 55) are the other way round: planted in the ENGINE,
whatever the architecture, the check requests streamed AGAIN with each and
judged by the sound reference (``--only burst1_...`` names them like any
other).  Their lines also carry ``reply_lengths`` (the job's
``serve.check_tokens_prompt*`` compares them with ``check_new_tokens``) and
``bursts_of_one`` (0: the fault had no burst of one to sit in), and
``error`` where the stream did not end.
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
    "motif": {
        "a_lambda_zero_no_subtraction": {"differential": False},
        "b_window_dropped_on_a_window_layer": {"window_dropped_on_layer": 4},
        "c_window_put_on_a_full_layer": {"window_put_on_layer": 3},
        "d_one_sinkhorn_sweep_for_twenty": {"mhc_sinkhorn_iters": 1},
        "e_h_res_identity": {"mhc_identity_res": True},
        "f_silu_for_polynorm": {"hidden_act": "silu"},
        "g_cache_row_in_8_bits": {"cache_row_mantissa_bits": 3},
    },
    "qwen3_next": {
        "a_no_decay": {"rule_decay": False},
        "b_beta_one": {"rule_beta": False},
        "c_q_k_not_l2_normalised": {"rule_l2_norm": False},
        # 16: a burst's iterations; it divides a chunk of 64 and a step
        "d_state_not_carried_past_16_tokens": {"state_reset_every": 16},
        "e_conv_rows_not_carried_past_16_tokens": {"conv_reset_every": 16},
        "f_state_held_in_bfloat16": {"state_held_in": "bfloat16"},
        "g_attention_gate_dropped": {"attention_gate": False},
        "h_w_for_one_plus_w": {"norm_one_plus_w": False},
        "i_rotary_on_the_whole_head": {"rotary_whole_head": True},
        "j_shared_expert_gate_dropped": {"shared_expert_gate": False},
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
    "motif": {
        "h_control_weights_in_8_bits": {"weight_mantissa_bits": 3},
    },
    "qwen3_next": {
        "k_control_weights_in_8_bits": {"weight_mantissa_bits": 3},
    },
}


def _state_rows_not_written(sched):
    """A burst of ONE iteration leaves every recurrent state row as it
    found it (a cache without state rows: nothing planted)."""
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2 import ragged_forward as rf
    real, kinds = rf.decode_burst, sched.engine.kv_cache.kinds

    def burst(params, kv, *args, k, **kw):
        kept = [tuple(jnp.copy(b) for b in entry)
                if k == 1 and kind == "state" else None
                for kind, entry in zip(kinds, kv)]
        toks, kv = real(params, kv, *args, k=k, **kw)
        return toks, tuple(old or new for old, new in zip(kept, kv))

    rf.decode_burst = burst
    return lambda: setattr(rf, "decode_burst", real)


def _token_in_flight_from_the_hosts_copy(sched):
    """A burst of ONE iteration launched on top of a step in flight gives a
    row whose token that step is choosing the newest token the HOST holds
    (the one before it), not the device's."""
    import numpy as np
    eng = sched.engine
    launch, ids, stale = eng._launch_burst, eng._ids_on_device, {}

    def _launch_burst(seqs, k, *args):
        if eng._burst_length(seqs, k) == 1:
            stale.update({s.slot: s.tokens[-1] for s in seqs})
        try:
            return launch(seqs, k, *args)
        finally:
            stale.clear()

    def _ids_on_device(toks, take_from):
        if stale:
            toks = np.where(take_from > 0, [stale.get(i, 0) for i in
                                            range(len(toks))], toks)
            take_from = np.zeros_like(take_from)
        return ids(toks.astype(np.int32), take_from)

    eng._launch_burst, eng._ids_on_device = _launch_burst, _ids_on_device
    return lambda: None


def _seen_tokens_advanced_twice(sched):
    """A burst of ONE iteration counts its position twice."""
    eng = sched.engine
    launch = eng._launch_burst

    def _launch_burst(*args):
        step = launch(*args)
        if step is not None and step.burst_k == 1:
            for seq in step.seqs:
                seq.seen_tokens += 1
        return step

    eng._launch_burst = _launch_burst
    return lambda: None


#: faults planted in the engine: ``plant(sched)`` returns what takes it out
ENGINE_FAULTS = {
    "burst1_state_rows_not_written": _state_rows_not_written,
    "burst1_token_in_flight_from_the_hosts_copy":
        _token_in_flight_from_the_hosts_copy,
    "burst1_seen_tokens_advanced_twice": _seen_tokens_advanced_twice,
}


def streamed(serve, ctx, model, params, prompts, new, plant=None):
    """The check requests through a fresh scheduler, with ``plant``'s fault
    in the engine: ``(the engine's params, the streams, facts of the run)``.
    A stream that does not end (a fault may leave a row that never runs) is
    an ``error``, with what was streamed until then."""
    sched = serve.build_scheduler(ctx, model, params)
    undo = plant(sched) if plant else lambda: None
    out = [[] for _ in prompts]
    for i, p in enumerate(prompts):
        sched.submit(p, max_new_tokens=new,
                     on_token=lambda t, done, i=i: out[i].append(t))
    facts = {}
    try:
        sched.drain(max_steps=50 * new)
    except Exception as e:          # noqa: BLE001 - reported, not handled
        facts["error"] = f"{type(e).__name__}: {e}"[:300]
    finally:
        undo()
    facts.update(reply_lengths=[len(o) for o in out],
                 bursts_of_one=getattr(sched, "bursts_of_one", None))
    return sched.engine.params, out, facts


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
    prompts = traffic_gen.check_requests(traffic, sizes["vocab_size"],
                                         opts.seed)
    new = int(traffic["check_new_tokens"])

    runs = [(None, {})] + list(FAULTS.get(config["arch"], {}).items()) \
        + list(CONTROLS.get(config["arch"], {}).items()) \
        + list(ENGINE_FAULTS.items())
    if opts.only:
        runs = [r for r in runs if r[0] is None or r[0] in
                opts.only.split(",")]
    # every stream before any judging: the engine's programs are compiled
    # once, and each scheduler's cache goes before the next is built
    streams = {}
    for name, plant in [(None, None)] + [r for r in runs
                                         if r[0] in ENGINE_FAULTS]:
        params, produced, facts = streamed(serve, ctx, model, params,
                                           prompts, new, plant)
        streams[name] = produced, facts
        gc.collect()
    lines = []
    for name, change in runs:
        in_engine = name in ENGINE_FAULTS
        produced, facts = streams[name if in_engine else None]
        # a reply cut short is judged as far as it goes
        rows = judged(serve, ref, params,
                      sizes if in_engine else dict(sizes, **change),
                      prompts, [toks[:new] for toks in produced], tols) \
            if all(produced) else []
        short = [f"serve.check_tokens_prompt{len(p)}"
                 for p, toks in zip(prompts, produced) if len(toks) != new]
        lines.append({"fault": name, "seed": opts.seed, "checks": rows,
                      **(facts if in_engine or name is None else {}),
                      "caught_by": short + [r["check"] for r in rows
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
