"""The architecture ``evabyte`` (ISSUE 27) as the benchmark sees it, at tiny
size on the CPU: the tiny cell through the harness is ``correct`` from its own
file's ``measured_worst``; the same rule rejects, on every seed tried, an
implementation that drops the summaries, leaves ``mu`` out, pools uniformly
or keeps a closed window's exact keys visible; the admission claim keeps the
tiny cell's load inside the cache; the two new readers; the lint."""

import copy
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import pb_helpers as pb
from perfbench import harness, loader, program_trace, serve_trace
from test_perfbench_manifest import lint_config
from test_perfbench_program_trace import RAGGED, US, _write, op, span

SEEDS = (1, 2, 5, 3_000_000_000)
CONFIG, TRAFFIC = "tiny_evabyte", "tiny_longctx"


@pytest.mark.parametrize("seed", SEEDS)
def test_tiny_cell_is_correct_from_its_own_measured_worst(tmp_path, seed,
                                                          capsys):
    root = pb.tiny_root(tmp_path, [("t_eva", CONFIG, TRAFFIC, "serve")])
    rc, result, _ = pb.run(root, "t_eva", seed=seed, trace=0)
    printed = capsys.readouterr().out
    assert rc == 0 and result["correct"], printed
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    # the shortest check prompt generates ACROSS the first window's end, the
    # longest has five closed windows behind it
    config = loader.load_json(os.path.join(
        pb.ROOT, "perfbench", "configs", CONFIG + ".json"))
    assert 28 < config["window_size"] < 28 + 32
    for n in (160, 80, 28):
        assert f"CHECK serve.logit_gap_prompt{n}:" in printed
    stated = config["measured_worst"]["serve.logit_gap"]["value"]
    assert f"must be <= {3 * stated:g}" in printed


# ------------------------------------------------------------- the controls
FAULTS = ("no_summaries", "no_mu", "uniform_pooling", "closed_window_visible")


def _attention(fault):
    """``models/evabyte.eva_attention`` with one fault (None: none)."""
    from deepspeed_tpu.models import evabyte

    def attend(q, k, v, phi, mu, window, chunk):
        if fault == "no_mu":
            mu = jnp.zeros_like(mu)
        if fault == "uniform_pooling":
            phi = jnp.zeros_like(phi)
        s = q.shape[1]
        t = jnp.arange(s)
        see = t[None, :] <= t[:, None]
        if fault != "closed_window_visible":
            see &= t[None, :] // window == t[:, None] // window
        q32 = q.astype(jnp.float32) * q.shape[-1] ** -0.5
        near = jnp.where(see[None, None], jnp.einsum(
            "bqhd,bkhd->bhqk", q32, k.astype(jnp.float32)), -jnp.inf)
        ks, vs = evabyte.chunk_summaries(k, v, phi, mu, chunk)
        closed = (jnp.arange(ks.shape[1])[None, :] * chunk) // window \
            < t[:, None] // window
        if fault == "no_summaries":
            closed = jnp.zeros_like(closed)
        far = jnp.where(closed[None, None],
                        jnp.einsum("bqhd,bnhd->bhqn", q32, ks), -jnp.inf)
        probs = jax.nn.softmax(jnp.concatenate([far, near], -1), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, jnp.concatenate(
            [vs, v.astype(jnp.float32)], axis=1))
    return attend


def _greedy(model, params, prompts, new, fault, monkeypatch, length=192):
    """Greedy decoding of head 0 by the dense model with ``fault``."""
    from deepspeed_tpu.models import evabyte
    monkeypatch.setattr(evabyte, "eva_attention", _attention(fault))
    forward = jax.jit(lambda p, ids, at: model.apply(
        {"params": p}, ids[None])[0, at, 0])
    out = []
    for prompt in prompts:
        ids, toks = np.zeros(length, np.int32), []
        ids[:len(prompt)] = prompt
        for i in range(new):
            at = len(prompt) + i - 1
            toks.append(int(jnp.argmax(forward(params, jnp.asarray(ids),
                                               at))))
            ids[at + 1] = toks[-1]
        out.append(toks)
    return out


def _rejected(serve, ref, params, sizes, prompts, produced, tols):
    checks = harness.Checks()
    serve.judge(checks, serve.logit_gaps(ref.logits_at, params, sizes,
                                         prompts, produced), tols)
    return not checks.all_passed


@pytest.mark.parametrize("seed", SEEDS)
def test_the_rule_rejects_each_fault_of_the_mechanism(seed, monkeypatch,
                                                      capsys):
    from perfbench import traffic_gen, weights
    config, arch, ref = pb.parts(CONFIG)
    serve = loader.load_part(pb.ROOT, "jobs", "serve")
    ctx = pb.serve_ctx(CONFIG, TRAFFIC)
    tols = serve.tolerances(ctx)
    model, _ = arch.build(config, "serve")
    sizes = arch.reference_sizes(config, "serve")
    params = weights.seeded_weights(arch.param_shapes(model),
                                    harness.fold_seed(seed))
    prompts = traffic_gen.check_requests(ctx.traffic, sizes["vocab_size"],
                                         seed)
    new = ctx.traffic["check_new_tokens"]
    judged = lambda produced: _rejected(serve, ref, params, sizes, prompts,
                                        produced, tols)
    # the engine itself passes, and so does the dense model without a fault
    assert not judged(pb.streamed(CONFIG, seed, traffic_name=TRAFFIC)[-1])
    assert not judged(_greedy(model, params, prompts, new, None,
                              monkeypatch))
    for fault in FAULTS:
        assert judged(_greedy(model, params, prompts, new, fault,
                              monkeypatch)), (fault, capsys.readouterr().out)


# ------------------------------------------------------------ (e) admission
def test_the_admission_claim_keeps_the_tiny_cells_load_inside_the_cache():
    from perfbench import traffic_gen, weights
    config, arch, _ = pb.parts(CONFIG)
    serve = loader.load_part(pb.ROOT, "jobs", "serve")
    ctx = pb.serve_ctx(CONFIG, TRAFFIC)
    model, _ = arch.build(config, "serve")
    params = weights.seeded_weights(arch.param_shapes(model),
                                    harness.fold_seed(0))
    # a pool a third of what the four sessions' peaks would take together
    ctx.config = copy.deepcopy(ctx.config)
    ctx.config["program"]["serve"]["engine"]["num_blocks"] = 16
    sched = serve.build_scheduler(ctx, model, params)
    sm = sched.engine.state_manager
    free0 = sm.free_blocks
    requests = traffic_gen.RequestStream(ctx.traffic, 80, 0)
    sessions, sent, done = range(ctx.traffic["sessions"]), 0, []
    busy = {}
    while len(done) < 24:
        for s in sessions:
            if s not in busy and sent < 24:
                prompt, want = requests.next(s)
                busy[s] = sched.submit(prompt, max_new_tokens=want)
                sent += 1
        sched.step()         # raises KVCacheExhausted past max_preemptions
        for s, uid in list(busy.items()):
            if sched.query(uid).state.name == "DONE":
                done.append(busy.pop(s))
        for seq in sm.tracked_sequences.values():
            assert len(seq.blocks) == sched.engine.kv_cache.blocks_for(
                seq.seen_tokens)
    assert sched.preemptions == 0
    assert sched.peak_running >= 2       # and it did run them side by side
    assert sm.free_blocks == free0


# ------------------------------------------------------------- the readers
EVA_TRACE = {
    "/device:TPU:0": {
        "XLA Modules": [
            (f"jit_ds_ragged_step_evabyte({RAGGED})", 0, 1000 * US, {}, {})],
        "XLA Ops": [
            op("%fusion.1 = f32[65,16,32,128]{3,2,1,0} fusion(bf16[8]{0} %p),"
               " kind=kLoop", 0, 30, RAGGED, "jit(ds_ragged_step_evabyte)/"
               "ds.attn/ds.eva_summary/gather"),
            op("%fusion.2 = bf16[2,180,128,32,128]{4,3,2,1,0} fusion(bf16[8]"
               "{0} %p), kind=kLoop", 30, 60, RAGGED,
               "jit(ds_ragged_step_evabyte)/ds.attn/ds.eva_summary/scatter"),
            op("%fusion.3 = bf16[768,11008]{1,0} fusion(bf16[8]{0} %p), "
               "kind=kOutput", 60, 500, RAGGED,
               "jit(ds_ragged_step_evabyte)/ds.mlp/dot_general"),
            # straddles the end of the traced stretch: 20 of its 40 us count
            op("%fusion.1 = f32[65,16,32,128]{3,2,1,0} fusion(bf16[8]{0} %p),"
               " kind=kLoop", 880, 920, RAGGED, "jit(ds_ragged_step_evabyte)/"
               "ds.attn/ds.eva_summary/gather"),
        ],
    },
    "/host:CPU": {
        "python3": [
            span("pb:traced", 0, 900),
            span("ds:serve.step", 0, 400, step=1, kind="ragged",
                 context_tokens=40000, held_blocks=100, block_size=128,
                 summary_pages=30, chunks_closed=48, windows_closed=0),
            span("ds:serve.step", 400, 800, step=2, kind="burst",
                 context_tokens=41000, held_blocks=150, block_size=128,
                 summary_pages=60, chunks_closed=16, windows_closed=1),
            span("ds:serve.step", 950, 1000, step=3, kind="ragged",
                 context_tokens=1, held_blocks=1000, block_size=128)],
    },
}


@pytest.mark.parametrize("metric,expected", [
    ("serve_eva_summary_ms_per_step", (0.03 + 0.03 + 0.02) / 2),
    ("serve_cache_tokens_per_row", 81000 / (250 * 128)),
])
def test_new_reader(metric, expected, tmp_path, monkeypatch):
    reader = loader.load_reader(pb.ROOT, metric)
    monkeypatch.setattr(program_trace, "ROOT", str(tmp_path))
    monkeypatch.setattr(serve_trace, "_CACHE", {})
    record = {"trace": {"busy_s": 1.0}}
    assert reader.read(record) is None              # no trace file
    _write(tmp_path, EVA_TRACE)
    assert reader.read({"trace": None}) is None     # an untraced run
    assert reader.read(record) == pytest.approx(expected)
    # a program that lacks the scope and the counts (the parent commit's,
    # another architecture's step): nothing to read, and no error
    monkeypatch.setattr(serve_trace, "_CACHE", {})
    bare = copy.deepcopy(EVA_TRACE)
    bare["/device:TPU:0"]["XLA Ops"] = [
        e[:4] + ({**e[4], "tf_op": "jit(ds_ragged_step_llama)/ds.attn/x:"}, )
        for e in bare["/device:TPU:0"]["XLA Ops"]]
    bare["/host:CPU"]["python3"] = [
        e[:3] + ({"step": 1, "kind": "ragged"}, {})
        if e[0] == "ds:serve.step" else e
        for e in bare["/host:CPU"]["python3"]]
    newer = _write(tmp_path, bare, cell="newer")
    os.utime(newer, (2e9, 2e9))
    assert reader.read(record) is None


def test_new_readers_give_nothing_with_a_program_without_the_names(
        tmp_path, monkeypatch):
    import sys
    import deepspeed_tpu.telemetry
    monkeypatch.setattr(program_trace, "ROOT", str(tmp_path))
    monkeypatch.setattr(serve_trace, "_CACHE", {})
    _write(tmp_path, EVA_TRACE)
    monkeypatch.setitem(sys.modules, "deepspeed_tpu.telemetry.names", None)
    monkeypatch.delattr(deepspeed_tpu.telemetry, "names")
    for metric in ("serve_eva_summary_ms_per_step",
                   "serve_cache_tokens_per_row"):
        assert loader.load_reader(pb.ROOT, metric).read(
            {"trace": {"busy_s": 1.0}}) is None


# ----------------------------------------------------------------- the lint
def test_lint_holds_evabyte_to_every_catalog_key():
    manifest = pb.read_manifest(pb.ROOT)
    entry = loader.find(manifest["configs"], "evabyte_1chip", "config")
    body = json.load(open(os.path.join(pb.ROOT, entry["file"])))
    assert entry["reduced"] == ["num_hidden_layers"] == list(body["reduced"])
    assert lint_config(body, entry["reduced"]) == []
    published = {k: v for k, v in body["published"].items()
                 if not k.startswith("_")}
    # the catalog row's keys, the nulls too
    assert len(published) == 29
    assert [k for k, v in published.items() if v is None] == [
        "init_cutoff_factor", "num_chunks", "rope_scaling"]
    assert (published["window_size"], published["chunk_size"],
            published["num_pred_heads"], published["vocab_size"]) == (
                2048, 16, 8, 320)
    assert body["num_hidden_layers"] == {"serve": 16}
    engine = body["program"]["serve"]["engine"]
    assert engine["block_size"] == body["window_size"] // body["chunk_size"]
    # a width halved is refused, listed in `reduced` or not
    halved = dict(body, window_size=1024)
    assert any("window_size" in f for f in
               lint_config(halved, entry["reduced"]))
    listed = dict(halved, reduced=dict(body["reduced"], window_size="x"))
    assert any("names the width 'window_size'" in f for f in lint_config(
        listed, ["num_hidden_layers", "window_size"]))
    for key in ("chunk_size", "num_pred_heads", "vocab_size",
                "num_key_value_heads", "rope_theta"):
        assert lint_config(dict(body, **{key: 4}), entry["reduced"]), key


def test_the_cell_is_the_issues_traffic_on_one_chip():
    manifest = pb.read_manifest(pb.ROOT)
    cell = loader.find(manifest["workloads"], "evabyte_serve_longctx",
                       "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "evabyte_1chip", "longctx_closed16", 1)
    t = loader.load_json(loader.part_path(pb.ROOT, "traffic",
                                          cell["traffic"], "json"))
    assert (t["job"], t["loop"], t["sessions"]) == ("serve", "closed", 16)
    assert t["prompt_len"] == {"dist": "lognormal", "median": 6144,
                               "sigma": 0.6, "min": 2040, "max": 20480}
    assert t["output_len"] == {"dist": "geometric", "mean": 128, "min": 16,
                               "max": 512}
    assert (t["pool_size"], t["pool_seed"], t["check_new_tokens"],
            t["trace_seconds"]) == (256, 20260927, 32, 5.0)
    # the shortest check prompt's 32 new bytes cross the first window's end
    assert t["prompt_len"]["min"] < 2048 < t["prompt_len"]["min"] + 32
    of = lambda name: {
        m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]
        if name in m.get("workloads", [name])}
    mine, chat = of(cell["name"]), of("mistral7b_serve_chat")
    assert mine - chat == {"serve_eva_summary_ms_per_step",
                           "serve_cache_tokens_per_row"}
    # the two per-burst readers are not listed here: this cell's traced
    # stretch holds no burst (PERF.md section 7)
    assert chat - mine == {"serve_burst_iteration_device_ms",
                           "serve_burst_paged_kernel_ms_per_iteration"}
