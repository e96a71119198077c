"""Architecture ``pangu_ultra_moe`` (openPangu-Ultra-MoE-718B) in the
benchmark, at tiny size on the CPU (``tiny_pangu_ultra_moe``: one leading
dense layer and four routed ones, 64 experts of which 8 held, 2 a token, 8
heads on a latent row of 32 + 8; ``tiny_reason``: contexts of 56 to 80 tokens
over pages of 8).

The system (the absorbed form over the paged latent cache) against the plain
reference (the expanded form, no cache) through the harness's own door and its
own comparison, and LOGITS against logits; the reference against the program's
dense forward and against itself (the shares add up to the uncut layer; a
second answer recomputed from the first's latent rows is the whole forward's);
planted faults, each REJECTED on every seed tried; the configuration, the cell
and the three readers."""

import copy
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import pb_helpers as pb
from perfbench import harness, loader, program_trace, serve_trace, weights
from test_perfbench_manifest import lint_config
from test_perfbench_program_trace import RAGGED, US, _write, op, span

serve = loader.load_part(pb.ROOT, "jobs", "serve")

CONFIG, TRAFFIC, CELL = "tiny_pangu_ultra_moe", "tiny_reason", \
    "pangu_ultra_moe_serve_reason"
SEEDS = (0, 1, 2, 3_500_000_019)
_runs = {}


def _run(seed):
    """A seed's streamed check requests, made once a module."""
    if seed not in _runs:
        _runs[seed] = pb.streamed(CONFIG, seed, None, TRAFFIC)
    return _runs[seed]


def _tols():
    return serve.tolerances(pb.serve_ctx(CONFIG, TRAFFIC))


def _judged(run, sizes=None, params=None):
    _, ref, own_params, own_sizes, prompts, produced = run
    rows = serve.routed_logit_gaps(
        ref.logits_and_routing_at, params or own_params, sizes or own_sizes,
        prompts, produced, _tols()["serve.router_margin"])
    checks = harness.Checks()
    serve.judge(checks, rows, _tols())
    return checks, rows


# ------------------------------------------------- the system = the reference
@pytest.mark.parametrize("seed", [1, 3_500_000_019])
def test_the_tiny_cell_runs_through_the_harness(tmp_path, seed, capsys):
    root = pb.tiny_root(tmp_path, [("tiny_reason_cell", CONFIG, TRAFFIC,
                                    "serve")])
    rc, result, last = pb.run(root, "tiny_reason_cell", seed=seed,
                              seconds=0.3)
    out = capsys.readouterr().out
    assert rc == 0 and result["correct"], out
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert json.loads(last) == result
    assert "CHECK serve.routed_two_answer_share" in out
    assert "CHECK serve.logit_gap_prompt48" in out


@pytest.mark.parametrize("seed", SEEDS)
def test_the_routed_check_passes_the_engine(seed, capsys):
    """Chunked prefill (prompts of 24 to 48 tokens in a budget of 64 beside
    the other two), single decode steps and the burst through the latent
    cache, contexts of 56 to 80 tokens: the engine's tokens against the
    reference's full forward."""
    checks, rows = _judged(_run(seed))
    assert checks.all_passed, capsys.readouterr().out
    assert sum(r[3] + r[5] for r in rows) == 3 * 32


def _float32_parts(seed, **model):
    """``(architecture, reference, configuration, model, float32 weights,
    sizes)`` of the preset with ``model`` fields changed."""
    config, arch, ref = pb.parts(CONFIG)
    config = copy.deepcopy(config)
    config["program"]["serve"]["model"] = dict(dtype="float32", **model)
    built, _ = arch.build(config, "serve")
    params = weights.seeded_weights(arch.param_shapes(built),
                                    harness.fold_seed(seed), jnp.float32)
    return arch, ref, config, built, params, arch.reference_sizes(config,
                                                                  "serve")


@pytest.mark.parametrize("seed", [0, 1])
def test_the_reference_is_the_programs_dense_forward(seed):
    _, ref, _, model, params, sizes = _float32_parts(seed)
    ids = np.random.default_rng(seed).integers(0, sizes["vocab_size"], 70)
    want = model.apply({"params": params}, jnp.asarray(ids)[None])[0]
    got = ref.logits_at(params, ids, np.arange(70), sizes)
    # two float32 programs that sum in another order (heads in blocks, the
    # width in column blocks): thousandths of the logits' spread
    np.testing.assert_allclose(got, want, atol=2e-3 * float(jnp.std(want)))
    assert np.array_equal(np.argmax(got, -1), np.argmax(want, -1))


def _engine_logits(seed, burst):
    """A prompt of 45 tokens through an engine that is float32 throughout
    (the cache too) with a budget of 16 rows (three chunks), then 14 decoded
    tokens: ``(reference, weights, sizes, prompt, generated tokens, the
    engine's logits row at each generated position that a ragged step
    computed, the engine)``."""
    from deepspeed_tpu.serving import build_serving_engine
    _, ref, _, model, params, sizes = _float32_parts(seed)
    sched = build_serving_engine(
        model, params=params,
        engine_config={"dtype": "float32", "decode_burst": burst,
                       "state_manager": {
                           "max_tracked_sequences": 2,
                           "max_ragged_sequence_count": 2, "max_context": 64,
                           "block_size": 8, "num_blocks": 64,
                           "max_ragged_batch_size": 16}},
        serving_config={"max_concurrent": 1})
    eng, rows, toks, seen = sched.engine, [], [], []
    step = eng._step_fn

    def spy(*args, **kw):
        out = step(*args, **kw)
        seen.append(out[0])
        return out
    if not burst:
        eng._step_fn = spy
    prompt = np.random.default_rng(seed).integers(
        0, sizes["vocab_size"], 45).tolist()
    sched.submit(prompt, max_new_tokens=14,
                 on_token=lambda t, done: toks.append(t))
    while not sched.idle:
        n = len(toks)
        sched.step()
        if len(toks) == n + 1 and not burst:    # a step that finished a row
            rows.append(np.asarray(seen[-1][1]))  # the one session: slot 1
    return ref, params, sizes, prompt, toks, rows, eng


@pytest.mark.parametrize("seed", [0, 5])
def test_the_engines_logits_are_the_references(seed):
    """LOGITS, not tokens: prefill in three chunks of 16 rows, then decode a
    step at a time through the latent cache (pages of 8: the reply crosses
    two page boundaries), each step's logits row against the reference's
    full forward over the whole sequence at that position."""
    ref, params, sizes, prompt, toks, rows, _ = _engine_logits(seed, 0)
    assert len(toks) == len(rows) == 14
    ids = np.asarray(prompt + toks[:-1], np.int32)
    at = np.arange(len(prompt) - 1, len(ids))
    want = np.asarray(ref.logits_at(params, ids, at, sizes))
    # float32 on both sides; the engine sums a page at a time in the absorbed
    # form, the reference all keys at once in the expanded one: thousandths

    np.testing.assert_allclose(np.stack(rows), want,
                               atol=2e-3 * float(np.std(want)))
    assert np.array_equal(np.argmax(want, -1), toks)


def test_the_burst_streams_the_steps_tokens():
    """The same request with bursts of 8: the tokens of the step-at-a-time
    run (whose logits are the reference's), and a burst did run."""
    *_, toks, _, _ = _engine_logits(0, 0)
    *_, burst_toks, _, eng = _engine_logits(0, 8)
    assert burst_toks == toks and eng.burst_steps > 0
    assert [tuple(x.shape for x in layer) for layer in eng.kv_cache.layers] \
        == [((64, 8, 128), )] * 5          # 40 values a token in rows of 128


def _uncut(seed):
    """The preset with all 64 experts held: ``(reference, float32 weights,
    sizes)``."""
    config, arch, ref = pb.parts(CONFIG)
    config = {k: v for k, v in config.items() if k != "share"}
    config["n_routed_experts"] = 64
    model, _ = arch.build(config, "serve")
    params = weights.seeded_weights(arch.param_shapes(model),
                                    harness.fold_seed(seed), jnp.float32)
    return ref, params, arch.reference_sizes(config, "serve")


def _share_of(params, sizes, chip, held=8):
    first = chip * held

    def cut(path, x):
        name = jax.tree_util.keystr(path)
        routed = name.endswith(("['moe']['w1']", "['moe']['w2']",
                                "['moe']['w3']"))
        return x[first:first + held] if routed else x
    return (jax.tree_util.tree_map_with_path(cut, params),
            dict(sizes, experts_held=held, first_expert=first))


def _scaled(leaf, factor):
    """The weights with every ``moe/<leaf>`` scaled."""
    def change(path, x):
        return x * factor if jax.tree_util.keystr(path).endswith(
            f"['moe']['{leaf}']") else x
    return lambda params: jax.tree_util.tree_map_with_path(change, params)


@pytest.mark.parametrize("seed", [0, 3_500_000_019])
def test_the_shares_add_up_to_the_uncut_layer(seed):
    """Eight shares of 8: the shares' routed parts plus the shared expert
    counted ONCE are the uncut reference's routed layer (the branch before
    its norm on the way out, where the sum is linear)."""
    ref, params, sizes = _uncut(seed)
    assert sizes["experts_held"] == 64 and sizes["first_expert"] == 0
    lp = params["layers_2"]
    h = jax.random.normal(harness.fold_seed(seed), (60, sizes["hidden_size"]))
    with jax.default_matmul_precision(ref.HIGHEST):
        whole = ref.moe_rows(h, lp["moe"], sizes)[0]
        shared = ref.moe_rows(h, _scaled("w2", 0.0)(params)["layers_2"][
            "moe"], sizes)[0]               # no routed expert adds
        parts = []
        for chip in range(8):
            p, s = _share_of(params, sizes, chip)
            parts.append(ref.moe_rows(h, p["layers_2"]["moe"], s)[0] - shared)
    scale = float(jnp.max(jnp.abs(whole - shared)))
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=1e-5 * scale)
    assert all(float(jnp.max(jnp.abs(p))) > 0.05 * scale for p in parts)
    assert float(jnp.max(jnp.abs(parts[0] + shared - whole))) > 0.1 * scale


def test_a_second_answer_from_the_firsts_latents_is_the_whole_forwards():
    """``flip`` at or after the first position asked for recomputes the
    suffix alone against the first answer's latent rows; the numbers are
    those of a pass over every token."""
    _, ref, params, sizes, prompts, produced = _run(0)
    ids = np.asarray(prompts[0] + produced[0][:-1], np.int32)
    at = np.arange(len(prompts[0]) - 1, len(ids))
    first, margins = ref.logits_and_routing_at(params, ids, at, sizes)
    assert ref._FIRST["start"] == at[0] and len(ref._FIRST["latent"]) == 5
    assert ref._FIRST["latent"][0].shape == (len(ids), 40)
    assert margins.shape == (len(at), 5)
    assert np.isinf(np.asarray(margins)[:, 0]).all()    # the dense layer
    tokens, layers = np.nonzero(np.isfinite(np.asarray(margins)))
    assert len(tokens) >= 2
    for i in (0, -1):
        flip = (int(layers[i]), int(at[tokens[i]]))
        fast, _ = ref.logits_and_routing_at(params, ids, at, sizes, flip=flip)
        kept = dict(ref._FIRST)
        ref._FIRST.clear()                           # nothing to start from
        whole, _ = ref.logits_and_routing_at(params, ids, at, sizes,
                                             flip=flip)
        ref._FIRST.update(kept)
        np.testing.assert_allclose(fast, whole, atol=2e-4)
        assert float(jnp.max(jnp.abs(fast - first))) > 1e-3


# ------------------------------------------------------------ planted faults
FAULTS = {
    "rope_part_of_the_score_dropped": dict(patch={
        "rope_score": lambda ref: lambda q_r, k_r: jnp.zeros(
            (q_r.shape[1], q_r.shape[0], k_r.shape[0]))}),
    "scaling_factor_dropped": dict(
        sizes=lambda s: dict(s, routed_scaling_factor=1.0)),
    "post_norms_dropped": dict(patch={"POST_NORMS": lambda ref: ()}),
    "weights_not_normalised": dict(
        sizes=lambda s: dict(s, norm_topk_prob=False)),
    "shared_expert_doubled": dict(params=_scaled("shared_down_proj']['kernel",
                                                 2.0)),
    "softmax_scale_of_the_nope_part_alone": dict(patch={
        "score_scale": lambda ref: lambda cfg: cfg["qk_nope_head_dim"]
        ** -0.5}),
}


@pytest.mark.parametrize("name", list(FAULTS))
def test_the_routed_check_rejects_a_planted_fault(name, monkeypatch):
    """Each fault is rejected on every seed.  A fault is planted in the
    reference (the comparison is symmetric): in what its sizes say, in the
    weights it is given, or in one of its functions."""
    fault = FAULTS[name]
    ref = _run(SEEDS[0])[1]
    for attr, make in fault.get("patch", {}).items():
        monkeypatch.setattr(ref, attr, make(ref))
    ref._layer_jit.clear_cache()           # traced with the sound functions
    try:
        rejected, gaps = [], []
        for seed in SEEDS:
            run = _run(seed)
            sizes = fault["sizes"](run[3]) if "sizes" in fault else None
            params = fault["params"](run[2]) if "params" in fault else None
            checks, rows = _judged(run, sizes, params)
            rejected.append(not checks.all_passed)
            gaps.append(max(r[1] for r in rows))
    finally:
        monkeypatch.undo()
        ref._layer_jit.clear_cache()
    assert all(rejected), (name, rejected, gaps)


def test_a_sound_run_reads_far_under_the_limit():
    worst = max(max(r[1] for r in _judged(_run(seed))[1]) for seed in SEEDS)
    assert worst < _tols()["serve.logit_gap"] / 3


@pytest.mark.parametrize("bits,rejected", [(4, True)])
def test_what_rounded_weights_read(bits, rejected):
    """The control the contract asks for: the ENGINE serves weights rounded
    to ``bits`` bits and is rejected."""
    for seed in SEEDS[:2]:
        run = pb.streamed(CONFIG, seed, pb.rounded_to(bits), TRAFFIC)
        assert _judged(run)[0].all_passed != rejected, (bits, seed)


# ------------------------------------------- the configuration and the cell
def test_the_configuration_is_the_drawn_row_as_one_chips_share():
    manifest = pb.read_manifest(pb.ROOT)
    entry = loader.find(manifest["configs"], "pangu_ultra_moe_1chip",
                        "config")
    body = loader.load_json(os.path.join(pb.ROOT, entry["file"]))
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    assert lint_config(body, entry["reduced"]) == []
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = [r for r in map(json.loads, open(catalog))
               if r["name"] == "openPangu-Ultra-MoE-718B"][0]
        assert entry["source"] == row["source_url"]
        assert {k: v for k, v in body["published"].items()
                if not k.startswith("_")} == row["config"]
    assert [body[k] for k in (
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "first_k_dense_replace", "num_experts_per_tok", "n_shared_experts",
        "routed_scaling_factor", "sandwich_norm")] == [
            7680, 18432, 2048, 128, 1536, 512, 128, 64, 128, 3, 8, 1, 2.5,
            True]
    assert (body["num_hidden_layers"], body["n_routed_experts"],
            body["vocab_size"], body["published"]["n_routed_experts"]) == (
                {"serve": 7}, 8, 19200, 256)
    assert body["share"]["chips_sharing_a_layer"] == 32 and \
        body["share"]["this_chip"] == 0 and set(body["share"]) == {
            "chips_sharing_a_layer", "this_chip", "how"}
    assert {"router", "routed_scaling_factor", "shared_expert",
            "softmax_scale", "rotary", "sandwich_norm",
            "multi_token_module"} <= set(body["assumed"])
    assert "USED BY NOTHING" in body["assumed"]["multi_token_module"]
    assert body["stands_for"]
    arch = loader.load_part(pb.ROOT, "models", "pangu_ultra_moe")
    sizes = arch.reference_sizes(body, "serve")
    assert (sizes["num_hidden_layers"], sizes["first_k_dense_replace"],
            sizes["experts_held"], sizes["first_expert"],
            sizes["vocab_size"]) == (7, 3, 8, 0, 19200)
    model, _ = arch.build(body, "serve")
    cfg = model.config
    assert (cfg.n_routed_experts, cfg.held, cfg.first_expert,
            cfg.kv_latent_dim, [cfg.routed(i) for i in range(7)]) == (
                256, 8, 0, 576, [False] * 3 + [True] * 4)
    shapes = arch.param_shapes(model)
    n = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    mla = 7680 * 1536 + 1536 * 128 * 192 + 7680 * 576 + 2 * 512 * 128 * 128 \
        + 16384 * 7680 + 1536 + 512
    expert = 3 * 7680 * 2048
    assert n == 7 * (mla + 4 * 7680) + 3 * 3 * 7680 * 18432 \
        + 4 * (9 * expert + 7680 * 256) + 2 * 19200 * 7680 + 7680
    assert 4.64e9 < n < 4.66e9
    assert shapes["layers_3"]["moe"]["w1"].shape == (8, 7680, 2048)
    assert shapes["layers_6"]["moe"]["gate"]["kernel"].shape == (7680, 256)
    assert shapes["layers_0"]["self_attn"]["kv_a_proj"]["kernel"].shape == \
        (7680, 576)
    assert "mlp" in shapes["layers_2"] and "moe" not in shapes["layers_2"]
    # the cache the engine builds for it: 7 buffers, 576 values a token in
    # rows of 640, 1 280 B a token a layer on the device
    eng = body["program"]["serve"]["engine"]
    assert set(eng) == {"max_concurrent", "block_size", "token_budget",
                        "decode_burst", "num_blocks"} == set(
                            body["program"]["serve"]["engine_why"])
    weights_gb = n * 2 / 1e9
    cache_gb = eng["num_blocks"] * eng["block_size"] * 640 * 2 * 7 / 1e9
    assert weights_gb + cache_gb >= 12.5


def test_the_cell_is_the_issues_traffic_on_one_chip():
    manifest = pb.read_manifest(pb.ROOT)
    cell = loader.find(manifest["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "pangu_ultra_moe_1chip", "reason_closed64", 1)
    t = loader.load_json(loader.part_path(pb.ROOT, "traffic",
                                          cell["traffic"], "json"))
    assert (t["job"], t["loop"], t["sessions"]) == ("serve", "closed", 64)
    assert t["prompt_len"] == {"dist": "lognormal", "median": 4096,
                               "sigma": 0.7, "min": 512, "max": 16384}
    assert t["output_len"] == {"dist": "geometric", "mean": 2048, "min": 128,
                               "max": 8192}
    assert (t["pool_size"], t["pool_seed"], t["check_new_tokens"],
            t["trace_seconds"]) == (256, 20260929, 32, 5.0)
    pools = {loader.load_json(os.path.join(
        pb.ROOT, "perfbench", "traffic", f))["pool_seed"] for f in os.listdir(
            os.path.join(pb.ROOT, "perfbench", "traffic"))
        if not f.startswith("tiny_") and f != "reason_closed64.json"
        and "pool_seed" in open(os.path.join(pb.ROOT, "perfbench", "traffic",
                                             f)).read()}
    assert t["pool_seed"] not in pools
    from perfbench import traffic_gen
    pool = traffic_gen.length_pool(t)
    prompts = np.array([p for p, _ in pool])
    replies = np.array([o for _, o in pool])
    assert (round(prompts.mean()), int(np.median(prompts)), prompts.min(),
            prompts.max()) == (4890, 3965, 685, 16384)
    assert (round(replies.mean()), replies.min(), replies.max()) == (
        2205, 128, 8192)
    of = lambda name: {
        m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]
        if name in m.get("workloads", [name])}
    mine, rag = of(CELL), of("command_a_plus_serve_rag")
    assert mine - rag == {"serve_latent_kernel_roofline_share",
                          "serve_mla_chunk_kernel_roofline_share",
                          "serve_mla_absorb_ms_per_step",
                          "serve_mla_down_ms_per_step"}
    # their readers take intermediate_size for an expert's width, divide by
    # every layer, or count window layers: not this cell's
    assert rag - mine == {"serve_moe_experts_roofline_share",
                          "serve_expert_copies_per_row",
                          "serve_window_page_share"}
    assert "serve_short_run_page_share" not in mine


# ------------------------------------------------------------------ readers
RECORD = {"trace": {"busy_s": 1.0},
          "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
STEP = "jit(ds_ragged_step_pangu_ultra_moe)/ds.attn/"
OPS = [
    op("%ds_paged_latent.3 = bf16[128,1024,512]{2,1,0} custom-call()", 0, 400,
       RAGGED, STEP + "pallas_call"),
    op("%ds_paged_latent.9 = bf16[9,1024,512]{2,1,0} custom-call()", 400, 500,
       RAGGED, "jit(ds_decode_burst)/while/body/ds.attn/pallas_call"),
    op("%fusion.4 = bf16[1024,128,512]{2,1,0} fusion()", 500, 560, RAGGED,
       STEP + "ds.mla_absorb/dot_general"),
    op("%fusion.5 = bf16[1024,128,128]{2,1,0} fusion()", 560, 590, RAGGED,
       STEP + "ds.mla_absorb/dot_general"),
    op("%fusion.6 = bf16[1024,128,192]{2,1,0} fusion()", 590, 710, RAGGED,
       STEP + "ds.mla_down/dot_general"),
    op("%scatter.1 = bf16[3400,128,640]{2,1,0} scatter()", 710, 720, RAGGED,
       STEP + "ds.kv_cache/scatter")]
STEPS = [
    span("ds:serve.step", 0, 450, step=1, kind="ragged", live_tokens=1000,
         absorbed_rows=1000, expanded_rows=0, grid_pages=4000,
         latent_keys=7 * 4_000_000, block_size=128),
    span("ds:serve.step", 450, 800, step=2, kind="burst", live_tokens=1024,
         absorbed_rows=1024, expanded_rows=0, grid_pages=50000,
         latent_keys=7 * 6_000_000, block_size=128)]


def _trace(steps, ops=OPS):
    return {
        "/device:TPU:0": {
            "XLA Modules": [(f"jit_ds_ragged_step_pangu_ultra_moe({RAGGED})",
                             0, 1000 * US, {}, {})],
            "XLA Ops": ops},
        "/host:CPU": {"python3": [span("pb:traced", 0, 900)] + steps}}


@pytest.fixture
def traced(tmp_path, monkeypatch):
    """A checkout whose newest trace is the new cell's."""
    monkeypatch.setattr(program_trace, "ROOT", str(tmp_path))
    monkeypatch.setattr(program_trace, "_CACHE", {})
    monkeypatch.setattr(serve_trace, "_CACHE", {})
    for name in ("BENCHMARK.json", "perfbench/configs"):
        os.makedirs(os.path.dirname(tmp_path / name), exist_ok=True)
        os.symlink(os.path.join(pb.ROOT, name), tmp_path / name)
    return lambda trace: _write(tmp_path, trace, cell=CELL)


METRICS = ("serve_latent_kernel_roofline_share",
           "serve_mla_absorb_ms_per_step", "serve_mla_down_ms_per_step")


def test_the_readers_read_the_new_scopes_kernel_and_counts(traced):
    traced(_trace(STEPS))
    read = lambda metric: loader.load_reader(pb.ROOT, metric).read(RECORD)
    assert read("serve_mla_absorb_ms_per_step") == pytest.approx(0.090 / 2)
    assert read("serve_mla_down_ms_per_step") == pytest.approx(0.120 / 2)
    roof = loader.load_reader(pb.ROOT, METRICS[0])
    assert roof.must_move_bytes(1, 1, 128, 128, 512, 64) == \
        (128 * 576 + 128 * (576 + 512)) * 2
    assert roof.must_compute_flops(1, 128, 512, 64) == 128 * (576 + 512) * 2
    floor = sum(max(
        roof.must_move_bytes(7 * pages, 7 * rows, 128, 128, 512, 64) / 819e9,
        roof.must_compute_flops(keys, 128, 512, 64) / 197e12)
        for pages, rows, keys in ((4000, 1000, 7 * 4_000_000),
                                  (50000, 1024, 7 * 6_000_000)))
    assert roof.read(RECORD) == pytest.approx(100 * floor / 500e-6)
    # the first step is bound by its operations, the burst by its page loads
    assert roof.must_compute_flops(28e6, 128, 512, 64) / 197e12 > \
        roof.must_move_bytes(28000, 7000, 128, 128, 512, 64) / 819e9
    assert roof.must_compute_flops(42e6, 128, 512, 64) / 197e12 < \
        roof.must_move_bytes(350000, 7168, 128, 128, 512, 64) / 819e9


@pytest.mark.parametrize("metric", METRICS)
def test_a_reader_gives_nothing_on_a_program_without_its_names(
        metric, traced, monkeypatch):
    """The parent commit's program: no scope, no kernel, no count.  Nothing
    is read and nothing is raised; an untraced run and no trace file alike."""
    reader = loader.load_reader(pb.ROOT, metric)
    assert reader.read(RECORD) is None                    # no trace file
    bare = [e[:3] + ({k: v for k, v in e[3].items() if k in (
        "step", "kind", "live_tokens", "grid_pages", "block_size")}, )
        + e[4:] for e in STEPS]
    parents = [op(o[0].replace("ds_paged_latent", "ds_paged_runs"),
                  o[1] / US, o[2] / US, RAGGED,
                  "jit(ds_ragged_step_cohere2_moe)/ds.attn/dot_general")
               for o in OPS]
    traced(_trace(bare, parents))
    assert reader.read({"trace": None}) is None           # an untraced run
    assert reader.read(RECORD) is None
    names = program_trace.program_names()
    for scope in ("SCOPE_MLA_ABSORB", "SCOPE_MLA_DOWN"):
        monkeypatch.delattr(names, scope)
    assert reader.read(RECORD) is None
