"""Architecture ``motif`` (Motif-3-Beta) in the benchmark, at tiny size on
the CPU (``tiny_motif3``: one dense layer and three routed ones, 64 experts of
which 8 held, 2 a token, 10 heads in 2 K/V groups on a latent row of 32 + 8,
a window of 16 on three layers of four, four streams; ``tiny_reason``:
contexts of 56 to 80 tokens over pages of 8, so every context passes the
window and crosses page edges).

The system (the absorbed form over the paged, windowed latent cache) against
the plain reference (the expanded form, no cache) through the harness's own
comparison, and LOGITS against logits (the engine against the program's dense
forward: ``tests/unit/inference/test_motif.py``); the reference against
itself (the shares add up to the uncut layer);
planted faults, each REJECTED; the configuration, the cell (looked up by
NAME: a later PR appends) and the five readers."""

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

CONFIG, TRAFFIC, CELL = "tiny_motif3", "tiny_reason", \
    "motif3_beta_serve_reason"
SEEDS = (0, 5_800_000_019)
_runs = {}


def _run(seed):
    """A seed's streamed check requests, made once a module."""
    if seed not in _runs:
        _runs[seed] = pb.streamed(CONFIG, seed, None, TRAFFIC)
    return _runs[seed]


def _tols():
    return serve.tolerances(pb.serve_ctx(CONFIG, TRAFFIC))


def _judged(run, sizes=None, params=None, requests=slice(None)):
    _, ref, own_params, own_sizes, prompts, produced = run
    prompts, produced = prompts[requests], produced[requests]
    rows = serve.routed_logit_gaps(
        ref.logits_and_routing_at, params or own_params, sizes or own_sizes,
        prompts, produced, _tols()["serve.router_margin"])
    checks = harness.Checks()
    serve.judge(checks, rows, _tols())
    return checks, rows


# ------------------------------------------------- the system = the reference
@pytest.mark.parametrize("seed", SEEDS)
def test_the_routed_check_passes_the_engine(seed, capsys):
    """Chunked prefill, single decode steps and the burst through the
    windowed latent cache: the engine's tokens against the reference's full
    forward, by the job's own rule."""
    checks, rows = _judged(_run(seed))
    assert checks.all_passed, capsys.readouterr().out
    assert sum(r[3] + r[5] for r in rows) == 3 * 32


def _float32_parts(seed, **model):
    config, arch, ref = pb.parts(CONFIG)
    config = copy.deepcopy(config)
    config["program"]["serve"]["model"] = dict(dtype="float32", **model)
    built, _ = arch.build(config, "serve")
    params = weights.seeded_weights(arch.param_shapes(built),
                                    harness.fold_seed(seed), jnp.float32)
    return arch, ref, config, built, params, arch.reference_sizes(config,
                                                                  "serve")


def test_the_engines_logits_are_the_references():
    """LOGITS, not tokens: a prompt of 45 tokens prefilled in three chunks of
    16 rows, then 14 tokens decoded a step at a time through the latent cache
    (pages of 8, a window of 16), each step's logits row against the
    reference's full forward over the whole sequence at that position."""
    from deepspeed_tpu.serving import build_serving_engine
    _, ref, _, model, params, sizes = _float32_parts(0)
    sched = build_serving_engine(
        model, params=params,
        engine_config={"dtype": "float32", "decode_burst": 0,
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
    eng._step_fn = spy
    prompt = np.random.default_rng(0).integers(
        0, sizes["vocab_size"], 45).tolist()
    sched.submit(prompt, max_new_tokens=14,
                 on_token=lambda t, done: toks.append(t))
    while not sched.idle:
        n = len(toks)
        sched.step()
        if len(toks) == n + 1:              # a step that finished a row
            rows.append(np.asarray(seen[-1][1]))
    assert len(toks) == len(rows) == 14
    ids = np.asarray(prompt + toks[:-1], np.int32)
    at = np.arange(len(prompt) - 1, len(ids))
    want = np.asarray(ref.logits_at(params, ids, at, sizes))
    np.testing.assert_allclose(np.stack(rows), want,
                               atol=2e-3 * float(np.std(want)))
    assert np.array_equal(np.argmax(want, -1), toks)


def _uncut(seed):
    """The preset with all 64 experts held: ``(reference, float32 weights,
    sizes)``."""
    config, arch, ref = pb.parts(CONFIG)
    config = {k: v for k, v in config.items() if k != "share"}
    config["num_experts"] = 64
    model, _ = arch.build(config, "serve")
    params = weights.seeded_weights(arch.param_shapes(model),
                                    harness.fold_seed(seed), jnp.float32)
    return ref, params, arch.reference_sizes(config, "serve")


def test_the_shares_add_up_to_the_uncut_layer():
    """Eight shares of 8 (each with its own experts' PolyNorm numbers): the
    shares' routed parts plus the shared expert counted ONCE are the uncut
    reference's routed layer."""
    ref, params, sizes = _uncut(0)
    assert sizes["experts_held"] == 64 and sizes["first_expert"] == 0
    moe = params["layers_2"]["moe"]
    h = jax.random.normal(harness.fold_seed(0), (60, sizes["hidden_size"]))
    with jax.default_matmul_precision(ref.HIGHEST):
        whole = ref.moe_rows(h, moe, sizes)[0]
        shared = ref.moe_rows(h, dict(moe, w2=0 * moe["w2"]), sizes)[0]
        parts = []
        for chip in range(8):
            cut = dict(moe, **{n: moe[n][8 * chip:8 * chip + 8]
                               for n in ("w1", "w2", "w3", "poly")})
            s = dict(sizes, experts_held=8, first_expert=8 * chip)
            parts.append(ref.moe_rows(h, cut, s)[0] - shared)
    scale = float(jnp.max(jnp.abs(whole - shared)))
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=1e-5 * scale)
    assert all(float(jnp.max(jnp.abs(p))) > 0.05 * scale for p in parts)


# ------------------------------------------------------------ planted faults
#: the issue's faults, as the sizes' named switches (``tools/
#: serve_fault_check.py`` plants the same on the chip)
FAULTS = {
    "lambda_zero": dict(differential=False),
    "window_dropped_on_a_window_layer": dict(window_dropped_on_layer=2),
    "window_put_on_a_full_layer": dict(window_put_on_layer=3),
    "one_sinkhorn_sweep": dict(mhc_sinkhorn_iters=1),
    "h_res_identity": dict(mhc_identity_res=True),
    "silu_for_polynorm": dict(hidden_act="silu"),
    "cache_row_in_8_bits": dict(cache_row_mantissa_bits=3),
}


@pytest.mark.parametrize("name", list(FAULTS))
def test_the_routed_check_rejects_a_planted_fault(name):
    """Each fault is rejected by the job's own rule.  Planted in the
    reference (the comparison is symmetric), in what its sizes say; judged on
    the longest check request (a context of 80 tokens: five windows), which
    a sound reference passes (the test above)."""
    run = _run(SEEDS[0])
    longest = max(range(3), key=lambda i: len(run[4][i]))
    checks, rows = _judged(run, dict(run[3], **FAULTS[name]),
                           requests=slice(longest, longest + 1))
    assert not checks.all_passed, (name, [r[1] for r in rows])


def test_a_sound_run_reads_far_under_the_limit():
    worst = max(max(r[1] for r in _judged(_run(seed))[1]) for seed in SEEDS)
    assert worst < _tols()["serve.logit_gap"] / 3


# ------------------------------------------- the configuration and the cell
def test_the_configuration_is_the_drawn_row_as_one_chips_share():
    manifest = pb.read_manifest(pb.ROOT)
    entry = loader.find(manifest["configs"], "motif3_beta_1chip", "config")
    body = loader.load_json(os.path.join(pb.ROOT, entry["file"]))
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert lint_config(body, entry["reduced"]) == []
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = [r for r in map(json.loads, open(catalog))
               if r["name"] == "Motif-3-Beta"][0]
        assert entry["source"] == row["source_url"]
        assert {k: v for k, v in body["published"].items()
                if not k.startswith("_")} == row["config"]
    assert (body["num_hidden_layers"], body["num_experts"],
            body["vocab_size"], body["published"]["num_experts"]) == (
                {"serve": 8}, 24, 27520, 384)
    assert body["share"]["chips_sharing_a_layer"] == 16 and \
        body["share"]["this_chip"] == 0
    assert {"noise_heads", "lambda_and_gate", "o_proj", "window_pattern",
            "mhc", "polynorm", "mla_norms", "router", "carried", "float32",
            "weights"} <= set(body["assumed"])
    assert "NOT guessed" in body["assumed"]["mhc"]
    assert "USED BY NOTHING" in body["assumed"]["carried"]
    arch = loader.load_part(pb.ROOT, "models", "motif")
    sizes = arch.reference_sizes(body, "serve")
    assert (sizes["num_hidden_layers"], sizes["n_dense_first_layers"],
            sizes["experts_held"], sizes["first_expert"],
            sizes["vocab_size"]) == (8, 2, 24, 0, 27520)
    model, _ = arch.build(body, "serve")
    cfg = model.config
    assert (cfg.num_experts, cfg.held, cfg.first_expert, cfg.kv_latent_dim,
            cfg.layer_windows) == (384, 24, 0, 576, (128, 128, 128, 0) * 2)
    shapes = arch.param_shapes(model)
    n = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    attn = 4096 * 1024 + 1024 * 80 * 192 + 4096 * 576 + 2 * 512 * 16 * 128 \
        + 4096 * 64 + 2 * 4096 * 8192 + 1024 + 512
    mhc = 2 * (16384 * 24 + 16384 + 3 + 24) + 2 * 4096
    expert = 3 * 4096 * 1280
    assert n == 8 * (attn + mhc) + 2 * (3 * 4096 * 12288 + 4) \
        + 6 * (25 * expert + 4096 * 384 + 25 * 4) + 2 * 27520 * 4096 + 4096
    assert 3.63e9 < n < 3.65e9
    assert shapes["layers_2"]["moe"]["w1"].shape == (24, 4096, 1280)
    assert shapes["layers_7"]["moe"]["gate"]["kernel"].shape == (4096, 384)
    assert "mlp" in shapes["layers_1"] and "moe" not in shapes["layers_1"]
    eng = body["program"]["serve"]["engine"]
    assert set(eng) == {"max_concurrent", "block_size", "token_budget",
                        "decode_burst", "num_blocks"} == set(
                            body["program"]["serve"]["engine_why"])
    cache_gb = eng["num_blocks"] * eng["block_size"] * 640 * 2 * 8 / 1e9
    assert n * 2 / 1e9 + cache_gb >= 0.6 * 16.9     # 25 % is the floor


def test_the_cell_is_the_issues_traffic_on_one_chip():
    manifest = pb.read_manifest(pb.ROOT)
    cell = loader.find(manifest["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "motif3_beta_1chip", "reason_closed64", 1)
    pangu = loader.find(manifest["workloads"],
                        "pangu_ultra_moe_serve_reason", "workload")
    assert pangu["traffic"] == cell["traffic"]     # the model alone differs
    of = lambda name: {
        m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]
        if name in m.get("workloads", [name])}
    mine, other = of(CELL), of("pangu_ultra_moe_serve_reason")
    assert mine - other == {
        "serve_mhc_ms_per_step", "serve_diff_combine_ms_per_step",
        "serve_polynorm_ms_per_step", "serve_gdla_absorbed_roofline_share",
        "serve_gdla_chunk_roofline_share",
        "serve_polynorm_experts_roofline_share",
        "serve_expert_copies_per_routed_row", "serve_window_page_share"}
    # the two rooflines that know no window, 80 heads or 16 groups, the row
    # share whose list its own test pins, and the five that
    # test_perfbench_step_trace.py pins to four cells
    assert other - mine == {
        "serve_latent_kernel_roofline_share",
        "serve_mla_chunk_kernel_roofline_share", "serve_expanded_row_share",
        "serve_ragged_step_device_ms", "serve_burst_iteration_device_ms",
        "serve_ragged_paged_kernel_ms",
        "serve_burst_paged_kernel_ms_per_iteration",
        "serve_launch_slack_ms_p05"}
    for m in manifest["per_layer"]:
        if m["name"] in mine - other - {"serve_window_page_share"}:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "serve_tokens_per_s"


# ------------------------------------------------------------------ readers
RECORD = {"trace": {"busy_s": 1.0},
          "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
STEP = "jit(ds_ragged_step_motif)/"
OPS = [
    op("%ds_paged_latent.3 = bf16[128,640,512]{2,1,0} custom-call()", 0, 400,
       RAGGED, STEP + "ds.attn/pallas_call"),
    op("%ds_paged_latent.9 = bf16[9,640,512]{2,1,0} custom-call()", 400, 500,
       RAGGED, "jit(ds_decode_burst)/while/body/ds.attn/pallas_call"),
    op("%fusion.4 = f32[24,1024]{1,0} fusion()", 500, 560, RAGGED,
       STEP + "ds.mhc/dot_general"),
    op("%fusion.5 = bf16[1024,4,4096]{2,1,0} fusion()", 560, 590, RAGGED,
       STEP + "ds.mhc/add"),
    op("%fusion.6 = bf16[1024,64,512]{2,1,0} fusion()", 590, 710, RAGGED,
       STEP + "ds.attn/ds.diff_attn/sub"),
    op("%fusion.7 = bf16[640,1280]{1,0} fusion()", 710, 720, RAGGED,
       STEP + "ds.mlp/ds.moe_experts/ds.polynorm/mul")]
STEPS = [
    span("ds:serve.step", 0, 450, step=1, kind="ragged", live_tokens=1000,
         absorbed_rows=1000, expanded_rows=0, grid_pages=9000,
         grid_pages_window=3000, grid_pages_full=6000,
         latent_keys=6 * 128_000 + 2 * 4_000_000, block_size=128,
         expert_copies=3000, expert_active=140),
    span("ds:serve.step", 450, 800, step=2, kind="burst", live_tokens=1024,
         absorbed_rows=1024, expanded_rows=0, grid_pages=110000,
         grid_pages_window=12000, grid_pages_full=98000,
         latent_keys=6 * 131_072 + 2 * 6_000_000, block_size=128,
         expert_copies=3144, expert_active=144)]


def _trace(steps, ops=OPS):
    return {
        "/device:TPU:0": {
            "XLA Modules": [(f"jit_ds_ragged_step_motif({RAGGED})",
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


METRICS = ("serve_mhc_ms_per_step", "serve_diff_combine_ms_per_step",
           "serve_polynorm_ms_per_step", "serve_gdla_absorbed_roofline_share",
           "serve_gdla_chunk_roofline_share",
           "serve_polynorm_experts_roofline_share",
           "serve_expert_copies_per_routed_row")


def test_the_readers_read_the_new_scopes_kernel_and_counts(traced):
    traced(_trace(STEPS))
    read = lambda metric: loader.load_reader(pb.ROOT, metric).read(RECORD)
    assert read("serve_mhc_ms_per_step") == pytest.approx(0.090 / 2)
    assert read("serve_diff_combine_ms_per_step") == pytest.approx(0.120 / 2)
    assert read("serve_polynorm_ms_per_step") == pytest.approx(0.010 / 2)
    roof = loader.load_reader(pb.ROOT, METRICS[3])
    assert roof.must_move_bytes(1, 1, 128, 80, 512, 64) == \
        (128 * 576 + 80 * (576 + 512)) * 2
    assert roof.must_compute_flops(1, 80, 512, 64) == 80 * (576 + 512) * 2
    floor = sum(max(
        roof.must_move_bytes(pages, 8 * rows, 128, 80, 512, 64) / 819e9,
        roof.must_compute_flops(keys, 80, 512, 64) / 197e12)
        for pages, rows, keys in (
            (9000, 1000, 6 * 128_000 + 2 * 4_000_000),
            (110000, 1024, 6 * 131_072 + 2 * 6_000_000)))
    assert roof.read(RECORD) == pytest.approx(100 * floor / 500e-6)
    chunk = loader.load_reader(pb.ROOT, METRICS[4])
    widths = chunk.widths_of({"num_attention_heads": 80,
                              "num_key_value_heads": 16, "kv_lora_rank": 512,
                              "qk_rope_head_dim": 64, "head_dim": 192,
                              "v_head_dim": 128})
    assert widths == (80, 16, 512, 64, 128, 128)
    # the pairs for all 80 heads, the keys and values made for 16 groups
    assert chunk.must_compute_flops(10, 3, *widths) == 2 * (
        80 * 10 * 320 + 16 * 3 * 512 * 256)
    assert chunk.must_move_bytes(3, 10, 80, 512, 64, 128, 128) == (
        3 * 576 + 10 * 80 * 320) * 2
    # the held experts: 24 of 384 at a width of 1280, six routed layers of 8
    experts = loader.load_reader(pb.ROOT, "serve_moe_experts_roofline_share")
    floor = sum(max(
        experts.must_move_bytes(active, copies, 4096, 1280) / 819e9,
        experts.must_compute_flops(copies, 4096, 1280) / 197e12)
        for active, copies in ((140, 3000), (144, 3144)))
    assert read(METRICS[5]) == pytest.approx(100 * floor / 10e-6)
    assert read(METRICS[6]) == pytest.approx((3000 + 3144) / (2024 * 6))


@pytest.mark.parametrize("metric", METRICS)
def test_a_reader_gives_nothing_on_a_program_without_its_names(
        metric, traced, monkeypatch):
    """The parent commit's program: no scope, no count by layer kind.
    Nothing is read and nothing is raised; an untraced run and no trace file
    alike."""
    reader = loader.load_reader(pb.ROOT, metric)
    assert reader.read(RECORD) is None                    # no trace file
    bare = [e[:3] + ({k: v for k, v in e[3].items() if k in (
        "step", "kind", "live_tokens", "grid_pages", "block_size",
        "latent_keys", "absorbed_rows")}, ) + e[4:] for e in STEPS]
    parents = [op(o[0], o[1] / US, o[2] / US, RAGGED,
                  "jit(ds_ragged_step_pangu_ultra_moe)/ds.attn/dot_general")
               for o in OPS]
    traced(_trace(bare, parents))
    assert reader.read({"trace": None}) is None           # an untraced run
    assert reader.read(RECORD) is None
    names = program_trace.program_names()
    for scope in ("SCOPE_MHC", "SCOPE_DIFF_ATTN", "SCOPE_POLYNORM"):
        monkeypatch.delattr(names, scope)
    assert reader.read(RECORD) is None
