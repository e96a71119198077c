"""Architecture ``cohere2_moe`` (Command A+) in the benchmark, at tiny size on
the CPU (``tiny_command_a_plus``: 16 experts of which 8 held, 2 a token, 2
shared, a window of 24 on three layers of four; ``tiny_rag``: contexts two
to three windows long).

The system against the plain reference through the harness's own door and its
own comparison; the reference against the program's dense forward and against
itself (the shares add up to the uncut layer; a second answer recomputed from
the first's keys is the whole forward's); ten planted faults, each REJECTED
on every seed tried; the configuration, the cell and the five readers."""

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

CONFIG, TRAFFIC, CELL = "tiny_command_a_plus", "tiny_rag", \
    "command_a_plus_serve_rag"
SEEDS = (0, 1, 2, 3, 7, 3_300_000_019)
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
@pytest.mark.parametrize("seed", [1, 3_300_000_019])
def test_the_tiny_cell_runs_through_the_harness(tmp_path, seed, capsys):
    root = pb.tiny_root(tmp_path, [("tiny_rag_cell", CONFIG, TRAFFIC,
                                    "serve")])
    rc, result, last = pb.run(root, "tiny_rag_cell", seed=seed, seconds=0.3)
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
    the other two), single decode steps and the burst, contexts of 56 to 80
    tokens over a window of 24: the engine's tokens against the reference's
    full forward."""
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
    np.testing.assert_allclose(got, want, atol=2e-3 * float(jnp.std(want)))
    assert np.array_equal(np.argmax(got, -1), np.argmax(want, -1))


def _uncut(seed):
    """The preset with all 16 experts held: ``(reference, float32 weights,
    sizes)``."""
    config, arch, ref = pb.parts(CONFIG)
    config = {k: v for k, v in config.items() if k != "share"}
    config["num_experts"] = 16
    model, _ = arch.build(config, "serve")
    params = weights.seeded_weights(arch.param_shapes(model),
                                    harness.fold_seed(seed), jnp.float32)
    return ref, params, arch.reference_sizes(config, "serve")


def _share_of(params, sizes, chip, held=8):
    first = chip * held

    def cut(path, x):
        name = jax.tree_util.keystr(path)
        routed = "moe" in name and name.rstrip("']").endswith(
            ("w1", "w2", "w3")) and "shared" not in name
        return x[first:first + held] if routed else x
    return (jax.tree_util.tree_map_with_path(cut, params),
            dict(sizes, experts_held=held, first_expert=first))


@pytest.mark.parametrize("layer", [0, 3], ids=["sliding", "full"])
@pytest.mark.parametrize("seed", [0, 3_300_000_019])
def test_the_shares_add_up_to_the_uncut_layer(seed, layer):
    """Two shares of 8: the shares' routed parts plus attention and the
    shared experts counted ONCE are the uncut reference's layer, and the
    PROGRAM's layer of each share is the reference's of that share."""
    ref, params, sizes = _uncut(seed)
    assert sizes["experts_held"] == 16 and sizes["first_expert"] == 0
    lp, kind = params[f"layers_{layer}"], sizes["layer_kinds"][layer]
    x = jax.random.normal(harness.fold_seed(seed), (60, sizes["hidden_size"]))
    from deepspeed_tpu.models import cohere2_moe as program
    with jax.default_matmul_precision(ref.HIGHEST):
        whole = ref.layer(x, lp, sizes, kind)[0]
        h = ref.layer_norm(x, lp["input_layernorm"]["weight"], 1e-5)
        alike = whole - ref.moe_part(h, lp["moe"], sizes)[0]      # x + a
        shared = ref.moe_part(h, _scaled("w2", 0.0)(params)[
            f"layers_{layer}"]["moe"], sizes)[0]     # no routed expert adds
        parts = []
        for chip in (0, 1):
            p, s = _share_of(params, sizes, chip)
            part = ref.layer(x, p[f"layers_{layer}"], s, kind)[0]
            parts.append(part - alike - shared)
            cfg = program.Cohere2MoeConfig(**{
                **{k: sizes[k] for k in (
                    "vocab_size", "hidden_size", "intermediate_size",
                    "num_attention_heads", "num_key_value_heads", "head_dim",
                    "sliding_window", "num_experts_per_tok",
                    "num_shared_experts", "rope_theta", "layer_norm_eps")},
                "num_hidden_layers": 4, "num_experts": 16, "experts_held": 8,
                "first_expert": 8 * chip, "dtype": "float32"})
            own = program.Cohere2MoeLayer(cfg, cfg.layer_windows[layer]) \
                .apply({"params": p[f"layers_{layer}"]}, x[None])[0]
            np.testing.assert_allclose(own, part, atol=2e-4)
    scale = float(jnp.max(jnp.abs(whole - alike)))
    np.testing.assert_allclose(sum(parts) + alike + shared, whole,
                               atol=1e-5 * scale)
    assert all(float(jnp.max(jnp.abs(p))) > 0.05 * scale for p in parts)


@pytest.mark.parametrize("seed", [0, 5])
def test_a_second_answer_from_the_firsts_keys_is_the_whole_forwards(seed):
    """``flip`` at or after the first position asked for recomputes the
    suffix alone; the numbers are those of a pass over every token."""
    _, ref, params, sizes, prompts, produced = _run(seed)
    ids = np.asarray(prompts[0] + produced[0][:-1], np.int32)
    at = np.arange(len(prompts[0]) - 1, len(ids))
    first, margins = ref.logits_and_routing_at(params, ids, at, sizes)
    assert ref._FIRST["start"] == at[0] and len(ref._FIRST["kv"]) == 4
    # three (token, layer) whose pair of experts is not wholly held elsewhere
    tokens, layers = np.nonzero(np.isfinite(np.asarray(margins)))
    assert len(tokens) >= 3
    for i in (0, len(tokens) // 2, -1):
        flip = (int(layers[i]), int(at[tokens[i]]))
        fast, _ = ref.logits_and_routing_at(params, ids, at, sizes, flip=flip)
        kept = dict(ref._FIRST)
        ref._FIRST.clear()                           # nothing to start from
        whole, _ = ref.logits_and_routing_at(params, ids, at, sizes,
                                             flip=flip)
        ref._FIRST.update(kept)
        np.testing.assert_allclose(fast, whole, atol=2e-4)
        assert float(jnp.max(jnp.abs(fast - first))) > 1e-3
    # an earlier token's flip is a whole pass (nothing before it is kept)
    early, _ = ref.logits_and_routing_at(params, ids, at, sizes, flip=(1, 2))
    assert early.shape == first.shape and margins.shape == (len(at), 4)


def test_expert_copies_are_the_live_pairs_the_reference_routes():
    """A float32 engine and the float32 reference route alike: the steps'
    ``expert_copies`` sum to the (token, layer, expert) weights the reference
    gives held experts, and dead rows of the 64-row buffer add none."""
    arch, ref, config, model, params, sizes = _float32_parts(4)
    ctx = pb.serve_ctx(CONFIG, TRAFFIC)
    ctx.config = config
    sched = serve.build_scheduler(ctx, model, params)
    ids = np.random.default_rng(4).integers(0, sizes["vocab_size"], 45)
    sched.submit(ids.tolist(), max_new_tokens=1)
    copies = live = 0
    while not sched.idle:
        sched.step()
        counts = sched.engine.last_step_counts
        copies += counts["expert_copies"]
        live += counts["live_tokens"]
        assert counts["token_budget"] == 64
    assert live == 45
    x, want = ref.embed(params, jnp.asarray(ids)), 0
    with jax.default_matmul_precision(ref.HIGHEST):
        for i, kind in enumerate(sizes["layer_kinds"]):
            x, _, _, w, _, _ = ref.layer(x, params[f"layers_{i}"], sizes, kind)
            want += int((np.asarray(w)[:, :8] > 0).sum())
    assert copies == want and 0 < want < 45 * 2 * 4


# ------------------------------------------------------------ planted faults
def _half_split(x, positions, theta):
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = positions.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * weight


def _sequential(ref):
    def layer(x, lp, cfg, kind, pos0=0, k_before=None, v_before=None,
              flip_token=-1, weights=None):
        norm = lambda y: ref.layer_norm(
            y, ref.f32(lp["input_layernorm"]["weight"]),
            cfg["layer_norm_eps"])
        a, k, v = ref.attention_part(norm(x), ref.f32(lp["self_attn"]), cfg,
                                     kind, pos0, k_before, v_before)
        x = x + a
        moe, router, margin, weights = ref.moe_part(norm(x), lp["moe"], cfg,
                                                    flip_token, weights)
        return x + moe, router, margin, weights, k, v
    return layer


def _scaled(leaf, factor, index=None):
    """The weights with ``moe/<leaf>`` (or its entry ``index``) scaled."""
    def change(path, x):
        if not jax.tree_util.keystr(path).endswith(f"['moe']['{leaf}']"):
            return x
        return x * factor if index is None else x.at[index].multiply(factor)
    return lambda params: jax.tree_util.tree_map_with_path(change, params)


FAULTS = {
    "softmax_scores": dict(patch={"score": lambda ref: jnp.exp}),
    "weights_not_normalised": dict(
        sizes=lambda s: dict(s, norm_topk_prob=False)),
    "shared_experts_summed": dict(params=_scaled("shared_w2", 2.0)),
    "rotary_on_the_full_layer": dict(patch={"ROTARY_KINDS": lambda r: "SF"}),
    "window_on_the_full_layer": dict(patch={"WINDOW_KINDS": lambda r: "SF"}),
    "no_window_on_a_sliding_layer": dict(
        patch={"WINDOW_KINDS": lambda r: ""}),
    "half_split_rotary": dict(patch={"rotary_pairs": lambda r: _half_split}),
    "sequential_block": dict(patch={"layer": _sequential}),
    "rms_norm": dict(patch={"layer_norm": lambda r: _rms_norm}),
    "dropped_held_expert": dict(params=_scaled("w2", 0.0, 0)),
}


#: the faults the configuration's limits reject on SOME seeds only.  A sound
#: run reads a gap of 0 to 0.006 on these seeds and these faults 0.12 to 1.4,
#: twenty times that on every seed; but the limit is 3 x the worst of sixty
#: sound seeds, and one of the sixty (48) read 0.093 (two flips at once in a
#: short request, which the second answers do not cover): 0.28.
SUBTLE = {"softmax_scores": 5, "rotary_on_the_full_layer": 3,
          "window_on_the_full_layer": 5}


@pytest.mark.parametrize("name", list(FAULTS))
def test_the_routed_check_rejects_a_planted_fault(name, monkeypatch):
    """Each fault is rejected on every seed, but the three of ``SUBTLE``: those
    on the stated number of the six seeds at least, and on every seed they
    read over fifteen times a sound run's worst gap.  A fault is planted in
    the reference (the comparison is symmetric): in what its sizes say, in
    the weights it is given, or in one of its functions."""
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
    assert sum(rejected) >= SUBTLE.get(name, len(SEEDS)), (name, rejected,
                                                           gaps)
    assert min(gaps) > 0.1, (name, gaps)       # a sound run: 0 to 0.006


def test_a_sound_run_reads_far_under_what_the_faults_read():
    assert max(max(r[1] for r in _judged(_run(seed))[1])
               for seed in SEEDS) < 0.01


@pytest.mark.parametrize("bits,rejected", [(4, True), (8, False)])
def test_what_rounded_weights_read(bits, rejected):
    """The control the contract asks for, as it stands: the ENGINE serves
    weights rounded to ``bits`` bits.  Four bits are rejected; eight are NOT
    (gaps of 0 to 0.093 over six seeds against the limit of 0.28: PERF.md
    section 7 says what would have to change)."""
    for seed in SEEDS[:2]:
        run = pb.streamed(CONFIG, seed, pb.rounded_to(bits), TRAFFIC)
        assert _judged(run)[0].all_passed != rejected, (bits, seed)


# ------------------------------------------- the configuration and the cell
def test_the_configuration_is_the_drawn_row_as_one_chips_share():
    manifest = pb.read_manifest(pb.ROOT)
    entry = loader.find(manifest["configs"], "command_a_plus_1chip", "config")
    body = loader.load_json(os.path.join(pb.ROOT, entry["file"]))
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert lint_config(body, entry["reduced"]) == []
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = [r for r in map(json.loads, open(catalog))
               if r["name"] == "command-a-plus-05-2026"][0]
        assert entry["source"] == row["source_url"]
        assert {k: v for k, v in body["published"].items()
                if not k.startswith("_")} == row["config"]
    assert (body["hidden_size"], body["num_attention_heads"],
            body["num_key_value_heads"], body["head_dim"],
            body["intermediate_size"], body["num_experts_per_tok"],
            body["num_shared_experts"], body["sliding_window"],
            body["published"]["num_experts"]) == (4096, 128, 8, 128, 4096, 8,
                                                  4, 4096, 128)
    assert (body["num_hidden_layers"], body["num_experts"],
            body["vocab_size"]) == ({"serve": 4}, 16, 32768)
    assert body["share"]["chips_sharing_a_layer"] == 8 and \
        body["share"]["this_chip"] == 0 and set(body["share"]) == {
            "chips_sharing_a_layer", "this_chip", "how"}
    assert {"expert_width", "shared_added", "router"} <= set(body["assumed"])
    assert body["stands_for"]
    arch = loader.load_part(pb.ROOT, "models", "cohere2_moe")
    sizes = arch.reference_sizes(body, "serve")
    assert (sizes["layer_kinds"], sizes["experts_held"],
            sizes["first_expert"], sizes["vocab_size"]) == ("SSSF", 16, 0,
                                                            32768)
    model, _ = arch.build(body, "serve")
    cfg = model.config
    assert (cfg.num_experts, cfg.held, cfg.first_expert,
            cfg.layer_windows) == (128, 16, 0, (4096, 4096, 4096, 0))
    shapes = arch.param_shapes(model)
    n = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    assert n == 4 * (344_461_312 + 16 * 50_331_648) + 32768 * 4096 + 4096
    assert shapes["layers_0"]["moe"]["w1"].shape == (16, 4096, 4096)
    assert shapes["layers_3"]["moe"]["gate"]["kernel"].shape == (4096, 128)


def test_the_cell_is_the_issues_traffic_on_one_chip():
    manifest = pb.read_manifest(pb.ROOT)
    cell = loader.find(manifest["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "command_a_plus_1chip", "rag_closed32", 1)
    assert "saturation" in cell["why"]
    t = loader.load_json(loader.part_path(pb.ROOT, "traffic",
                                          cell["traffic"], "json"))
    assert (t["job"], t["loop"], t["sessions"]) == ("serve", "closed", 32)
    assert t["prompt_len"] == {"dist": "lognormal", "median": 3072,
                               "sigma": 0.9, "min": 128, "max": 16384}
    assert t["output_len"] == {"dist": "geometric", "mean": 256, "min": 16,
                               "max": 1024}
    assert (t["pool_size"], t["pool_seed"], t["check_new_tokens"],
            t["trace_seconds"]) == (256, 20260928, 32, 5.0)
    from perfbench import traffic_gen
    prompts = np.array([p for p, _ in traffic_gen.length_pool(t)])
    assert (round(prompts.mean()), int(np.median(prompts)), prompts.min(),
            prompts.max()) == (4190, 2822, 265, 16384)
    of = lambda name: {
        m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]
        if name in m.get("workloads", [name])}
    mine, chat = of(CELL), of("mistral7b_serve_chat")
    # its own five, and the latent cells' row share, which reads 0 here
    # (PR 51 listed it for a pin a ``benchmark`` PR alone may edit)
    assert mine - chat == {
        "serve_moe_experts_ms_per_step", "serve_moe_shared_ms_per_step",
        "serve_moe_experts_roofline_share", "serve_expert_copies_per_row",
        "serve_window_page_share", "serve_expanded_row_share"}
    # one accepted metric's list is held to two cells by its own test
    assert chat - mine == {"serve_short_run_page_share"}


# ------------------------------------------------------------------ readers
RECORD = {"trace": {"busy_s": 1.0},
          "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
STEP = "jit(ds_ragged_step_cohere2_moe)/ds.mlp/"
OPS = [
    op("%ds_grouped_matmul.7 = bf16[6656,4096]{1,0} custom-call()", 0, 100,
       RAGGED, STEP + "ds.moe_experts/cond/branch_0_fun/pallas_call"),
    op("%gather.1 = bf16[2560,4096]{1,0} fusion()", 100, 150, RAGGED,
       STEP + "ds.moe_experts/cond/branch_0_fun/gather"),
    # the conditional that holds the two carries no scope path on a v5e
    op("%conditional.1 = bf16[2048,4096]{1,0} conditional()", 0, 150,
       RAGGED),
    op("%fusion.9 = bf16[2048,16384]{1,0} fusion()", 150, 450, RAGGED,
       STEP + "ds.moe_shared/dot_general"),
    op("%fusion.2 = f32[2048,128]{1,0} fusion()", 450, 460, RAGGED,
       STEP + "ds.moe_router/dot_general")]
STEPS = [
    # a prompt's middle chunk fetches nothing: what it counted on the device
    # rides back with the next step's tokens, and is booked there
    span("ds:serve.step", 0, 200, step=1, kind="ragged", live_tokens=1000,
         grid_pages=300, grid_pages_window=200, grid_pages_full=100),
    span("ds:serve.step", 200, 400, step=2, kind="ragged", live_tokens=500,
         expert_copies=6200, expert_active=64,
         grid_pages=600, grid_pages_window=400, grid_pages_full=200),
    span("ds:serve.step", 400, 800, step=3, kind="burst", live_tokens=512,
         expert_copies=1990, expert_active=60,
         grid_pages=5000, grid_pages_window=3000, grid_pages_full=2000),
    span("ds:serve.step", 950, 1000, step=4, kind="ragged", live_tokens=9,
         expert_copies=9, expert_active=9,
         grid_pages=9, grid_pages_window=6, grid_pages_full=3)]


def _trace(steps, ops=OPS):
    return {
        "/device:TPU:0": {
            "XLA Modules": [(f"jit_ds_ragged_step_cohere2_moe({RAGGED})", 0,
                             1000 * US, {}, {})],
            "XLA Ops": ops},
        "/host:CPU": {"python3": [span("pb:traced", 0, 900)] + steps}}


@pytest.fixture
def traced(tmp_path, monkeypatch):
    """A checkout whose newest trace is the new cell's."""
    monkeypatch.setattr(program_trace, "ROOT", str(tmp_path))
    monkeypatch.setattr(serve_trace, "_CACHE", {})
    for name in ("BENCHMARK.json", "perfbench/configs"):
        os.makedirs(os.path.dirname(tmp_path / name), exist_ok=True)
        os.symlink(os.path.join(pb.ROOT, name), tmp_path / name)
    return lambda trace: _write(tmp_path, trace, cell=CELL)


def _read(metric):
    return loader.load_reader(pb.ROOT, metric).read(RECORD)


def test_the_readers_read_the_new_scopes_and_counts(traced):
    traced(_trace(STEPS))
    ms = lambda us: us / 1e3
    assert _read("serve_moe_experts_ms_per_step") == pytest.approx(
        ms(150) / 3)
    assert _read("serve_moe_shared_ms_per_step") == pytest.approx(ms(300) / 3)
    assert _read("serve_expert_copies_per_row") == pytest.approx(
        (6200 + 1990) / ((1500 + 512) * 4))
    assert _read("serve_window_page_share") == pytest.approx(
        100 * 3600 / (3 * 2300))
    roof = loader.load_reader(pb.ROOT, "serve_moe_experts_roofline_share")
    d = 4096
    floor = sum(max(roof.must_move_bytes(a, c, d, d) / 819e9,
                    roof.must_compute_flops(c, d, d) / 197e12)
                for a, c in ((64, 6200), (60, 1990)))
    assert roof.must_move_bytes(16, 2048, d, d) == \
        (16 * 3 * d * d + 2048 * 4 * d) * 2
    assert roof.must_compute_flops(2048, d, d) == 2048 * 6 * d * d
    assert roof.read(RECORD) == pytest.approx(100 * floor / 150e-6)


@pytest.mark.parametrize("metric", [
    "serve_moe_experts_ms_per_step", "serve_moe_shared_ms_per_step",
    "serve_moe_experts_roofline_share", "serve_expert_copies_per_row",
    "serve_window_page_share"])
def test_a_reader_gives_nothing_on_a_program_without_its_names(
        metric, traced, monkeypatch):
    """The parent commit's program: no scope, no count.  Nothing is read and
    nothing is raised; an untraced run and no trace file alike."""
    reader = loader.load_reader(pb.ROOT, metric)
    assert reader.read(RECORD) is None                    # no trace file
    bare = [e[:3] + ({k: v for k, v in e[3].items() if k in (
        "step", "kind", "live_tokens", "grid_pages")}, ) + e[4:]
        for e in STEPS]
    parents = [op(o[0], o[1] / US, o[2] / US, RAGGED,
                  "jit(ds_ragged_step_mixtral)/ds.mlp/dot_general")
               for o in OPS]
    traced(_trace(bare, parents))
    assert reader.read({"trace": None}) is None           # an untraced run
    assert reader.read(RECORD) is None
    names = program_trace.program_names()
    for scope in ("SCOPE_MOE_EXPERTS", "SCOPE_MOE_SHARED"):
        monkeypatch.delattr(names, scope)
    assert reader.read(RECORD) is None
