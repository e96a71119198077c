"""Architecture ``longcat_flash`` (LongCat-Flash-Chat) in the benchmark, at
tiny size on the CPU (``tiny_longcat_flash``: four layers of two attentions
each, a router over 120 real experts of which 8 held and 8 identity ones, 4 a
token, 8 heads on a latent row of 32 + 8; ``tiny_agent``: prompts of 48 to 144
tokens in chunks of 64 rows, 32 new ones, pages of 8).

The system (the absorbed form over EIGHT paged latent caches) against the
plain reference (the expanded form, no cache) through the harness's own door
and its own comparison; the reference against the program's dense forward and
against itself (the shares add up to the uncut layer with the identity part
counted once; a second answer recomputed from the first's latent rows is the
whole forward's; which near-ties count); planted faults, each REJECTED on
every seed tried; the configuration, the cell and the five readers."""

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
faults = loader.load_file(os.path.join(pb.ROOT, "tools",
                                       "serve_fault_check.py"))

CONFIG, TRAFFIC, CELL = "tiny_longcat_flash", "tiny_agent", \
    "longcat_flash_serve_agent"
SEEDS = (0, 1, 2, 3_500_000_019)
_runs = {}


def _run(seed):
    """A seed's streamed check requests, made once a module."""
    if seed not in _runs:
        _runs[seed] = pb.streamed(CONFIG, seed, None, TRAFFIC)
    return _runs[seed]


def _tols():
    return serve.tolerances(pb.serve_ctx(CONFIG, TRAFFIC))


def _judged(run, sizes=None):
    _, ref, params, own_sizes, prompts, produced = run
    rows = faults.judged(serve, ref, params, sizes or own_sizes, prompts,
                         produced, _tols())
    return all(r["pass"] for r in rows), rows


# ------------------------------------------------- the system = the reference
@pytest.mark.parametrize("seed", [1, 3_500_000_019])
def test_the_tiny_cell_runs_through_the_harness(tmp_path, seed, capsys):
    root = pb.tiny_root(tmp_path, [("tiny_agent_cell", CONFIG, TRAFFIC,
                                    "serve")])
    rc, result, last = pb.run(root, "tiny_agent_cell", seed=seed,
                              seconds=0.3)
    out = capsys.readouterr().out
    assert rc == 0 and result["correct"], out
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert json.loads(last) == result
    assert "CHECK serve.routed_two_answer_share" in out
    assert "CHECK serve.logit_gap_prompt144" in out
    assert '"depth": 4' in out


@pytest.mark.parametrize("seed", SEEDS)
def test_the_routed_check_passes_the_engine(seed, capsys):
    """Chunked prefill (a prompt of 144 tokens in a budget of 64 beside the
    other two), single decode steps and the burst through eight latent
    caches: the engine's tokens against the reference's full forward."""
    ok, rows = _judged(_run(seed))
    assert ok, capsys.readouterr().out
    assert sum(r["check"].startswith("serve.logit_gap_prompt")
               for r in rows) == 3


def _float32_parts(seed, **changes):
    """``(architecture, reference, model, float32 weights with a seeded
    NON-constant choice bias, sizes)`` of the preset with keys changed."""
    config, arch, ref = pb.parts(CONFIG)
    config = copy.deepcopy(config)
    config.update(changes)
    config["program"]["serve"]["model"] = dict(dtype="float32")
    built, _ = arch.build(config, "serve")
    params = weights.seeded_weights(arch.param_shapes(built),
                                    harness.fold_seed(seed), jnp.float32)
    for l in range(built.config.num_layers):
        params[f"layers_{l}"]["moe"]["e_score_correction_bias"] = \
            0.01 * jax.random.normal(jax.random.PRNGKey(seed + l),
                                     (built.config.router_width, ))
    return arch, ref, built, params, arch.reference_sizes(config, "serve")


@pytest.mark.parametrize("seed", [0, 1])
def test_the_reference_is_the_programs_dense_forward(seed):
    _, ref, model, params, sizes = _float32_parts(seed)
    ids = np.random.default_rng(seed).integers(0, sizes["vocab_size"], 70)
    want = model.apply({"params": params}, jnp.asarray(ids)[None])[0]
    got = ref.logits_at(params, ids, np.arange(70), sizes)
    np.testing.assert_allclose(got, want, atol=2e-3 * float(jnp.std(want)))
    assert np.array_equal(np.argmax(got, -1), np.argmax(want, -1))


def test_the_reference_computes_a_long_sequence_in_blocks(monkeypatch):
    """Row blocks, query blocks and head blocks smaller than the sequence:
    the numbers are those of one block."""
    _, ref, _, params, sizes = _float32_parts(0)
    ids = np.random.default_rng(3).integers(0, sizes["vocab_size"], 75)
    whole = ref.logits_at(params, ids, np.arange(75), sizes)
    for name, value in (("ROW_BLOCK", 32), ("QUERY_ROWS", 8),
                        ("HEAD_BLOCK", 2), ("MLP_COLS", 32)):
        monkeypatch.setattr(ref, name, value)
    ref._layer_jit.clear_cache()
    try:
        blocked = ref.logits_at(params, ids, np.arange(75), sizes)
    finally:
        monkeypatch.undo()
        ref._layer_jit.clear_cache()
    np.testing.assert_allclose(blocked, whole,
                               atol=2e-4 * float(jnp.std(whole)))


def _uncut_branch(seed, real=64, identity=32, k=6, tokens=60):
    """One expert branch of ``real`` experts all held beside ``identity``
    identity ones: ``(reference, its weights, sizes, rows)``."""
    _, ref, _, params, sizes = _float32_parts(
        seed, n_routed_experts=real, zero_expert_num=identity, moe_topk=k,
        share=None, published={"n_routed_experts": real})
    assert (sizes["experts_held"], sizes["first_expert"]) == (real, 0)
    h = jax.random.normal(harness.fold_seed(seed), (tokens,
                                                    sizes["hidden_size"]))
    return ref, params["layers_2"]["moe"], sizes, h


@pytest.mark.parametrize("seed", [0, 3_500_000_019])
def test_the_shares_add_up_to_the_uncut_layer(seed):
    """64 real experts as 32 shares of 2: the shares' held parts, with the
    identity experts' part (which every chip computes alike) counted ONCE,
    are the uncut reference's branch."""
    ref, moe, sizes, h = _uncut_branch(seed)
    with jax.default_matmul_precision(ref.HIGHEST):
        whole = ref.moe_rows(h, moe, sizes)[0]
        identity = ref.moe_rows(h, moe, dict(sizes,
                                             held_experts_part=False))[0]
        parts = []
        for chip in range(32):
            stacks = {n: moe[n][2 * chip:2 * chip + 2]
                      for n in ("w1", "w2", "w3")}
            share = dict(sizes, experts_held=2, first_expert=2 * chip)
            parts.append(ref.moe_rows(h, {**moe, **stacks}, share)[0]
                         - identity)
    scale = float(jnp.max(jnp.abs(whole)))
    np.testing.assert_allclose(sum(parts) + identity, whole,
                               atol=1e-5 * scale)
    assert float(jnp.max(jnp.abs(identity))) > 0.1 * scale
    assert sum(float(jnp.max(jnp.abs(p))) > 0.02 * scale for p in parts) > 16
    assert float(jnp.max(jnp.abs(parts[0] + identity - whole))) > 0.1 * scale


def test_a_near_tie_counts_unless_both_experts_are_held_elsewhere():
    """Scores and a bias made by hand, 6 real experts (2 held: ids 2, 3) and
    3 identity ones, 2 a token: the choice is by ``p + b``, the weights are
    ``scale x p``; the margin is infinite only where the k-th and (k+1)-th
    are BOTH real experts held elsewhere."""
    ref = pb.parts(CONFIG)[2]
    p = np.full((4, 9), 0.01, np.float32)
    b = np.zeros(9, np.float32)
    p[0, [0, 1, 5]] = 0.5, 0.2, 0.19      # tie of 1 and 5: both elsewhere
    p[1, [0, 2, 5]] = 0.5, 0.2, 0.19      # tie of 2 (held) and 5
    p[2, [0, 7, 5]] = 0.5, 0.2, 0.19      # tie of 7 (identity) and 5
    p[3, [0, 3, 4]] = 0.5, 0.1, 0.2       # the bias lifts 3 (held) over 4
    b[3] = 0.11
    flip = jnp.asarray([False, False, True, False])
    w, margin = ref.route(jnp.asarray(p), jnp.asarray(b), 2, 6, (2, 2),
                          scale=6.0)
    assert np.isinf(margin[0]) and np.isfinite(np.asarray(margin[1:])).all()
    np.testing.assert_allclose(margin[1], 0.01 / 0.39, rtol=1e-4)
    np.testing.assert_allclose(margin[3], 0.01 / 0.3, rtol=1e-4)
    assert sorted(np.flatnonzero(w[3])) == [0, 3]         # chosen by p + b
    np.testing.assert_allclose(w[3, [0, 3]], [3.0, 0.6], rtol=1e-5)  # 6 x p
    flipped, _ = ref.route(jnp.asarray(p), jnp.asarray(b), 2, 6, (2, 2),
                           flip=flip, scale=6.0)
    assert sorted(np.flatnonzero(flipped[2])) == [0, 5]
    np.testing.assert_array_equal(flipped[:2], w[:2])
    renorm, _ = ref.route(jnp.asarray(p), jnp.asarray(b), 2, 6, (2, 2),
                          renormalise=True)
    np.testing.assert_allclose(renorm.sum(-1), 1.0, rtol=1e-5)


def test_a_second_answer_from_the_firsts_latents_is_the_whole_forwards():
    """``flip`` at or after the first position asked for recomputes the
    suffix alone against the first answer's latent rows (both attentions' of
    every layer); the numbers are those of a pass over every token."""
    _, ref, params, sizes, prompts, produced = _run(0)
    ids = np.asarray(prompts[1] + produced[1][:-1], np.int32)
    at = np.arange(len(prompts[1]) - 1, len(ids))
    first, margins = ref.logits_and_routing_at(params, ids, at, sizes)
    assert ref._FIRST["start"] == at[0] and len(ref._FIRST["latent"]) == 4
    assert [lat.shape for lat in ref._FIRST["latent"][0]] == \
        [(len(ids), 40)] * 2
    assert margins.shape == (len(at), 4)
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
        assert float(jnp.max(jnp.abs(fast - first))) > 1e-4


def test_the_relative_error_of_a_score_is_what_the_margin_is_held_to():
    """``router_logit_error`` is the largest ``|log p - log p~|``: zero for a
    pass rounded to float32, hundredths for one rounded to bfloat16."""
    _, ref, params, sizes, prompts, produced = _run(1)
    ids = np.asarray(prompts[2] + produced[2][:-1], np.int32)
    assert ref.router_logit_error(params, ids, sizes, "float32") == 0.0
    assert 0.005 < ref.router_logit_error(params, ids, sizes) < 0.2


# ------------------------------------------------------------ planted faults
FAULTS = faults.FAULTS["longcat_flash"]
#: the held experts' part is thousandths of the residual at 8 of 128 held:
#: the comparison does NOT see it (PERF.md section 7 has the chip's reading)
SEEN = [name for name in FAULTS if not name.startswith("f_")]


def test_the_faults_are_the_issues_six():
    assert [name[0] for name in FAULTS] == list("abcdef")
    assert all(len(change) == 1 for change in FAULTS.values())


@pytest.mark.parametrize("name", SEEN)
def test_the_routed_check_rejects_a_planted_fault(name):
    """Each fault is rejected on every seed.  A fault is planted in the
    reference (the comparison is symmetric): in a reading its sizes state."""
    rejected = []
    for seed in SEEDS:
        run = _run(seed)
        ok, rows = _judged(run, dict(run[3], **FAULTS[name]))
        rejected.append(not ok)
    assert all(rejected), (name, rejected)


def test_a_sound_run_reads_far_under_the_limit():
    worst = max(r["observed"] for seed in SEEDS for r in _judged(_run(seed))[1]
                if r["check"].startswith("serve.logit_gap_prompt"))
    assert worst < _tols()["serve.logit_gap"] / 3


@pytest.mark.parametrize("bits,rejected", [(4, True)])
def test_what_rounded_weights_read(bits, rejected):
    """The control the contract asks for: the ENGINE serves weights rounded
    to ``bits`` bits and is rejected."""
    for seed in SEEDS[:2]:
        run = pb.streamed(CONFIG, seed, pb.rounded_to(bits), TRAFFIC)
        assert _judged(run)[0] != rejected, (bits, seed)


def test_the_control_is_the_reference_with_its_matrices_in_8_bits():
    """``tools/serve_fault_check.py``'s control at the timed size: the
    REFERENCE rounds every matrix to an 8-bit float's three mantissa bits
    (``float8_e4m3fn``'s, in its normal range) and is rejected on every
    seed."""
    assert faults.CONTROLS["longcat_flash"] == {
        "control_weights_in_8_bits": {"weight_mantissa_bits": 3}}
    ref = loader.load_part(pb.ROOT, "reference", "longcat_flash")
    x = np.random.default_rng(0).normal(size=4096).astype(np.float32)
    want = np.asarray(jnp.asarray(x).astype(jnp.float8_e4m3fn), np.float32)
    normal = np.abs(x) > 2.0 ** -6
    assert np.array_equal(np.asarray(ref.f32(x, 3))[normal], want[normal])
    assert np.array_equal(np.asarray(ref.f32(x)), x)
    for seed in SEEDS:
        run = _run(seed)
        assert not _judged(run, dict(run[3], weight_mantissa_bits=3))[0], seed


# ------------------------------------------- the configuration and the cell
def test_the_configuration_is_the_drawn_row_as_one_chips_share():
    manifest = pb.read_manifest(pb.ROOT)
    entry = loader.find(manifest["configs"], "longcat_flash_1chip", "config")
    body = loader.load_json(os.path.join(pb.ROOT, entry["file"]))
    assert entry["reduced"] == ["num_layers", "n_routed_experts",
                                "vocab_size"]
    assert lint_config(body, entry["reduced"]) == []
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = [r for r in map(json.loads, open(catalog))
               if r["name"] == "LongCat-Flash-Chat"][0]
        assert entry["source"] == body["source"] == row["source_url"]
        assert {k: v for k, v in body["published"].items()
                if not k.startswith("_")} == row["config"]
    assert [body[k] for k in (
        "hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size",
        "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "zero_expert_num", "moe_topk", "routed_scaling_factor",
        "mla_scale_q_lora", "mla_scale_kv_lora", "zero_expert_type")] == [
            6144, 12288, 2048, 64, 1536, 512, 128, 64, 128, 256, 12, 6, True,
            True, "identity"]
    # the depth under the published key AND under the harness's
    assert (body["num_layers"], body["num_hidden_layers"],
            body["cache_entries_per_layer"], body["n_routed_experts"],
            body["vocab_size"], body["published"]["n_routed_experts"],
            body["published"]["num_layers"]) == (
                4, {"serve": 4}, 2, 16, 16384, 512, 28)
    assert "num_hidden_layers" not in body["published"]
    assert body["share"]["chips_sharing_a_layer"] == 32 and \
        body["share"]["this_chip"] == 0 and set(body["share"]) == {
            "chips_sharing_a_layer", "this_chip", "how"}
    assert {"layer", "mla_scales", "rotary", "softmax_scale", "router",
            "identity_experts", "shared_expert", "cache",
            "weights"} <= set(body["assumed"])
    assert "ONES" in body["assumed"]["weights"]
    assert body["stands_for"]
    arch = loader.load_part(pb.ROOT, "models", "longcat_flash")
    sizes = arch.reference_sizes(body, "serve")
    assert (sizes["num_hidden_layers"], sizes["n_routed_experts"],
            sizes["experts_held"], sizes["first_expert"],
            sizes["vocab_size"]) == (4, 512, 16, 0, 16384)
    with pytest.raises(ValueError, match="num_layers"):
        arch.depth_of(dict(body, num_layers=5), "serve")
    model, _ = arch.build(body, "serve")
    cfg = model.config
    assert (cfg.num_layers, cfg.router_width, cfg.held, cfg.first_expert,
            cfg.kv_latent_dim, cfg.kv_cache_entries, cfg.q_scale) == (
                4, 768, 16, 0, 576, 8, 2.0)
    shapes = arch.param_shapes(model)
    n = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    mla = 6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 2 * 512 * 64 * 128 \
        + 8192 * 6144 + 1536 + 512
    ffn, expert = 3 * 6144 * 12288, 3 * 6144 * 2048
    assert n == 4 * (2 * mla + 2 * ffn + 4 * 6144 + 6144 * 768 + 768
                     + 16 * expert) + 2 * 16384 * 6144 + 6144
    assert 5.16e9 < n < 5.19e9
    moe = shapes["layers_3"]["moe"]
    assert moe["w1"].shape == (16, 6144, 2048)
    assert moe["gate"]["kernel"].shape == (6144, 768)
    assert moe["e_score_correction_bias"].shape == (768, )
    attn = shapes["layers_0"]["self_attn_1"]
    assert attn["kv_a_proj"]["kernel"].shape == (6144, 576)
    # kv_b_proj as its two halves [r, H, d] (openPangu's leaves); q_b_proj
    # [out, in]: what the generator then draws it at, and why, is
    # `assumed.up_projection_layout`'s
    assert [attn[f"{n}_b_proj"]["kernel"].shape for n in "qkv"] == [
        (12288, 1536), (512, 64, 128), (512, 64, 128)]
    assert "one-hot" in body["assumed"]["up_projection_layout"].lower()
    # the generator's rules (weights.py) meet the leaves they are meant for
    names = [jax.tree_util.keystr(p) for p, s in
             jax.tree_util.tree_flatten_with_path(shapes)[0]
             if len(s.shape) == 3 and "moe" in jax.tree_util.keystr(p)]
    assert len(names) == 4 * 3 and all(n[-4:-2] in ("w1", "w2", "w3")
                                       for n in names)
    # the cache the engine builds for it: 8 buffers of 640-value rows
    eng = body["program"]["serve"]["engine"]
    assert set(eng) == {"max_concurrent", "block_size", "token_budget",
                        "decode_burst", "num_blocks"} == set(
                            body["program"]["serve"]["engine_why"])
    assert (eng["max_concurrent"], eng["block_size"], eng["token_budget"],
            eng["decode_burst"]) == (32, 128, 2048, 16)
    weights_gb = n * 2 / 1e9
    cache_gb = eng["num_blocks"] * eng["block_size"] * 640 * 2 * 8 / 1e9
    assert weights_gb + cache_gb >= 12.5


def test_the_cell_is_the_issues_traffic_on_one_chip():
    manifest = pb.read_manifest(pb.ROOT)
    cell = loader.find(manifest["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "longcat_flash_1chip", "agent_closed32", 1)
    t = loader.load_json(loader.part_path(pb.ROOT, "traffic",
                                          cell["traffic"], "json"))
    assert (t["job"], t["loop"], t["sessions"]) == ("serve", "closed", 32)
    assert t["prompt_len"] == {"dist": "lognormal", "median": 8192,
                               "sigma": 0.6, "min": 1024, "max": 16384}
    assert t["output_len"] == {"dist": "geometric", "mean": 256, "min": 32,
                               "max": 1024}
    assert (t["pool_size"], t["check_new_tokens"], t["trace_seconds"]) == (
        128, 32, 5.0)
    folder = os.path.join(pb.ROOT, "perfbench", "traffic")
    others = {loader.load_json(os.path.join(folder, f)).get("pool_seed")
              for f in os.listdir(folder)
              if not f.startswith("tiny_") and f != "agent_closed32.json"}
    assert t["pool_seed"] not in others
    from perfbench import traffic_gen
    pool = traffic_gen.length_pool(t)
    prompts = np.array([p for p, _ in pool])
    replies = np.array([o for _, o in pool])
    assert (round(prompts.mean()), int(np.median(prompts)), prompts.min(),
            prompts.max()) == (8226, 7163, 1628, 16384)
    assert (round(replies.mean()), replies.min(), replies.max()) == (
        224, 32, 1024)
    of = lambda name: {
        m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]
        if name in m.get("workloads", [name])}
    mine, pangu = of(CELL), of("pangu_ultra_moe_serve_reason")
    # the copies a live row and layer lands on held experts have something
    # to read here (every layer is routed; 12 x 16 / 768 = 0.25 when even)
    assert mine - pangu == {
        "serve_expert_copies_per_row",
        "serve_zero_expert_copy_share", "serve_moe_zero_ms_per_step",
        "serve_dense_ffn_ms_per_step",
        "serve_latent_kernel_roofline_share_by_call",
        "serve_held_experts_roofline_share"}
    # a shared expert it has none of; a reader that halves its bytes; and the
    # five that test_perfbench_step_trace.py pins to four cells
    assert pangu - mine == {
        "serve_moe_shared_ms_per_step", "serve_latent_kernel_roofline_share",
        "serve_ragged_step_device_ms", "serve_burst_iteration_device_ms",
        "serve_ragged_paged_kernel_ms",
        "serve_burst_paged_kernel_ms_per_iteration",
        "serve_launch_slack_ms_p05"}
    for m in manifest["per_layer"]:
        if m["name"] in mine - pangu:
            assert m["workloads"][-1] == CELL
            assert (m["workloads"] == [CELL]) == (
                m["name"] != "serve_expert_copies_per_row")
            assert m["moves"] == "serve_tokens_per_s"


# ------------------------------------------------------------------ readers
RECORD = {"trace": {"busy_s": 1.0},
          "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
STEP = "jit(ds_ragged_step_longcat_flash)/"
OPS = [
    op("%ds_paged_latent.3 = bf16[128,1024,512]{2,1,0} custom-call()", 0, 400,
       RAGGED, STEP + "ds.attn/pallas_call"),
    op("%ds_paged_latent.9 = bf16[3,1024,512]{2,1,0} custom-call()", 400, 500,
       RAGGED, "jit(ds_decode_burst)/while/body/ds.attn/pallas_call"),
    op("%fusion.4 = bf16[2048,12288]{1,0} fusion()", 500, 620, RAGGED,
       STEP + "ds.dense_ffn/dot_general"),
    op("%fusion.5 = bf16[2048,6144]{1,0} fusion()", 620, 700, RAGGED,
       STEP + "ds.dense_ffn/dot_general"),
    op("%fusion.6 = bf16[2048,6144]{1,0} fusion()", 700, 710, RAGGED,
       STEP + "ds.mlp/ds.moe_zero/mul"),
    op("%fusion.7 = bf16[640,2048]{1,0} fusion()", 710, 760, RAGGED,
       STEP + "ds.mlp/ds.moe_experts/pallas_call"),
    op("%fusion.8 = f32[2048,768]{1,0} fusion()", 760, 770, RAGGED,
       STEP + "ds.mlp/ds.moe_router/dot_general")]
STEPS = [
    span("ds:serve.step", 0, 450, step=1, kind="ragged", live_tokens=2000,
         absorbed_rows=2000, expanded_rows=0, grid_pages=4000,
         latent_keys=8 * 8_000_000, block_size=128, expert_copies=2000,
         expert_active=60, zero_expert_copies=32000),
    span("ds:serve.step", 450, 800, step=2, kind="burst", live_tokens=512,
         absorbed_rows=512, expanded_rows=0, grid_pages=40000,
         latent_keys=8 * 3_000_000, block_size=128, expert_copies=500,
         expert_active=64, zero_expert_copies=8200)]


def _trace(steps, ops=OPS):
    return {
        "/device:TPU:0": {
            "XLA Modules": [(f"jit_ds_ragged_step_longcat_flash({RAGGED})",
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


METRICS = ("serve_zero_expert_copy_share", "serve_moe_zero_ms_per_step",
           "serve_dense_ffn_ms_per_step",
           "serve_latent_kernel_roofline_share_by_call",
           "serve_held_experts_roofline_share")


def test_the_readers_read_the_new_scopes_kernel_and_counts(traced):
    traced(_trace(STEPS))
    read = lambda metric: loader.load_reader(pb.ROOT, metric).read(RECORD)
    assert read("serve_zero_expert_copy_share") == pytest.approx(
        100 * 40200 / (2512 * 12 * 4))
    assert read("serve_moe_zero_ms_per_step") == pytest.approx(0.010 / 2)
    assert read("serve_dense_ffn_ms_per_step") == pytest.approx(0.200 / 2)
    # the two roofline readers: EXACTLY the accepted readers' functions, at
    # this model's widths and 8 calls (4 layers x 2 cache entries)
    latent = loader.load_reader(pb.ROOT,
                                "serve_latent_kernel_roofline_share")
    floor = sum(max(
        latent.must_move_bytes(8 * pages, 8 * rows, 128, 64, 512, 64) / 819e9,
        latent.must_compute_flops(keys, 64, 512, 64) / 197e12)
        for pages, rows, keys in ((4000, 2000, 8 * 8_000_000),
                                  (40000, 512, 8 * 3_000_000)))
    assert read("serve_latent_kernel_roofline_share_by_call") == \
        pytest.approx(100 * floor / 500e-6)
    # the accepted reader counts 4 calls: half the bytes
    assert latent.read(RECORD) < read(
        "serve_latent_kernel_roofline_share_by_call")
    experts = loader.load_reader(pb.ROOT, "serve_moe_experts_roofline_share")
    floor = sum(max(
        experts.must_move_bytes(active, copies, 6144, 2048) / 819e9,
        experts.must_compute_flops(copies, 6144, 2048) / 197e12)
        for active, copies in ((60, 2000), (64, 500)))
    assert read("serve_held_experts_roofline_share") == pytest.approx(
        100 * floor / 50e-6)
    with pytest.raises(KeyError):        # why the cell is not on its list
        experts.read(RECORD)


@pytest.mark.parametrize("metric", METRICS)
def test_a_reader_gives_nothing_on_a_program_without_its_names(
        metric, traced, monkeypatch):
    """The parent commit's program: no scope, no count.  Nothing is read and
    nothing is raised; an untraced run and no trace file alike."""
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
    for scope in ("SCOPE_MOE_ZERO", "SCOPE_DENSE_FFN"):
        monkeypatch.delattr(names, scope)
    assert reader.read(RECORD) is None
