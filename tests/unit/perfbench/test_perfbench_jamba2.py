"""Architecture ``jamba`` (AI21-Jamba2-3B) in the benchmark, at tiny size on
the CPU (``tiny_jamba2``: a period of 6 layers with attention at layer 2
between Mamba layers, 4 query heads on one KV head, 128 inner channels of 16
states; ``tiny_reason``: contexts of 56 to 80 tokens over pages of 8, the
three check prompts' 108 tokens through a budget of 64).

The system (recurrent state rows beside paged MQA layers) against the plain
reference through the harness's own door and its own comparison; the two
configuration files against the lint and the drawn row; the cell's traffic
and its metric set; the four readers on a trace and on a parent's."""

import json
import os

import numpy as np
import pytest

import pb_helpers as pb
from perfbench import harness, loader, program_trace, serve_trace, traffic_gen
from test_perfbench_manifest import lint_config
from test_perfbench_program_trace import RAGGED, US, _write, op, span

serve = loader.load_part(pb.ROOT, "jobs", "serve")

CONFIG, TRAFFIC, CELL = "tiny_jamba2", "tiny_reason", "jamba2_3b_serve_reason"
SEEDS = (0, 1, 2, 4_100_000_019)
_runs = {}


def _run(seed):
    """A seed's streamed check requests, made once a module."""
    if seed not in _runs:
        _runs[seed] = pb.streamed(CONFIG, seed, None, TRAFFIC)
    return _runs[seed]


def _tols():
    return serve.tolerances(pb.serve_ctx(CONFIG, TRAFFIC))


def _judged(run, params=None):
    _, ref, own_params, sizes, prompts, produced = run
    rows = serve.logit_gaps(ref.logits_at, params or own_params, sizes,
                            prompts, produced)
    checks = harness.Checks()
    serve.judge(checks, rows, _tols())
    return checks, rows


# ------------------------------------------------- the system = the reference
@pytest.mark.parametrize("seed", [1, 4_100_000_019])
def test_the_tiny_cell_runs_through_the_harness(tmp_path, seed, capsys):
    root = pb.tiny_root(tmp_path, [("tiny_jamba_cell", CONFIG, TRAFFIC,
                                    "serve")])
    rc, result, last = pb.run(root, "tiny_jamba_cell", seed=seed,
                              seconds=0.3)
    out = capsys.readouterr().out
    assert rc == 0 and result["correct"], out
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert json.loads(last) == result
    # compared as a dense model: no routed check
    assert "CHECK serve.logit_gap_prompt48" in out
    assert "routed" not in out


@pytest.mark.parametrize("seed", SEEDS)
def test_the_check_passes_the_engine(seed):
    """Prompts of 24, 36 and 48 tokens through a budget of 64 (the longest in
    two chunks, the second beside the others' decode rows), single decode
    steps and bursts of 4 through state rows and pages in bfloat16: the
    engine's tokens against the reference's full forward."""
    checks, rows = _judged(_run(seed))
    assert checks.all_passed, (seed, rows)
    # a reply of 32 holds many distinct tokens: the tied table does not
    # make the model repeat its input
    assert min(len(set(toks)) for toks in _run(seed)[5]) >= 20


def test_the_reference_has_no_routing_and_no_loss():
    ref = pb.parts(CONFIG)[2]
    assert not hasattr(ref, "logits_and_routing_at")
    assert not hasattr(ref, "train_losses")
    source = open(ref.__file__).read()
    assert "deepspeed_tpu" not in source.split('"""', 2)[2]
    assert "HIGHEST" in source and "lax.scan" in source


def _scaled(leaf, factor):
    import jax

    def change(path, x):
        return x * factor if leaf in jax.tree_util.keystr(path) else x
    return lambda params: jax.tree_util.tree_map_with_path(change, params)


FAULTS = {
    # the comparison is symmetric: a fault in the weights the REFERENCE is
    # given reads as the same fault in the engine
    "inner_norms_scale_dropped": _scaled("b_layernorm", 0.25),
    "recurrence_output_halved": _scaled("mamba']['out_proj", 0.5),
    "skip_term_dropped": _scaled("mamba']['D", 0.0),
    "convolution_tap_dropped": _scaled("conv1d']['weight", 0.5),
}


@pytest.mark.parametrize("name", list(FAULTS))
def test_the_check_rejects_a_planted_fault(name):
    rejected, gaps = [], []
    for seed in SEEDS:
        run = _run(seed)
        checks, rows = _judged(run, FAULTS[name](run[2]))
        rejected.append(not checks.all_passed)
        gaps.append(max(r[1] for r in rows))
    assert all(rejected), (name, rejected, gaps)


# ------------------------------------------------------- the configurations
def test_both_configuration_files_pass_the_lint():
    for name, reduced in (("jamba2_3b_1chip", ["num_hidden_layers"]),
                          ("tiny_jamba2", [])):
        body = pb.parts(name)[0]
        assert lint_config(body, reduced) == [], name
        assert all(isinstance(v.get("value"), float) and v.get("where")
                   for k, v in body["measured_worst"].items()
                   if not k.startswith("_")), name


def test_the_configuration_is_the_drawn_row_whole():
    """Every number of the catalog row's config under the same key; the
    serving depth is the published 28 and ``reduced`` names the key for the
    form alone; no share; the engine layout and what it holds."""
    body, arch, _ = pb.parts("jamba2_3b_1chip")
    manifest = pb.read_manifest(pb.ROOT)
    entry = loader.find(manifest["configs"], "jamba2_3b_1chip", "config")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == body["source"] == \
        "https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json"
    published = {k: v for k, v in body["published"].items()
                 if not k.startswith("_")}
    assert published == {
        "attn_layer_offset": 7, "attn_layer_period": 14,
        "expert_layer_offset": 1, "expert_layer_period": 2,
        "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 8192,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
        "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "model_type": "jamba",
        "num_attention_heads": 20, "num_experts": 1,
        "num_experts_per_tok": 1, "num_hidden_layers": 28,
        "num_key_value_heads": 1, "num_logits_to_keep": 1,
        "rms_norm_eps": 1e-06, "sliding_window": None,
        "tie_word_embeddings": True, "use_mamba_kernels": True,
        "vocab_size": 65536}
    assert body["num_hidden_layers"] == {"serve": 28}
    assert set(body["reduced"]) == {"num_hidden_layers"}
    assert "share" not in body and "28" in body["reduced"]["num_hidden_layers"]
    for key in ("head_dim", "inner_norms", "no_rotary", "state_types",
                "weights"):
        assert body["assumed"][key], key
    model, _ = arch.build(body, "serve")
    cfg = model.config
    assert cfg.layer_kinds.count("pages") == 2 and cfg.is_attention(7) \
        and cfg.is_attention(21) and cfg.head_dim == 128
    shapes = arch.param_shapes(model)
    import jax
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert round(n / 1e9, 2) == 3.03
    eng = body["program"]["serve"]["engine"]
    assert set(eng) == set(body["program"]["serve"]["engine_why"]) == {
        "max_concurrent", "block_size", "token_budget", "decode_burst",
        "num_blocks"}
    assert (eng["max_concurrent"], eng["block_size"], eng["token_budget"],
            eng["decode_burst"]) == (256, 128, 2048, 16)
    # what the chip holds: weights, 257 slots of state, the pages
    state = 257 * 26 * (16 * 5120 + 3 * 5120) * 2
    pages = eng["num_blocks"] * 128 * 2 * 2 * 128 * 2
    assert 0.25 * 16e9 < 2 * n + state + pages < 0.75 * 16e9
    assert round(state / 257 / 1e6, 2) == 5.06


def test_the_cell_is_the_issues_traffic_on_one_chip():
    manifest = pb.read_manifest(pb.ROOT)
    cell = loader.find(manifest["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "jamba2_3b_1chip", "reason_closed256", 1)
    assert len(cell["why"]) <= 200
    t = loader.load_json(loader.part_path(pb.ROOT, "traffic",
                                          cell["traffic"], "json"))
    assert (t["job"], t["loop"], t["sessions"]) == ("serve", "closed", 256)
    assert t["prompt_len"] == {"dist": "lognormal", "median": 1024,
                               "sigma": 1.0, "min": 128, "max": 16384}
    assert t["output_len"] == {"dist": "geometric", "mean": 2048, "min": 128,
                               "max": 8192}
    assert (t["pool_size"], t["check_new_tokens"], t["trace_seconds"]) == (
        1024, 32, 5.0)
    folder = os.path.join(pb.ROOT, "perfbench", "traffic")
    others = {loader.load_json(os.path.join(folder, f)).get("pool_seed")
              for f in os.listdir(folder)
              if not f.startswith("tiny_") and f != "reason_closed256.json"}
    assert t["pool_seed"] not in others
    pool = traffic_gen.length_pool(t)
    prompts = np.array([p for p, _ in pool])
    replies = np.array([o for _, o in pool])
    assert (round(prompts.mean()), float(np.median(prompts)), prompts.min(),
            prompts.max()) == (1716, 1024.5, 128, 16384)
    assert (round(replies.mean()), float(np.median(replies)), replies.min(),
            replies.max()) == (2064, 1497.0, 128, 8192)
    assert round(float((prompts > 2048).mean()), 3) == 0.244
    # the claims of 256 such sessions, each at its final context, fit the
    # pool with room: nothing is ever preempted
    eng = pb.parts("jamba2_3b_1chip")[0]["program"]["serve"]["engine"]
    fullest = max(sum(-(-(p + o) // 128) for p, o in pool[i:i + 256])
                  for i in range(0, 1024, 256))
    assert fullest == 8132 and eng["num_blocks"] > 1.2 * fullest


MINE = {"serve_ssm_scan_ms_per_step", "serve_ssm_scan_roofline_share",
        "serve_ssm_proj_ms_per_step", "serve_state_bytes_per_seq"}


def test_the_cells_metric_set():
    """The cell's own set, stated without holding any OTHER cell's position
    or set: the two end-to-end metrics, the ten serving metrics every
    serving cell reports, and the four this architecture brings."""
    manifest = pb.read_manifest(pb.ROOT)
    mine = {m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]
            if CELL in m.get("workloads", [CELL])}
    assert mine == MINE | {
        "serve_tokens_per_s", "setup_s", "serve_queue_ms_p95",
        "serve_preemptions_per_100", "serve_ttft_ms_p95", "serve_step_ms_p50",
        "serve_tpot_ms_p95", "device_idle_share.serve",
        "serve_live_token_share", "serve_host_ms_per_step",
        "serve_paged_kernel_ms_per_step", "serve_kv_cache_ms_per_step"}
    for m in manifest["per_layer"]:
        if m["name"] in MINE:
            assert m["workloads"] == [CELL] and \
                m["moves"] == "serve_tokens_per_s"


# ------------------------------------------------------------------ readers
RECORD = {"trace": {"busy_s": 1.0},
          "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
STEP = "jit(ds_ragged_step_jamba)/ds.ssm/"
BURST = "jit(ds_decode_burst)/while/body/ds.ssm/"
OPS = [
    op("%ds_selective_scan.3 = (f32[2048,5120], bf16[257,16,5120]) "
       "custom-call()", 0, 300, RAGGED, STEP + "ds.ssm_scan/pallas_call"),
    op("%fusion.7 = f32[2048,16,128]{2,1,0} fusion()", 300, 340, RAGGED,
       STEP + "ds.ssm_scan/broadcast_in_dim"),
    op("%fusion.9 = bf16[257,16,5120]{2,1,0} fusion()", 340, 440, RAGGED,
       BURST + "ds.ssm_scan/mul"),
    op("%fusion.4 = f32[2048,10240]{1,0} fusion()", 440, 640, RAGGED,
       STEP + "ds.ssm_proj/dot_general"),
    op("%fusion.5 = f32[2048,5120]{1,0} fusion()", 640, 670, RAGGED,
       STEP + "ds.ssm_conv/add"),
    op("%fusion.6 = bf16[2048,2560]{1,0} fusion()", 670, 700, RAGGED,
       "jit(ds_ragged_step_jamba)/ds.mlp/dot_general")]
STEPS = [
    span("ds:serve.step", 0, 450, step=1, kind="ragged", live_tokens=2000,
         state_rows_read=26 * 200, state_rows_written=26 * 201,
         scan_tokens=26 * 2000, state_row_bytes=5058560, block_size=128),
    span("ds:serve.step", 450, 800, step=2, kind="burst", live_tokens=4096,
         state_rows_read=26 * 4096, state_rows_written=26 * 4096,
         scan_tokens=26 * 4096, state_row_bytes=5058560, block_size=128)]


def _trace(steps, ops=OPS):
    return {
        "/device:TPU:0": {
            "XLA Modules": [(f"jit_ds_ragged_step_jamba({RAGGED})", 0,
                             1000 * US, {}, {})],
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


def test_the_readers_read_the_new_scopes_and_counts(traced):
    traced(_trace(STEPS))
    read = lambda metric: loader.load_reader(pb.ROOT, metric).read(RECORD)
    assert read("serve_ssm_scan_ms_per_step") == pytest.approx(0.440 / 2)
    assert read("serve_ssm_proj_ms_per_step") == pytest.approx(0.200 / 2)
    assert read("serve_state_bytes_per_seq") == 5058560
    roof = loader.load_reader(pb.ROOT, "serve_ssm_scan_roofline_share")
    # a slot's h in or out: 16 x 5120 bfloat16; a row: x, dt in and y out a
    # channel, B and C a state
    assert roof.must_move_bytes(1, 1, 0, 5120, 16) == 2 * 16 * 5120 * 2
    assert roof.must_move_bytes(0, 0, 1, 5120, 16) == 10 * 5120 + 8 * 16
    assert roof.must_compute_ops(1, 5120, 16) == 7 * 16 * 5120
    floor = sum(max(roof.must_move_bytes(r, w, t, 5120, 16) / 819e9,
                    roof.must_compute_ops(t, 5120, 16) / 197e12)
                for r, w, t in ((26 * 200, 26 * 201, 26 * 2000),
                                (26 * 4096, ) * 3))
    assert roof.read(RECORD) == pytest.approx(100 * floor / 440e-6)
    # both kinds of step are bound by their bytes: the matrix peak is no
    # vector unit's, and the operations' side reads far under the bytes'
    assert roof.must_compute_ops(26 * 2000, 5120, 16) / 197e12 < \
        0.1 * roof.must_move_bytes(26 * 200, 26 * 201, 26 * 2000, 5120,
                                   16) / 819e9


@pytest.mark.parametrize("metric", sorted(MINE))
def test_a_reader_gives_nothing_on_a_program_without_its_names(
        metric, traced, monkeypatch):
    """The parent commit's program: no scope, no kernel, no count.  Nothing
    is read and nothing is raised; an untraced run and no trace file alike."""
    reader = loader.load_reader(pb.ROOT, metric)
    assert reader.read(RECORD) is None                    # no trace file
    bare = [e[:3] + ({k: v for k, v in e[3].items() if k in (
        "step", "kind", "live_tokens", "block_size")}, ) + e[4:]
        for e in STEPS]
    parents = [op(o[0], o[1] / US, o[2] / US, RAGGED,
                  "jit(ds_ragged_step_llama)/ds.attn/dot_general")
               for o in OPS]
    traced(_trace(bare, parents))
    assert reader.read({"trace": None}) is None           # an untraced run
    assert reader.read(RECORD) is None
    names = program_trace.program_names()
    for scope in ("SCOPE_SSM_SCAN", "SCOPE_SSM_PROJ"):
        monkeypatch.delattr(names, scope)          # the parent's names.py
    monkeypatch.setattr(serve_trace, "_CACHE", {})
    assert reader.read(RECORD) is None
