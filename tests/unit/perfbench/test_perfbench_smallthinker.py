"""SmallThinker on the training path: the plain reference against the program
in float32 and against the bf16 engine at ``tiny_smallthinker``, the planted
faults the comparison rejects, the shares adding up, the configuration's
floors, the cell, and the readers of a routed training step on a written
trace."""

import os
import shutil

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import pb_helpers as pb
from perfbench import flops, harness, loader, program_trace

CELL = "smallthinker_21b_train_8k"
CONFIG = "smallthinker_21b_1chip"
SEEDS = (1, 2, 3_000_000_000)


def reader(name):
    return loader.load_reader(pb.ROOT, name)


# ------------------------------------------------ reference against program
def test_reference_equals_the_programs_model_in_float32(monkeypatch):
    """Logits, loss and the gradient of every parameter, with the reference's
    blocks made small enough that several of each kind run (96 tokens in
    blocks of 32, the head's 95 in three with a filled last).  2e-4: float32
    sums in another order, as the other references' tests."""
    config, arch, ref = pb.parts("tiny_smallthinker")
    monkeypatch.setattr(ref, "QUERY_BLOCK", 32)
    monkeypatch.setattr(ref, "ROW_BLOCK", 32)
    config = dict(config, program={"train": {"model": {
        "remat": False, "dtype": "float32"}}})
    model, _ = arch.build(config, "train")
    sizes = arch.reference_sizes(config, "train")
    ids = np.random.default_rng(0).integers(0, 256, size=(2, 96))
    params = model.init(jax.random.PRNGKey(3), jnp.asarray(ids))["params"]
    loss_of = lambda p: model.apply({"params": p}, jnp.asarray(ids),
                                    jnp.asarray(ids))[0]
    with jax.default_matmul_precision("highest"):
        want = model.apply({"params": params}, jnp.asarray(ids))
        want_loss, want_grads = jax.value_and_grad(loss_of)(params)
    for b in range(2):
        got = ref.logits_at(params, ids[b], np.arange(96), sizes)
        np.testing.assert_allclose(got, want[b], atol=2e-4, rtol=2e-4)
    fn = ref.make_loss_and_grad(sizes, 2)
    loss, grads = ref.batch_loss_and_grad(fn, ref.f32(params),
                                          jnp.asarray(ids))
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    for got, exp in zip(jax.tree_util.tree_leaves(grads),
                        jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(got, exp, rtol=2e-4, atol=2e-4 * float(
            jnp.max(jnp.abs(exp))))
    # every assumption and both layouts are read: another reading differs
    for change in ({"norm_topk_prob": False}, {"expert_activation": "silu"},
                   {"router_input": "attention_norm"},
                   {"rope_layout": (1, 1, 1, 1)},
                   {"rope_layout": (0, 0, 0, 0)},
                   {"sliding_window_layout": (0, 0, 0, 0)},
                   {"num_experts_per_tok": 1}):
        off = ref.logits_at(params, ids[0], np.arange(96),
                            dict(sizes, **change))
        assert float(jnp.max(jnp.abs(off - want[0]))) > 1e-2, change


def test_the_references_shares_add_up_to_the_uncut_layer():
    """Four shares of 2 of 8 experts: attention (every chip's alike, counted
    once) plus the four expert parts is the layer with all 8 held."""
    config, arch, ref = pb.parts("tiny_smallthinker")
    sizes = arch.reference_sizes(config, "train")
    key = jax.random.PRNGKey(0)
    part = lambda i, *shape: jax.random.normal(
        jax.random.fold_in(key, i), shape, jnp.float32) / np.sqrt(shape[-2])
    D, I, E = 64, 32, 8
    lp = {"input_layernorm": {"weight": jnp.ones(D)},
          "post_attention_layernorm": {"weight": jnp.ones(D)},
          "self_attn": {"q_proj": {"kernel": part(1, D, 4, 16)},
                        "k_proj": {"kernel": part(2, D, 2, 16)},
                        "v_proj": {"kernel": part(3, D, 2, 16)},
                        "o_proj": {"kernel": part(4, 64, D)}},
          "moe": {"gate": {"kernel": part(5, D, E)}, "w1": part(6, E, D, I),
                  "w3": part(7, E, D, I), "w2": part(8, E, I, D)}}
    x = jax.random.normal(jax.random.fold_in(key, 9), (40, D), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = ref.layer(x, lp, dict(sizes, experts_held=None), 16, 1)
        attention = ref.attention_block(x, lp, sizes, 16, 1)
        total = attention
        for chip in range(4):
            mine = dict(lp, moe=dict(lp["moe"], **{
                k: lp["moe"][k][2 * chip:2 * chip + 2]
                for k in ("w1", "w2", "w3")}))
            total = total + ref.layer(
                x, mine, dict(sizes, experts_held=2, first_expert=2 * chip),
                16, 1) - attention
    np.testing.assert_allclose(total, whole, atol=1e-5 * float(
        jnp.max(jnp.abs(whole))))


# ------------------------------------------- the engine against the reference
#: another reading of what the config leaves open, a rotary on the
#: position-free layer, no window: the reference's sizes say which
FAULTS = {
    "unnormalised_top_k_weights": {"norm_topk_prob": False},
    "silu_for_relu": {"expert_activation": "silu"},
    "rotary_on_the_full_layer": {"rope_layout": (1, 1, 1, 1)},
    "no_window": {"sliding_window_layout": (0, 0, 0, 0)},
    "router_fed_the_attention_norm": {"router_input": "attention_norm"},
}
#: median over a sequence's positions of the largest difference between the
#: bf16 engine's logits and the float32 reference's (spread 1).  Measured on
#: the CPU over 12 seeds: 0.025-0.043 (activations rounded to 8 bits of
#: mantissa through four layers; a token whose router margin lies inside
#: that rounding takes another expert and reads up to 1.2, so the MEDIAN is
#: compared, not the largest).  The four faults that change what a token's
#: experts compute or what it attends read 0.57-1.37.
LOGIT_MEDIAN_TOL = 0.06
#: || (w2 - w0) - (w2_ref - w0) || / || w2_ref - w0 || over every parameter
#: after two updates.  Measured over 12 seeds on 1 and 8 devices: the engine
#: 0.10-0.28 (Adam's first steps move a weight by lr x sign(gradient), and a
#: gradient near zero has the other sign in bfloat16), the reference with
#: its parameters kept in bfloat16 0.39-0.47 (an update of 1e-3 on a weight
#: of 0.1 is one or two steps of bfloat16: rounded away or doubled)
CHANGE_TOL = 0.335


def two_updates(ref, sizes, w0, batch, adam, keep_in=None):
    """The reference's parameters after two AdamW updates on ``batch``;
    ``keep_in``: the mantissa bits they are rounded to before the first loss
    and after every update (7: kept in bfloat16, no float32 master weights;
    ``reduce_precision``, which no compiler may elide)."""
    fn = ref.make_loss_and_grad(sizes, batch.shape[0])
    r = lambda t: jax.tree_util.tree_map(
        lambda x: jax.lax.reduce_precision(x, exponent_bits=8,
                                           mantissa_bits=keep_in), t) \
        if keep_in else t
    params = r(jax.tree_util.tree_map(jnp.array, w0))
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    for t in range(2):
        _, grads = ref.batch_loss_and_grad(fn, params, batch)
        params, m, v = ref.base.adamw_step(
            params, grads, m, v, jnp.float32(t + 1), **adam)
        params = r(params)
    return params


def change_error(got, want, w0):
    leaves = jax.tree_util.tree_leaves
    num = sum(float(jnp.sum(jnp.square((a - w) - (b - w))))
              for a, b, w in zip(leaves(got), leaves(want), leaves(w0)))
    den = sum(float(jnp.sum(jnp.square(b - w)))
              for b, w in zip(leaves(want), leaves(w0)))
    return (num / den) ** 0.5


def test_engine_agrees_with_the_reference_and_planted_faults_do_not():
    """The bf16 ZeRO-3 engine against the float32 reference on seeded
    weights: its losses over two updates under the rule a chip run uses
    (TOL_FACTOR x the worst error the configuration's file states), its
    logits (LOGIT_MEDIAN_TOL) and what two updates did to its parameters
    (CHANGE_TOL).  Each planted fault is read against the same limits on
    every seed: the four that change what a token computes fail the logits'
    limit by a factor of nine or more; master weights dropped fails the
    parameters' limit.  The router fed RMSNorm_1(x) is the weak one at seeded
    weights (norm weights of 1 scale a token's 8 logits alike: the SAME
    experts, another softmax temperature): it reads above the engine's own
    error on every seed and over the limit on some, and the float32
    comparison above rejects it by a factor of fifty."""
    config, arch, ref = pb.parts("tiny_smallthinker")
    train = loader.load_part(pb.ROOT, "jobs", "train")
    traffic = loader.load_json(loader.part_path(
        pb.ROOT, "traffic", "tiny_train", "json"))
    sizes = arch.reference_sizes(config, "train")
    model, tp_rules = arch.build(config, "train")
    adam = dict(lr=traffic["optimizer"]["params"]["lr"], b1=0.9, b2=0.999,
                eps=1e-8, weight_decay=0.0)
    tol = train.tolerances(harness.Context(config=config), 2)
    at = np.arange(traffic["seq_len"])
    for seed in SEEDS:
        rows = jax.device_count()
        batch = np.random.default_rng([seed, 1]).integers(
            0, 256, size=(rows, traffic["seq_len"])).astype(np.int32)
        ctx = harness.Context(traffic=traffic, devices=jax.devices())
        engine = train._build_engine(ctx, model, tp_rules,
                                     harness.fold_seed(seed), batch)
        w0 = jax.tree_util.tree_map(jnp.asarray, engine.get_fp32_param())
        logits = model.apply({"params": engine.params},
                             batch[:1])[0].astype(jnp.float32)
        gap = lambda cfg: float(np.median(np.max(np.abs(np.asarray(
            logits - ref.logits_at(w0, batch[0], at, cfg))), axis=1)))
        own = gap(sizes)
        assert own <= LOGIT_MEDIAN_TOL, (seed, own)
        for name, change in FAULTS.items():
            read = gap(dict(sizes, **change))
            if name == "router_fed_the_attention_norm":
                assert read > 1.25 * own, (seed, name, read, own)
            else:
                assert read > 9 * LOGIT_MEDIAN_TOL, (seed, name, read)
        got = [float(train._step(engine, batch)) for _ in range(2)]
        w2 = jax.tree_util.tree_map(jnp.asarray, engine.get_fp32_param())
        got.append(float(train._step(engine, batch)))
        want = ref.train_losses(jax.tree_util.tree_map(jnp.array, w0),
                                batch, sizes, steps=2, adam=adam)
        for k, v in train.loss_errors(got, want).items():
            assert v <= tol["train." + k], (seed, k, v)
        # un-normalised weights also move what an update does to the loss
        off = ref.train_losses(
            jax.tree_util.tree_map(jnp.array, w0), batch,
            dict(sizes, norm_topk_prob=False), steps=2, adam=adam)
        assert train.loss_errors(got, off)["drop1_rel_err"] > \
            tol["train.drop1_rel_err"], seed
        ref2 = two_updates(ref, sizes, w0, batch, adam)
        assert change_error(w2, ref2, w0) <= CHANGE_TOL, seed
        assert change_error(two_updates(ref, sizes, w0, batch, adam, 7),
                            ref2, w0) > \
            CHANGE_TOL, seed
        engine = None
        train._release()


# -------------------------------------------------- configuration and cell
def test_the_configuration_is_one_chips_share_inside_the_floors():
    """What the lint does not check for ``moe_num_primary_experts`` (it is no
    ``SHARE_KEY``): 16 held on each of 4 chips is the published 64, at least
    8; and the arithmetic of the cut."""
    config, arch, _ = pb.parts(CONFIG)
    pub, share = config["published"], config["share"]
    assert share["chips_sharing_a_layer"] == 4 and share["this_chip"] == 0
    held = config["moe_num_primary_experts"]
    assert held * share["chips_sharing_a_layer"] == \
        pub["moe_num_primary_experts"] == 64 and held >= 8
    assert config["vocab_size"] * 4 == pub["vocab_size"]
    assert config["num_hidden_layers"] == {"train": 4}
    # one whole period of both layouts, the first: full without positions,
    # then three window layers with rotary
    assert len(pub["rope_layout"]) == len(pub["sliding_window_layout"]) == 52
    assert pub["rope_layout"] == pub["sliding_window_layout"] == \
        [0, 1, 1, 1] * 13
    fields = arch.program_fields(config, "train")
    assert fields["sliding_window_layout"] == fields["rope_layout"] == \
        (0, 1, 1, 1)
    assert (fields["moe_num_primary_experts"], fields["experts_held"],
            fields["first_expert"]) == (64, 16, 0)
    assert set(config["assumed"]) >= {
        "router_input", "expert_activation", "rotary", "auxiliary_loss",
        "secondary_experts", "weights"}
    model, _ = arch.build(config, "train")
    shapes = arch.param_shapes(model)
    n = sum(int(np.prod(x.shape))
            for x in jax.tree_util.tree_leaves(shapes))
    assert round(n / 1e6, 1) == 656.5           # 10.50 GB at 16 B
    assert shapes["layers_0"]["moe"]["w1"].shape == (16, 2560, 768)
    assert shapes["layers_0"]["moe"]["gate"]["kernel"].shape == (2560, 64)
    assert shapes["lm_head"]["kernel"].shape == (2560, 37984)
    # a share further along holds the experts further along
    other = dict(config, share=dict(share, this_chip=3))
    assert arch.share_of(other) == (64, 16, 48)


def test_reference_sizes_carry_what_the_train_job_reads():
    config, arch, _ = pb.parts(CONFIG)
    sizes = arch.reference_sizes(config, "train")
    assert sizes["num_hidden_layers"] == 4 and sizes["vocab_size"] == 37984
    per_token = flops.train_flops_per_token(sizes, 4, 8192)
    # flops.py counts every token's six experts as held here and every layer
    # with the window: 2.47 GFLOP a token against 1.9 for the share
    assert 2.3e9 < per_token < 2.6e9
    assert 0.2 < flops.lm_head_share(sizes, 4, 8192) < 0.3
    assert (sizes["experts_held"], sizes["first_expert"],
            sizes["num_local_experts"], sizes["num_experts_per_tok"]) == (
        16, 0, 64, 6)


def test_the_cell_is_the_issues_traffic_on_one_chip():
    manifest = pb.read_manifest(pb.ROOT)
    cell = loader.find(manifest["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "train_8k", 1)
    assert len(cell["why"]) <= 200
    t = loader.load_json(loader.part_path(pb.ROOT, "traffic", "train_8k",
                                          "json"))
    assert (t["job"], t["seq_len"], t["micro_batch_per_chip"],
            t["gradient_accumulation_steps"], t["dtype"], t["zero_stage"]) \
        == ("train", 8192, 1, 1, "bfloat16", 3)
    assert t["optimizer"] == {"type": "fusedadam", "params": {"lr": 0.0001}}
    assert (t["sync_every_steps"], t["check_steps"], t["trace_steps"]) == (
        10, 2, 20)
    reports = {m["name"] for section in ("end_to_end", "per_layer")
               for m in loader.metrics_of_cell(manifest, section, CELL)}
    assert reports >= {
        "train_tokens_per_s_per_chip", "setup_s", "train_host_ms_per_step",
        "device_idle_share.train", "peak_hbm_gb.train",
        "train_optimizer_ms_per_step", "train_lm_head_ms_per_step",
        "train_flash_kernel_ms_per_step", "train_moe_experts_ms_per_step",
        "train_moe_router_ms_per_step", "train_moe_experts_roofline_share",
        "train_expert_rows_max_share"}
    # a tier buffer of 15 360 rows: what docs/kernels.md's readings are of
    from deepspeed_tpu.moe.held_experts import tier_rows
    assert tier_rows(8192, 6, 16, 64) == 15360


# ------------------------------------------- the readers, on a written trace
US = 1000.0
MICRO, APPLY = 11, 12


def op(instr, start, end, program, scope):
    return (instr, start * US, end * US, {"device_offset_ps": 0},
            {"program_id": program, "hlo_category": "x",
             "tf_op": scope + ":"})


def span(name, start, end, **counts):
    return (name, start * US, end * US, counts, {})


FUSION = "%fusion.{} = bf16[15360,768]{{1,0}} fusion(bf16[8]{{0}} %p), " \
    "kind=kOutput"
PATH = "jit(ds_micro_flat)/{}/layers_1/ds.mlp/moe/{}/ragged_dot_general"

#: two traced steps of a routed training loop: the second span brings the
#: first step's counts, a span after it the second's and the third's
ROUTED = {
    "/device:TPU:0": {
        "XLA Modules": [
            (f"jit_ds_micro_flat({MICRO})", 0, 400 * US, {}, {}),
            (f"jit_ds_apply_update({APPLY})", 400 * US, 500 * US, {}, {})],
        "XLA Ops": [
            op(FUSION.format(1), 0, 100, MICRO, PATH.format(
                "jvp(SmallThinkerModel)", "ds.moe_experts")),
            op(FUSION.format(2), 100, 130, MICRO, PATH.format(
                "jvp(SmallThinkerModel)", "ds.moe_router")),
            op(FUSION.format(3), 130, 330, MICRO, PATH.format(
                "transpose(jvp(SmallThinkerModel))", "transpose(jvp("
                "ds.moe_experts))")),
            op(FUSION.format(4), 330, 340, MICRO, PATH.format(
                "transpose(jvp(SmallThinkerModel))",
                "transpose(jvp(ds.moe_router))")),
            op(FUSION.format(5), 340, 400, MICRO, "jit(ds_micro_flat)/"
               "jvp(SmallThinkerModel)/ds.lm_head_loss/lm_head/dot_general"),
            op(FUSION.format(6), 500, 600, MICRO, PATH.format(
                "jvp(SmallThinkerModel)", "ds.moe_experts")),
            op(FUSION.format(7), 600, 900, MICRO, PATH.format(
                "transpose(jvp(SmallThinkerModel))/rematted_computation",
                "ds.moe_experts")),
        ],
    },
    "/host:CPU": {
        "python3": [
            span("pb:traced", 0, 1000),
            span("ds:train.micro", 0, 10, step=4, micro_step=4),
            span("ds:train.apply", 10, 20, step=4, micro_step=4),
            span("ds:train.micro", 500, 510, step=5, micro_step=5,
                 expert_copies=49000, expert_active=64,
                 expert_rows_max=3600, micro_steps_covered=1),
            span("ds:train.apply", 510, 520, step=5, micro_step=5),
            span("ds:train.micro", 900, 910, step=6, micro_step=6,
                 expert_copies=98000, expert_active=127,
                 expert_rows_max=7000, micro_steps_covered=2)],
    },
}


@pytest.fixture()
def traced_root(tmp_path, monkeypatch):
    """A benchmark root that holds the manifest, the configuration and a
    written trace of the cell; returns ``write(planes)``."""
    root = tmp_path / "root"
    os.makedirs(root / "perfbench" / "configs")
    for f in ("BENCHMARK.json", f"perfbench/configs/{CONFIG}.json"):
        shutil.copy(os.path.join(pb.ROOT, f), root / f)
    monkeypatch.setattr(program_trace, "ROOT", str(root))

    def write(planes, cell=CELL):
        folder = root / ".perfbench_trace" / cell / "plugins" / "profile" / "t"
        os.makedirs(folder, exist_ok=True)
        program_trace.write_planes(planes, str(folder / "x.xplane.pb"))
    return write


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
READERS = ("train_moe_experts_ms_per_step", "train_moe_router_ms_per_step",
           "train_moe_experts_roofline_share", "train_expert_rows_max_share")


def test_readers_of_a_routed_training_step_on_a_written_trace(traced_root):
    traced_root(ROUTED)
    record = {"trace": {"busy_s": 1.0}, "peaks": PEAKS}
    # forward, backward and recomputed ops under the scope, over two steps
    assert reader(READERS[0]).read(record) == pytest.approx(
        (100 + 200 + 100 + 300) / 1e3 / 2)
    assert reader(READERS[1]).read(record) == pytest.approx(40 / 1e3 / 2)
    roof = reader(READERS[2])
    counts = {"expert_copies": 147000, "expert_active": 191}
    floor = roof.floor_seconds(counts, 2560, 768, PEAKS)
    assert floor == pytest.approx(147000 * 18 * 2560 * 768 / 197e12)
    # three counted micro-steps against three steps' worth of measured time
    assert roof.read(record) == pytest.approx(
        100 * floor / (0.35e-3 * 3))
    assert reader(READERS[3]).read(record) == pytest.approx(
        100 * 10600 * 16 / 147000)


def test_the_two_count_functions():
    roof = reader("train_moe_experts_roofline_share")
    # nine products of D x I a copy, two operations a multiply-add
    assert roof.must_compute_flops(1, 2560, 768) == 9 * 2 * 2560 * 768
    assert roof.must_compute_flops(12288, 2560, 768) == \
        12288 * roof.must_compute_flops(1, 2560, 768)
    # three matrices an expert, read twice and written once, 2 bytes
    assert roof.must_move_bytes(1, 2560, 768) == 3 * 2560 * 768 * 3 * 2
    assert roof.must_move_bytes(64, 2560, 768) == \
        64 * roof.must_move_bytes(1, 2560, 768)
    # the cell's step (768 rows an expert) is bound by compute, a step of
    # 16 rows an expert by the weights' bytes
    busy = {"expert_copies": 64 * 768, "expert_active": 64}
    thin = {"expert_copies": 64 * 16, "expert_active": 64}
    assert roof.floor_seconds(busy, 2560, 768, PEAKS) == pytest.approx(
        roof.must_compute_flops(64 * 768, 2560, 768) / 197e12)
    assert roof.floor_seconds(thin, 2560, 768, PEAKS) == pytest.approx(
        roof.must_move_bytes(64, 2560, 768) / 819e9)
    rows = reader("train_expert_rows_max_share")
    assert rows.share({"expert_copies": 4 * 16 * 768,
                       "expert_rows_max": 4 * 768}, 16) == 100.0
    assert rows.share({"expert_copies": 0, "expert_rows_max": 0}, 16) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_return_none_where_there_is_nothing_to_read(
        name, traced_root, monkeypatch):
    """No traced run; a trace with no op under the scopes and no counts (the
    parent's program, or a dense model's cell); a program without the
    names."""
    read = reader(name).read
    assert read({"trace": None, "peaks": PEAKS}) is None
    plain = {
        "/device:TPU:0": {"XLA Ops": [op(
            FUSION.format(1), 0, 100, MICRO,
            "jit(ds_micro_flat)/jvp(LlamaModel)/layers_0/mlp/dot_general")]},
        "/host:CPU": {"python3": [
            span("pb:traced", 0, 1000),
            span("ds:train.micro", 0, 10, step=4, micro_step=4),
            span("ds:train.apply", 10, 20, step=4, micro_step=4)]}}
    traced_root(plain)
    record = {"trace": {"busy_s": 1.0}, "peaks": PEAKS}
    assert read(record) is None
    monkeypatch.setattr(program_trace, "program_names", lambda: None)
    assert read(record) is None
