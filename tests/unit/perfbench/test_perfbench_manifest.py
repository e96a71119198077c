"""Lint of ``BENCHMARK.json`` against the contract, and of ``perfbench/``
against its own rules (parts found by name, public names only, no CPU
fallback)."""

import json
import os
import re
import subprocess
import sys

import pytest

import pb_helpers as pb

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return json.load(open(os.path.join(pb.ROOT, "BENCHMARK.json")))


def _line(s, limit=200):
    return 1 <= len(s) <= limit and "\n" not in s and "\t" not in s


def test_top_level_keys_and_sizes(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(pb.ROOT, "BENCHMARK.json")) < 65536
    assert manifest["command"] == ["python3", "perfbench/run.py"]
    assert manifest["paths"] == ["perfbench", "tests/unit/perfbench"]
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    # a full check of 24 cells fits the driver's budget
    runs = 2 + 14 * 24
    assert runs * (manifest["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


#: a key of a configuration that ``reduced`` may never name: a width (hidden,
#: intermediate, latent, state or projection size, a head size or count, an
#: expansion factor, the experts a token uses, a router spans or every chip
#: holds alike, a window, the vocabulary).  The cuts are depth and, for a
#: configuration that states itself ONE CHIP'S SHARE of a deployment (a
#: ``share`` block), the keys of ``SHARE_KEY``.
WIDTH_KEY = re.compile(r"(_dim|_rank|hidden_size|intermediate_size"
                       r"|experts_per_tok|vocab_size)$"
                       r"|^(num_local_experts|num_experts|n_routed_experts"
                       r"|n_shared_experts|num_shared_experts|d_model|d_ff)$"
                       r"|head|window")

#: of those, a key that counts what ONE CHIP HOLDS of a layer divided over
#: several: the routed experts (the published count stays the router's width)
#: and the rows of the vocabulary.  Widths like any other with no ``share``
#: block; with one, ``reduced`` may name them, under the floors of the
#: model-configs guide's section 4 (``share_faults``).
EXPERT_KEYS = ("num_experts", "num_local_experts", "n_routed_experts")
SHARE_KEY = re.compile(r"^(%s|vocab_size)$" % "|".join(EXPERT_KEYS))
SHARE_FIELDS = {"chips_sharing_a_layer", "this_chip", "how"}
MIN_EXPERTS_HELD = 8          # routed experts held in a layer that has them
MAX_VOCAB_CUT = 8             # the slice is at least an eighth
MIN_LAYERS_PAST_DENSE = 4     # and at least one whole period of layer kinds


WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "num_attention_heads", "num_key_value_heads", "num_heads",
          "head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
          "q_lora_rank", "num_experts_per_tok", "num_local_experts",
          "num_experts", "n_routed_experts", "n_shared_experts",
          "num_shared_experts", "d_model", "d_ff", "sliding_window",
          "vocab_size")
CUTS = ("num_hidden_layers", "max_position_embeddings", "rope_theta",
        "rms_norm_eps", "first_k_dense_replace")
SHARES = EXPERT_KEYS + ("vocab_size",)


@pytest.mark.parametrize("key", WIDTHS + CUTS)
def test_width_key_names_every_width_and_no_depth(key):
    assert bool(WIDTH_KEY.search(key)) == (key in WIDTHS)


@pytest.mark.parametrize("key", WIDTHS + CUTS)
def test_share_key_names_what_a_chip_holds_and_no_other_width(key):
    """Every share key is a width (so it is one with no ``share`` block), and
    no head count, experts per token, shared expert, window or size is one."""
    assert bool(SHARE_KEY.search(key)) == (key in SHARES)
    assert key not in SHARES or WIDTH_KEY.search(key)


def _whole(n):
    return isinstance(n, int) and not isinstance(n, bool)


def period_of(types):
    """The least ``p`` with ``types[i] == types[i + p]`` throughout."""
    return next(p for p in range(1, len(types) + 1)
                if all(a == b for a, b in zip(types, types[p:])))


def share_faults(body, reduced, published):
    """The faults of a configuration that states a ``share`` block: the block
    itself, then each share key in ``reduced`` and the depth against the
    floors of the model-configs guide's section 4."""
    share, faults = body["share"], []
    if not isinstance(share, dict) or not set(share) <= SHARE_FIELDS:
        return [f"`share` has other keys than {sorted(SHARE_FIELDS)}"]
    n = share.get("chips_sharing_a_layer")
    if not _whole(n) or n < 2:
        return ["`share.chips_sharing_a_layer` is not a whole number of at "
                "least 2"]
    chip = share.get("this_chip", 0)
    if not _whole(chip) or not 0 <= chip < n:
        faults.append(f"`share.this_chip` is not one of 0..{n - 1}")
    if not isinstance(share.get("how"), str) or not _line(share["how"]):
        faults.append("`share.how` is not one line of what is divided and "
                      "what every chip computes alike")
    cut = [k for k in reduced if SHARE_KEY.match(k)]
    if not cut:
        faults.append("a `share` block and no share key in `reduced`")
    for key in cut:
        held, whole = body.get(key), published.get(key)
        if not _whole(held) or not _whole(whole):
            faults.append(f"share key {key!r} is not a whole number, run "
                          "and published")
        elif key == "vocab_size":
            if held < 1 or whole % held:
                faults.append(f"{key!r}: {held} rows held does not divide "
                              f"the published {whole}")
            elif whole // held > MAX_VOCAB_CUT:
                faults.append(f"{key!r}: {held} rows held of {whole} is "
                              f"under 1/{MAX_VOCAB_CUT} of the vocabulary")
        elif held * n != whole:
            faults.append(f"{key!r}: {held} held on each of {n} chips is "
                          f"not the published {whole}")
        elif held < MIN_EXPERTS_HELD:
            faults.append(f"{key!r}: {held} experts held, under the floor "
                          f"of {MIN_EXPERTS_HELD}")
    dense = published.get("first_k_dense_replace") or 0
    floors = {"the leading dense layers + 4": dense + MIN_LAYERS_PAST_DENSE}
    following = (published.get("layer_types") or [])[dense:]
    if following:
        floors["the leading dense layers + one period of `layer_types`"] = \
            dense + period_of(following)
    depths = body.get("num_hidden_layers")
    for job, depth in (depths.items() if isinstance(depths, dict)
                       else [("every job", depths)]):
        for what, floor in floors.items():
            if not _whole(depth) or depth < floor:
                faults.append(f"depth {depth!r} ({job}) is under {what} = "
                              f"{floor}")
    return faults


def lint_config(body, reduced):
    """The faults of one configuration file against its OWN statement of what
    was published: every key of ``published`` is run at the published value
    unless ``reduced`` names it, and ``reduced`` names no width: only depth
    and, where the file states a ``share`` block (one chip's share of a
    deployment), the experts held and the vocabulary's slice, under the
    guide's floors.  Returns the faults as strings (none = "published widths,
    never cut" holds)."""
    faults = []
    if set(body.get("reduced", reduced)) != set(reduced):
        faults.append("the file's `reduced` is not the manifest's")
    published = {k: v for k, v in body.get("published", {}).items()
                 if not k.startswith("_")}
    if not published:
        faults.append("no `published` block")
    shared = "share" in body
    if shared:
        faults += share_faults(body, reduced, published)
    for key in reduced:
        if not NAME.match(key) or key not in body:
            faults.append(f"reduced key {key!r} is not a key of the file")
        if key not in published:
            faults.append(f"reduced key {key!r} is not in `published`")
        if WIDTH_KEY.search(key) and not (shared and SHARE_KEY.match(key)):
            faults.append(f"reduced names the width {key!r}" + (
                " with no `share` block" if SHARE_KEY.match(key) else ""))
    for key, value in published.items():
        if key in reduced:
            if body.get(key) == value:
                faults.append(f"{key!r} is in `reduced` and not changed")
        elif key not in body:
            faults.append(f"published key {key!r} is left out")
        elif body[key] != value:
            faults.append(f"{key!r}: run {body[key]!r}, published {value!r}, "
                          "not in `reduced`")
    return faults


def test_configs(manifest):
    assert 1 <= len(manifest["configs"]) <= 24
    files = set()
    used = {w["config"] for w in manifest["workloads"]}
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert PATH.match(c["file"]) and c["file"].startswith("perfbench/")
        assert c["file"] not in files
        files.add(c["file"])
        body = json.load(open(os.path.join(pb.ROOT, c["file"])))
        assert body["source"] == c["source"]
        assert len(c["reduced"]) <= 16
        # published widths, never cut: against the file's own `published`
        assert lint_config(body, c["reduced"]) == [], c["name"]
    assert len({c["name"] for c in manifest["configs"]}) == len(files)


def test_every_configuration_file_states_what_was_published():
    """The tiny presets too (they state themselves): the lint has no
    configuration it cannot read."""
    folder = os.path.join(pb.ROOT, "perfbench", "configs")
    for f in sorted(os.listdir(folder)):
        body = json.load(open(os.path.join(folder, f)))
        assert lint_config(body, list(body.get("reduced", []))) == [], f
        assert all(isinstance(v.get("value"), float) and v.get("where")
                   for k, v in body["measured_worst"].items()
                   if not k.startswith("_")), f


#: OLMoE-1B-7B-0125-Instruct as the model-configs guide's catalog has it
#: (https://huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct/blob/main/config.json):
#: not Mistral's widths, 64 experts, 8 a token
OLMOE = {
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 1024,
    "max_position_embeddings": 4096, "model_type": "olmoe",
    "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 16,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304}


def _olmoe(**changes):
    body = dict(OLMOE, published=dict(OLMOE), arch="olmoe",
                num_hidden_layers={"serve": 8},
                reduced={"num_hidden_layers": "16 published"})
    body.update(changes)
    return body


@pytest.mark.parametrize("changes,reduced,fault", [
    ({}, ["num_hidden_layers"], None),
    ({"intermediate_size": 512}, ["num_hidden_layers"], "intermediate_size"),
    ({"intermediate_size": 512,
      "reduced": {"num_hidden_layers": "", "intermediate_size": ""}},
     ["num_hidden_layers", "intermediate_size"], "names the width"),
    ({"num_experts": 8, "reduced": {"num_hidden_layers": "",
                                    "num_experts": ""}},
     ["num_hidden_layers", "num_experts"], "names the width"),
    ({"num_attention_heads": 8,
      "reduced": {"num_hidden_layers": "", "num_attention_heads": ""}},
     ["num_hidden_layers", "num_attention_heads"], "names the width"),
    ({"num_key_value_heads": 4,
      "reduced": {"num_hidden_layers": "", "num_key_value_heads": ""}},
     ["num_hidden_layers", "num_key_value_heads"], "names the width"),
    ({"num_attention_heads": 8}, ["num_hidden_layers"],
     "num_attention_heads"),
    ({"num_experts_per_tok": 2}, ["num_hidden_layers"],
     "num_experts_per_tok"),
    ({"vocab_size": 32000}, ["num_hidden_layers"], "vocab_size"),
    ({"rope_theta": None}, ["num_hidden_layers"], "rope_theta"),
    ({"num_hidden_layers": 16}, ["num_hidden_layers"], "not changed"),
    ({"published": {}}, ["num_hidden_layers"], "no `published`"),
], ids=["olmoe_as_published", "expert_width_halved_unlisted",
        "expert_width_listed", "experts_listed", "heads_listed",
        "kv_heads_listed", "heads_cut_unlisted", "experts_per_token_cut",
        "vocabulary_cut", "key_nulled", "reduced_but_unchanged",
        "nothing_published"])
def test_published_widths_are_held_to_the_files_own_statement(
        changes, reduced, fault):
    """A configuration that is not Mistral-shaped passes the lint at its own
    published sizes, and fails it the moment a width differs from what the
    file itself says was published."""
    faults = lint_config(_olmoe(**changes), reduced)
    if fault is None:
        assert faults == []
    else:
        assert any(fault in f for f in faults), faults


#: command-a-plus-05-2026 as the model-configs guide's catalog has it
#: (https://huggingface.co/CohereLabs/command-a-plus-05-2026/blob/main/config.json):
#: 128 routed experts of width 4096, 8 a token, four shared; three sliding
#: layers to one full.  No file under perfbench/configs/: the `model_config`
#: PR that adds the architecture brings it.
COMMAND_A_PLUS = {
    "attention_bias": False, "expert_selection_fn": "sigmoid",
    "first_k_dense_replace": 0, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 4096, "layer_norm_eps": 1e-05,
    "layer_switch": 4,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 8,
    "logit_scale": 1, "max_position_embeddings": 200000,
    "model_type": "cohere2_moe", "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 32, "num_key_value_heads": 8,
    "num_shared_experts": 4,
    "order_of_interleaved_layers": "local_attn_first",
    "position_embedding_type": "rope_gptj",
    "prefix_dense_intermediate_size": 16384,
    "prefix_dense_sliding_window_pattern": 1, "rms_norm_eps": None,
    "rope_parameters": {"rope_theta": 50000, "rope_type": "default"},
    "rope_theta": 50000, "rotary_pct": 1,
    "shared_expert_combination_strategy": "average", "sliding_window": 4096,
    "tf_legacy_loss": False, "tie_word_embeddings": True,
    "use_embedding_sharing": True, "use_gated_activation": True,
    "use_parallel_block": True, "use_parallel_embedding": False,
    "use_qk_norm": False, "vocab_size": 262144}

SHARE_CUT = ["num_hidden_layers", "num_experts", "vocab_size"]


def _listed(*widths):
    """``reduced`` of a share file that lists ``widths`` beside its cuts."""
    return {k: "" for k in SHARE_CUT + list(widths)}


def _share(published, chips, **changes):
    """``published`` as ``chips`` chips' share: an eighth of the vocabulary,
    its experts divided evenly, depth 8 to serve, unless ``changes`` say."""
    body = dict(published, published=dict(published), arch="test",
                num_hidden_layers={"serve": 8},
                num_experts=published["num_experts"] // chips,
                vocab_size=published["vocab_size"] // 8,
                share={"chips_sharing_a_layer": chips,
                       "how": "routed experts and vocabulary rows divided "
                              "evenly; attention, router and shared experts "
                              "whole on every chip"},
                reduced=_listed())
    for key, value in changes.items():
        if key.startswith("share."):
            body["share"] = dict(body["share"], **{key[6:]: value})
        elif key == "share" and value is None:
            del body["share"]                 # the same file with no block
        else:
            body[key] = value
    return body


@pytest.mark.parametrize("body,fault", [
    pytest.param(_share(OLMOE, 8), None, id="olmoe_share_of_8"),
    pytest.param(_share(OLMOE, 8, **{"share.this_chip": 7}), None,
                 id="olmoe_last_of_8"),
    pytest.param(_share(COMMAND_A_PLUS, 8, num_hidden_layers={"serve": 4}),
                 None, id="command_a_plus_share_of_8"),
    pytest.param(_share(COMMAND_A_PLUS, 32, num_hidden_layers={"serve": 4}),
                 "4 experts held, under the floor of 8",
                 id="command_a_plus_4_experts"),
    pytest.param(_share(COMMAND_A_PLUS, 8, num_hidden_layers={"serve": 4},
                        vocab_size=262144 // 16), "under 1/8",
                 id="command_a_plus_sixteenth_of_vocabulary"),
    pytest.param(_share(COMMAND_A_PLUS, 8, num_hidden_layers={"serve": 3}),
                 "one period of `layer_types` = 4",
                 id="command_a_plus_depth_3"),
    pytest.param(_share(OLMOE, 16), "4 experts held, under the floor of 8",
                 id="experts_under_8"),
    pytest.param(_share(OLMOE, 8, num_experts=16),
                 "16 held on each of 8 chips is not the published 64",
                 id="experts_not_an_nth"),
    pytest.param(_share(OLMOE, 8, vocab_size=50304 // 16), "under 1/8",
                 id="vocabulary_under_an_eighth"),
    pytest.param(_share(OLMOE, 8, vocab_size=6000), "does not divide",
                 id="vocabulary_not_a_divisor"),
    pytest.param(_share(dict(OLMOE, first_k_dense_replace=1), 8,
                        num_hidden_layers={"serve": 4}),
                 "the leading dense layers + 4 = 5",
                 id="depth_under_4_past_dense"),
    pytest.param(_share(OLMOE, 8, num_hidden_layers={"serve": 8, "train": 3}),
                 "depth 3 (train)", id="one_jobs_depth_under_4"),
    pytest.param(_share(OLMOE, 8, num_experts=64, vocab_size=50304,
                        reduced={"num_hidden_layers": ""}),
                 "no share key in `reduced`", id="share_with_nothing_cut"),
    pytest.param(_share(OLMOE, 8, num_experts=64, share=None,
                        reduced={"num_hidden_layers": "", "vocab_size": ""}),
                 "names the width 'vocab_size' with no `share` block",
                 id="share_key_without_a_share"),
    pytest.param(_share(OLMOE, 8, num_attention_heads=8,
                        reduced=_listed("num_attention_heads")),
                 "names the width 'num_attention_heads'",
                 id="heads_listed_beside_a_share"),
    pytest.param(_share(OLMOE, 8, num_experts_per_tok=2,
                        reduced=_listed("num_experts_per_tok")),
                 "names the width 'num_experts_per_tok'",
                 id="experts_per_token_listed_beside_a_share"),
    pytest.param(_share(OLMOE, 8, num_experts_per_tok=2),
                 "'num_experts_per_tok': run 2, published 8",
                 id="experts_per_token_cut_beside_a_share"),
    pytest.param(_share(OLMOE, 1, num_experts=8), "at least 2",
                 id="one_chip_is_no_share"),
    pytest.param(_share(OLMOE, 8, **{"share.this_chip": 8}), "0..7",
                 id="this_chip_out_of_range"),
    pytest.param(_share(OLMOE, 8, **{"share.how": ""}), "`share.how`",
                 id="share_without_how"),
    pytest.param(_share(OLMOE, 8,
                        **{"share.stands_in_for": "the absent chips"}),
                 "other keys", id="share_with_a_stand_in"),
])
def test_one_chips_share_is_held_to_the_guides_floors(body, fault):
    """A configuration that states a ``share`` block may cut the experts held
    and the vocabulary's slice beside depth; everything else stays a width,
    and every floor of the model-configs guide's section 4 is a fault by
    name.  The manifest's ``reduced`` is the file's own."""
    faults = lint_config(body, list(body["reduced"]))
    if fault is None:
        assert faults == []
    else:
        assert any(fault in f for f in faults), faults


def test_workloads_resolve_by_name(manifest):
    from perfbench import loader
    cells = manifest["workloads"]
    assert 1 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    four = [w for w in cells if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        assert not w["config"].startswith("tiny")
        assert not w["traffic"].startswith("tiny")
        entry = loader.find(manifest["configs"], w["config"], "config")
        config = loader.load_json(os.path.join(pb.ROOT, entry["file"]))
        traffic = loader.load_json(loader.part_path(
            pb.ROOT, "traffic", w["traffic"], "json"))
        for kind, name in (("jobs", traffic["job"]),
                           ("models", config["arch"]),
                           ("reference", config["arch"])):
            assert os.path.isfile(loader.part_path(pb.ROOT, kind, name, "py"))
        assert traffic["job"] in config["num_hidden_layers"]


def test_metrics(manifest):
    from perfbench import loader
    cells = [w["name"] for w in manifest["workloads"]]
    e2e = manifest["end_to_end"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(manifest["per_layer"]) <= 128
    names = [m["name"] for m in e2e + manifest["per_layer"]]
    assert len(set(names)) == len(names)
    setup = loader.find(e2e, "setup_s", "metric")
    assert "workloads" not in setup and setup["bound"] <= 0.1
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert _line(m["layer"])
        assert callable(loader.load_reader(pb.ROOT, m["name"]).read)
        moved = loader.find(e2e, m["moves"], "end-to-end metric")
        for cell in m.get("workloads", cells):
            assert cell in moved.get("workloads", cells), (m["name"], cell)
    for m in e2e + manifest["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= set(cells)
    for cell in cells:
        mine = loader.metrics_of_cell(manifest, "end_to_end", cell)
        assert len(mine) >= 2 and "setup_s" in [m["name"] for m in mine]
        assert loader.metrics_of_cell(manifest, "per_layer", cell)


def test_one_reader_serves_a_quantity_split_by_what_it_moves(manifest):
    from perfbench import loader
    split = [m["name"] for m in manifest["per_layer"] if "." in m["name"]]
    assert {"device_idle_share.train", "device_idle_share.serve"} <= \
        set(split)
    for name in split:
        reader = loader.load_reader(pb.ROOT, name)
        assert reader.__file__.endswith(name.split(".")[0] + ".py")
    files = {f[:-3] for f in os.listdir(
        os.path.join(pb.ROOT, "perfbench", "layer_metrics"))
        if f.endswith(".py")}
    # every reader is used, and none is a copy of another
    assert files == {m["name"].split(".")[0] if m["name"] not in files
                     else m["name"] for m in manifest["per_layer"]}
    with pytest.raises(FileNotFoundError):
        loader.load_reader(pb.ROOT, "no_such_metric.train")


def test_saturated_serving_cells_are_judged_on_throughput_only(manifest):
    """Tails of a closed loop above capacity swing with the smallest change:
    they are per-layer metrics, with no bound."""
    e2e = {m["name"] for m in manifest["end_to_end"]}
    per_layer = {m["name"] for m in manifest["per_layer"]}
    assert "serve_tokens_per_s" in e2e
    assert {"serve_ttft_ms_p95", "serve_tpot_ms_p95"} <= per_layer - e2e
    cell = [w for w in manifest["workloads"]
            if w["name"] == "mistral7b_serve_chat"][0]
    assert "saturation" in cell["why"]


def test_engine_layout_is_the_configurations_not_the_traffics(manifest):
    from perfbench import loader
    knobs = {"block_size", "token_budget", "decode_burst", "num_blocks",
             "max_concurrent"}
    traffic_dir = os.path.join(pb.ROOT, "perfbench", "traffic")
    for f in os.listdir(traffic_dir):
        assert not knobs & set(loader.load_json(os.path.join(traffic_dir, f)))
    for c in manifest["configs"]:
        serve = loader.load_json(os.path.join(pb.ROOT, c["file"]))[
            "program"].get("serve")
        if serve:
            assert knobs == set(serve["engine"])


def test_files_under_paths_are_named_from_name_characters(manifest):
    for base in manifest["paths"]:
        for dirpath, dirs, files in os.walk(os.path.join(pb.ROOT, base)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(dirpath, f), pb.ROOT)
                assert PATH.match(rel), rel


def test_benchmark_uses_only_public_names_of_the_program():
    import ast
    own = {"self"}                     # the benchmark's own objects
    for dirpath, dirs, files in os.walk(os.path.join(pb.ROOT, "perfbench")):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            if not f.endswith(".py"):
                continue
            src = open(os.path.join(dirpath, f)).read()
            assert not re.search(r"^\s*(from|import) deepspeed_tpu\S*\._",
                                 src, re.M)
            assert not re.search(r"from deepspeed_tpu\S* import _", src)
            for node in ast.walk(ast.parse(src)):
                if isinstance(node, ast.Attribute) and \
                        node.attr.startswith("_") and \
                        not node.attr.startswith("__"):
                    base = node.value
                    while isinstance(base, (ast.Attribute, ast.Subscript,
                                            ast.Call)):
                        base = getattr(base, "value", None) or base.func
                    assert isinstance(base, ast.Name) and base.id in own, \
                        (f, node.lineno, node.attr)
    # the references import nothing of the program
    for name in os.listdir(os.path.join(pb.ROOT, "perfbench", "reference")):
        src = open(os.path.join(pb.ROOT, "perfbench", "reference",
                                name)).read()
        assert "import deepspeed_tpu" not in src
        assert "from deepspeed_tpu" not in src


def test_command_refuses_a_cpu_and_prints_no_result():
    r = subprocess.run(
        [sys.executable, os.path.join(pb.ROOT, "perfbench", "run.py"),
         "--workload", "mistral7b_train_4k", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=pb.ROOT,
        capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "platform 'cpu'" in r.stderr
    assert '"correct"' not in r.stdout


def test_peaks_table_has_its_source():
    peaks = json.load(open(os.path.join(pb.ROOT, "perfbench", "peaks.json")))
    assert "cloud.google.com" in peaks["_source"]
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["ici_bits_per_s"] == 1600e9


def test_flops_per_token_counts_the_head_not_the_embedding():
    from perfbench import flops
    cfg = dict(hidden_size=4096, intermediate_size=14336,
               num_attention_heads=32, num_key_value_heads=8,
               vocab_size=32000, sliding_window=4096)
    layer = 4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    attn = lambda s, w=4096: 2 * 2 * 32 * 128 * flops.mean_keys(s, w)
    fwd = flops.forward_flops_per_token(cfg, 2, 4096)
    assert fwd == 2 * (2 * layer + 4096 * 32000) + 2 * attn(4096)
    assert flops.train_flops_per_token(cfg, 2, 4096) == 3 * fwd
    assert flops.mean_keys(4096, 4096) == 4097 / 2        # causal half
    assert flops.mean_keys(8192, 4096) == (4096 * 4097 / 2
                                           + 4096 * 4096) / 8192
    assert 0.21 < flops.lm_head_share(cfg, 2, 4096) < 0.23
    moe = dict(cfg, num_local_experts=8, num_experts_per_tok=2,
               sliding_window=None)
    per_layer, _ = flops.matmul_params_per_token(moe, 1)
    assert per_layer == (4096 * 4096 * 2 + 2 * 4096 * 1024
                         + 2 * 3 * 4096 * 14336 + 4096 * 8)
