"""A later PR adds a configuration, a traffic mix, an architecture and a
per-layer metric by adding files and appending entries — and edits no file
that is there.  This test does exactly that in a throw-away copy and runs the
result on the CPU at tiny size."""

import hashlib
import json
import os

import pytest

import pb_helpers as pb


def _digest(root):
    out = {}
    for dirpath, dirs, files in os.walk(os.path.join(root, "perfbench")):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            p = os.path.join(dirpath, f)
            out[os.path.relpath(p, root)] = hashlib.sha1(
                open(p, "rb").read()).hexdigest()
    return out


def test_add_config_traffic_arch_and_metric_as_files(tmp_path, capsys):
    root = pb.tiny_root(tmp_path, [("t_serve", "tiny_mistral", "tiny_chat",
                                    "serve")])
    before = _digest(root)
    bench = os.path.join(root, "perfbench")

    # a new architecture: two files that say how it differs (here: not at all)
    for kind in ("models", "reference"):
        with open(os.path.join(bench, kind, "newarch.py"), "w") as f:
            f.write(
                "import os\nfrom perfbench.loader import load_file\n"
                "_base = load_file(os.path.join(os.path.dirname("
                "os.path.abspath(__file__)), 'mistral.py'))\n"
                "globals().update({k: v for k, v in vars(_base).items() "
                "if not k.startswith('__')})\n")
    # a new configuration of it
    config = json.load(open(os.path.join(bench, "configs",
                                         "tiny_mistral.json")))
    config.update(arch="newarch", num_hidden_layers={"serve": 3},
                  num_key_value_heads=4)
    json.dump(config, open(os.path.join(bench, "configs", "new_config.json"),
                           "w"))
    # a new traffic mix: parameters only, read by the one generator
    traffic = json.load(open(os.path.join(bench, "traffic",
                                          "tiny_chat.json")))
    traffic.update(sessions=3,
                   prompt_len={"dist": "fixed", "value": 30, "min": 30,
                               "max": 30},
                   output_len={"dist": "fixed", "value": 5, "min": 5,
                               "max": 5}, pool_size=4)
    json.dump(traffic, open(os.path.join(bench, "traffic", "new_mix.json"),
                            "w"))
    # a new per-layer metric: a small reader of its own
    with open(os.path.join(bench, "layer_metrics", "new_metric.x.py"),
              "w") as f:
        f.write('def read(record):\n    return record.get("completed")\n')

    manifest = pb.read_manifest(root)
    manifest["configs"].append(
        {"name": "new_config", "source": "test",
         "file": "perfbench/configs/new_config.json",
         "reduced": ["num_hidden_layers"], "why": "test"})
    manifest["workloads"].append(
        {"name": "new_cell", "config": "new_config", "traffic": "new_mix",
         "chips": 1, "why": "test"})
    for m in manifest["end_to_end"]:
        if m["name"].startswith("serve"):
            m["workloads"].append("new_cell")
    manifest["per_layer"].append(
        {"name": "new_metric.x", "unit": "req", "better": "higher",
         "source": "program_counter", "layer": "scheduler",
         "moves": "serve_tokens_per_s", "workloads": ["new_cell"]})
    pb.write_manifest(root, manifest)

    after = _digest(root)
    assert {k: v for k, v in after.items() if k in before} == before
    assert len(after) == len(before) + 5

    rc, result, _ = pb.run(root, "new_cell", seed=12, trace=0)
    assert rc == 0 and result["correct"], capsys.readouterr().out
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    rc, result, _ = pb.run(root, "new_cell", seed=12, trace=1)
    assert rc == 0 and result["correct"]
    assert result["metrics"]["new_metric.x"]["value"] == \
        result["attempted"] > 0
    # the cell that was there still runs, and does not report the new metric
    rc, result, _ = pb.run(root, "t_serve", seed=12, trace=1)
    assert rc == 0 and "new_metric.x" not in result["metrics"]


#: a routed configuration's own ``measured_worst``, as far as serving reads it
ROUTED_BLOCK = {
    "serve.logit_gap": {"value": 0.03, "where": "this test"},
    "serve.router_margin": {"value": 0.02, "where": "this test"},
    "serve.routed_two_answer_share": {"value": 0.3, "where": "this test"},
    "serve.routed_left_out_share": {"value": 0.04, "where": "this test"}}


def _add_routed_config(root, name, **changes):
    """A routed configuration of the ``mixtral`` architecture as ONE new file
    and one appended entry; returns the cell's name."""
    bench = os.path.join(root, "perfbench")
    config = json.load(open(os.path.join(bench, "configs",
                                         "tiny_mixtral.json")))
    config.update(num_hidden_layers={"serve": 2}, **changes)
    config["published"] = dict(config["published"],
                               num_hidden_layers={"serve": 2})
    json.dump(config, open(os.path.join(bench, "configs", name + ".json"),
                           "w"))
    manifest = pb.read_manifest(root)
    manifest["configs"].append(
        {"name": name, "source": "test",
         "file": f"perfbench/configs/{name}.json", "reduced": [],
         "why": "test"})
    cell = name + "_cell"
    manifest["workloads"].append(
        {"name": cell, "config": name, "traffic": "tiny_chat", "chips": 1,
         "why": "test"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in m and "t_serve" in m["workloads"]:
            m["workloads"].append(cell)
    pb.write_manifest(root, manifest)
    return cell


def test_add_a_routed_configuration_with_its_own_tolerances_as_files(
        tmp_path, capsys):
    """Not Mistral-shaped, not dense: its widths, what it says was published
    and the worst errors measured for it are data of its own file, and the
    serving comparison it gets is the routed one."""
    root = pb.tiny_root(tmp_path, [("t_serve", "tiny_mistral", "tiny_chat",
                                    "serve")])
    before = _digest(root)
    cell = _add_routed_config(root, "new_routed",
                              measured_worst=dict(ROUTED_BLOCK))
    after = _digest(root)
    assert {k: v for k, v in after.items() if k in before} == before
    assert len(after) == len(before) + 1

    rc, result, _ = pb.run(root, cell, seed=12, trace=0)
    printed = capsys.readouterr().out
    assert rc == 0 and result["correct"], printed
    # its own tolerances, by the jobs' rule, and where they came from
    setup = [l for l in printed.splitlines()
             if l.startswith("INFO setup: ")][-1]
    setup = json.loads(setup.split(": ", 1)[1])
    assert setup["tolerances"] == pytest.approx(
        {"serve.logit_gap": 0.09, "serve.router_margin": 0.06,
         "serve.routed_two_answer_share": 0.9,
         "serve.routed_left_out_share": 0.12})
    assert setup["tolerances_from"] == "perfbench/configs/new_routed.json"
    assert "CHECK serve.routed_two_answer_share" in printed
    assert "CHECK serve.routed_left_out_share" in printed
    assert "must be <= 0.09" in printed
    # the dense cell beside it is compared as before: no routed line
    rc, result, _ = pb.run(root, "t_serve", seed=12, trace=0)
    printed = capsys.readouterr().out
    assert rc == 0 and result["correct"], printed
    assert "serve.routed" not in printed
    assert "must be <= 0.02:" in printed          # 3 x 0.0014 < the floor


@pytest.mark.parametrize("missing", ["serve.router_margin",
                                     "serve.logit_gap",
                                     "serve.routed_two_answer_share",
                                     "serve.routed_left_out_share",
                                     "measured_worst"])
def test_a_configuration_without_its_measured_worst_fails_by_name(
        tmp_path, missing):
    """Never a default: the run stops before anything is built and names the
    key and the file."""
    root = pb.tiny_root(tmp_path, [("t_serve", "tiny_mistral", "tiny_chat",
                                    "serve")])
    block = dict(ROUTED_BLOCK)
    block.pop(missing, None)
    cell = _add_routed_config(
        root, "no_block",
        **({} if missing == "measured_worst" else {"measured_worst": block}))
    if missing == "measured_worst":
        path = os.path.join(root, "perfbench", "configs", "no_block.json")
        config = json.load(open(path))
        del config["measured_worst"]
        json.dump(config, open(path, "w"))
        missing = "serve.logit_gap"
    with pytest.raises(KeyError) as err:
        pb.run(root, cell, seed=12, trace=0)
    assert missing in str(err.value)
    assert "perfbench/configs/no_block.json" in str(err.value)


def test_add_one_chips_share_of_a_deployment_as_a_file(tmp_path):
    """A configuration that is ONE CHIP'S SHARE of a deployment (its experts
    and its slice of the vocabulary cut beside depth, a ``share`` block) is
    one new file and one appended entry: it passes the lint and every part
    it names resolves.  It is not run: the program has no expert layer that
    is told which experts it holds yet (the `model_config` PR brings it)."""
    from perfbench import loader
    from test_perfbench_manifest import lint_config
    root = pb.tiny_root(tmp_path, [("t_serve", "tiny_mistral", "tiny_chat",
                                    "serve")])
    before = _digest(root)
    bench = os.path.join(root, "perfbench")
    config = json.load(open(os.path.join(bench, "configs",
                                         "tiny_mixtral.json")))
    # a deployment of 32 experts and 2048 vocabulary rows a layer over 4
    # chips, depth 8; this file is chip 1's share, cut to depth 4
    published = dict(config["published"], num_local_experts=32,
                     vocab_size=2048, num_hidden_layers=8)
    cut = ["num_hidden_layers", "num_local_experts", "vocab_size"]
    config.update(
        published=published, num_local_experts=8, vocab_size=256,
        num_hidden_layers={"serve": 4}, reduced={k: "test" for k in cut},
        share={"chips_sharing_a_layer": 4, "this_chip": 1,
               "how": "32 routed experts and the vocabulary's rows divided "
                      "evenly; attention and the router whole on every chip"})
    json.dump(config, open(os.path.join(bench, "configs", "new_share.json"),
                           "w"))
    manifest = pb.read_manifest(root)
    manifest["configs"].append(
        {"name": "new_share", "source": "test",
         "file": "perfbench/configs/new_share.json", "reduced": cut,
         "why": "test"})
    manifest["workloads"].append(
        {"name": "new_share_cell", "config": "new_share",
         "traffic": "tiny_chat", "chips": 1, "why": "test"})
    pb.write_manifest(root, manifest)
    after = _digest(root)
    assert {k: v for k, v in after.items() if k in before} == before
    assert len(after) == len(before) + 1

    manifest = loader.load_manifest(root)
    cell = loader.find(manifest["workloads"], "new_share_cell", "workload")
    entry = loader.find(manifest["configs"], cell["config"], "config")
    body = loader.load_json(os.path.join(root, entry["file"]))
    assert lint_config(body, entry["reduced"]) == []
    # one expert fewer a chip, a sixteenth of the vocabulary, depth 3: faults
    for change in ({"num_local_experts": 7}, {"vocab_size": 128},
                   {"num_hidden_layers": {"serve": 3}}):
        assert lint_config(dict(body, **change), entry["reduced"]), change
    arch = loader.load_part(root, "models", body["arch"])
    assert loader.load_part(root, "reference", body["arch"]).moe_block
    sizes = arch.reference_sizes(body, "serve")
    # the traffic draws its ids from the slice; depth is the job's
    assert sizes["vocab_size"] == 256 and sizes["num_hidden_layers"] == 4
    assert os.path.isfile(loader.part_path(root, "traffic", cell["traffic"],
                                           "json"))
