"""A later PR adds a configuration, a traffic mix, an architecture and a
per-layer metric by adding files and appending entries — and edits no file
that is there.  This test does exactly that in a throw-away copy and runs the
result on the CPU at tiny size."""

import hashlib
import json
import os

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
