"""``perfbench/benchmark_additions_check.py`` in a temporary git repository:
what a later PR may do to the benchmark (add files, append entries) reads
clean, and each thing it may not do is listed and exits 1."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import pb_helpers as pb

TOOL = os.path.join(pb.ROOT, "perfbench", "benchmark_additions_check.py")

BASE = {
    "command": ["python3", "perfbench/run.py"], "paths": ["perfbench"],
    "run_seconds": 30,
    "configs": [{"name": "a", "source": "s", "file": "perfbench/a.json",
                 "reduced": [], "why": "w"}],
    "workloads": [{"name": "a_serve", "config": "a", "traffic": "chat",
                   "chips": 1, "why": "w"}],
    "end_to_end": [
        {"name": "serve_tokens_per_s", "unit": "tokens/s", "better": "higher",
         "bound": 0.02, "source": "host_clock", "workloads": ["a_serve"]},
        {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
         "source": "host_clock"}],
    "per_layer": [
        {"name": "step_ms", "unit": "ms", "better": "lower",
         "source": "program_span", "layer": "step",
         "moves": "serve_tokens_per_s", "workloads": ["a_serve"]}],
}


def git(repo, *args):
    subprocess.run(["git", "-c", "user.name=t", "-c", "user.email=t@t",
                    "-c", "commit.gpgsign=false", *args], cwd=repo,
                   check=True, capture_output=True)


def write(repo, path, text):
    full = os.path.join(repo, path)
    os.makedirs(os.path.dirname(full), exist_ok=True)
    with open(full, "w") as f:
        f.write(text)


@pytest.fixture
def repo(tmp_path):
    if not shutil.which("git"):
        pytest.skip("no git here")
    repo = str(tmp_path / "repo")
    os.makedirs(repo)
    git(repo, "init", "-q")
    write(repo, "BENCHMARK.json", json.dumps(BASE, indent=1))
    write(repo, "perfbench/serve_trace.py", "COUNTS = ('grid_pages',)\n")
    write(repo, "perfbench/a.json", "{}\n")
    write(repo, "deepspeed_tpu/engine.py", "x = 1\n")
    git(repo, "add", "-A")
    git(repo, "commit", "-q", "-m", "base")
    return repo


def check(repo, *refs):
    r = subprocess.run([sys.executable, TOOL, *refs], cwd=repo,
                       capture_output=True, text=True)
    return r.returncode, r.stdout


def add_a_cell(repo):
    """What a `model_config` PR does: new files, entries appended, the new
    cell's name appended to the metrics it reports; the manifest re-wrapped."""
    m = json.loads(json.dumps(BASE))
    m["configs"].append({"name": "b", "source": "s",
                         "file": "perfbench/b.json", "reduced": [],
                         "why": "w"})
    m["workloads"].append({"name": "b_serve", "config": "b",
                           "traffic": "chat", "chips": 1, "why": "w"})
    m["end_to_end"][0]["workloads"].append("b_serve")
    m["per_layer"][0]["workloads"].append("b_serve")
    m["per_layer"].append(
        {"name": "new_ms", "unit": "ms", "better": "lower",
         "source": "device_trace", "layer": "step",
         "moves": "serve_tokens_per_s", "workloads": ["b_serve"]})
    write(repo, "BENCHMARK.json", json.dumps(m))          # one line now
    write(repo, "perfbench/b.json", "{}\n")
    write(repo, "perfbench/layer_metrics/new_ms.py",
          "def read(record):\n    return None\n")
    write(repo, "deepspeed_tpu/engine.py", "x = 2\n")      # the program's
    return m


def test_added_files_and_appended_entries_read_clean(repo):
    add_a_cell(repo)
    rc, out = check(repo, "HEAD")                  # the working tree
    assert rc == 0, out
    assert "1 configs, 1 workloads, 1 per_layer" in out
    git(repo, "add", "-A")
    git(repo, "commit", "-q", "-m", "adds a cell")
    assert check(repo, "HEAD^", "HEAD")[0] == 0    # two refs
    assert check(repo, "HEAD")[0] == 0             # nothing since


def _edit_one_character(repo, m):
    write(repo, "perfbench/serve_trace.py", "COUNTS = ('grid_page',)\n")


def _delete_a_file(repo, m):
    os.remove(os.path.join(repo, "perfbench", "a.json"))


def _rename_a_file(repo, m):
    os.rename(os.path.join(repo, "perfbench", "serve_trace.py"),
              os.path.join(repo, "perfbench", "serve_trace2.py"))
    git(repo, "add", "-A")


def _bound(repo, m):
    m["end_to_end"][0]["bound"] = 0.05


def _run_seconds(repo, m):
    m["run_seconds"] = 10


def _retire_a_metric(repo, m):
    del m["per_layer"][0]


def _take_a_cell_out_of_a_metric(repo, m):
    m["per_layer"][0]["workloads"] = ["b_serve"]


def _new_entry_first(repo, m):
    m["per_layer"].insert(0, m["per_layer"].pop())


def _new_end_to_end_metric(repo, m):
    m["end_to_end"].append({"name": "ttft_ms", "unit": "ms",
                            "better": "lower", "bound": 0.05,
                            "source": "host_clock", "workloads": ["b_serve"]})


def _a_cells_why(repo, m):
    m["workloads"][0]["why"] = "another"


@pytest.mark.parametrize("change,named", [
    (_edit_one_character, "perfbench/serve_trace.py: modified"),
    (_delete_a_file, "perfbench/a.json"),    # "deleted", or "renamed" to b
    (_rename_a_file, "perfbench/serve_trace.py"),
    (_bound, "'serve_tokens_per_s' changed in bound: bound 0.02 -> 0.05"),
    (_run_seconds, "run_seconds: 30 -> 10"),
    (_retire_a_metric, "per_layer: entries removed: ['step_ms']"),
    (_take_a_cell_out_of_a_metric, "'step_ms' changed in workloads"),
    (_new_entry_first, "append at the end"),
    (_new_end_to_end_metric, "end_to_end: entries added: ['ttft_ms']"),
    (_a_cells_why, "workloads: entry 'a_serve' changed in why"),
], ids=lambda x: x.__name__.strip("_") if callable(x) else None)
def test_each_change_to_what_was_there_is_listed_and_fails(repo, change,
                                                           named):
    """Beside a sound addition, ONE change to what the benchmark had."""
    m = add_a_cell(repo)
    change(repo, m)
    write(repo, "BENCHMARK.json", json.dumps(m))
    rc, out = check(repo, "HEAD")
    assert rc == 1 and named in out, out
    assert "1 change(s)" in out, out
    git(repo, "add", "-A")
    git(repo, "commit", "-q", "-m", "change")
    rc, again = check(repo, "HEAD^", "HEAD")
    assert rc == 1 and named in again, again
