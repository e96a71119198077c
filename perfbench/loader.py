"""Find the benchmark's parts by name: no table in code.

Every configuration, traffic mix, job, architecture, reference and per-layer
metric is a file of its own under ``<root>/perfbench/<kind>/``; the name in
``BENCHMARK.json`` (or in a configuration's ``arch`` / a traffic mix's
``job``) is the file's name.  A later PR adds files and appends entries.
"""

import hashlib
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_file(path):
    """Import a python file by path under a name made from the path, once."""
    path = os.path.abspath(path)
    name = "perfbench_file_" + hashlib.sha1(path.encode()).hexdigest()[:16]
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.isfile(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def part_path(root, kind, name, ext):
    return os.path.join(root, "perfbench", kind, f"{name}.{ext}")


def load_part(root, kind, name):
    """The module ``<root>/perfbench/<kind>/<name>.py``."""
    return load_file(part_path(root, kind, name, "py"))


def load_reader(root, metric):
    """The reader of a per-layer metric: ``layer_metrics/<metric>.py``, or,
    for a quantity split by what it moves (``device_idle_share.train``,
    ``device_idle_share.serve``), the one reader of the quantity,
    ``layer_metrics/device_idle_share.py``.  Layer, unit and ``moves`` are
    BENCHMARK.json's to state."""
    for name in (metric, metric.split(".", 1)[0]):
        if os.path.isfile(part_path(root, "layer_metrics", name, "py")):
            return load_part(root, "layer_metrics", name)
    raise FileNotFoundError(part_path(root, "layer_metrics", metric, "py"))


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_manifest(root=ROOT):
    return load_json(os.path.join(root, "BENCHMARK.json"))


def find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"{what} {name!r} is not in BENCHMARK.json "
                   f"(has: {[e['name'] for e in entries]})")


def metrics_of_cell(manifest, section, cell_name):
    """The metrics of ``section`` that the cell reports: those with no
    ``workloads`` key and those that list the cell."""
    return [m for m in manifest[section]
            if "workloads" not in m or cell_name in m["workloads"]]
