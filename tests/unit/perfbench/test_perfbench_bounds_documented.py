"""``PERF.md`` section 2's table and ``BENCHMARK.json`` name the same bound for
every end-to-end metric (ISSUE 53: the training bound moved, and the table is
what the next writer reads first)."""

import os
import re

import pytest

import pb_helpers as pb

METRICS = ("train_tokens_per_s_per_chip", "serve_tokens_per_s", "setup_s")


def _table_bounds():
    """``{metric: bound}`` of the table under ``## 2.``: a row's first cell
    is the metric's name in backquotes, its last begins with the bound."""
    text = open(os.path.join(pb.ROOT, "PERF.md")).read()
    section = text.split("\n## 2.", 1)[1].split("\n## 3.", 1)[0]
    bounds = {}
    for line in section.splitlines():
        cells = [c.strip() for c in line.strip().strip("|").split("|")]
        name = re.fullmatch(r"`(\w+)`", cells[0])
        number = re.match(r"\d+(\.\d+)?", cells[-1])
        if line.startswith("|") and name and number:
            bounds[name.group(1)] = float(number.group(0))
    return bounds


def test_the_table_lists_exactly_the_manifests_end_to_end_metrics():
    manifest = pb.read_manifest(pb.ROOT)
    assert set(_table_bounds()) == {m["name"] for m in manifest["end_to_end"]}
    assert set(METRICS) == set(_table_bounds())


@pytest.mark.parametrize("metric", METRICS)
def test_the_table_and_the_manifest_name_the_same_bound(metric):
    manifest = pb.read_manifest(pb.ROOT)
    entry = next(m for m in manifest["end_to_end"] if m["name"] == metric)
    assert _table_bounds()[metric] == entry["bound"]
