"""The reader of ``train_expert_padded_call_share`` (ISSUE 50) on written
traces: over the traced ``ds:train.micro`` spans, the layer-calls that ran in
per-expert padded blocks (``expert_padded_calls``) over the configuration's
depth times the micro-steps the counts cover; a program whose spans lack the
count (the parent commit's), a cell with no expert layer, an untraced run and
a run with no trace file give nothing and raise nothing."""

import copy

import pytest

import pb_helpers as pb
from perfbench import loader
from test_perfbench_smallthinker import (  # noqa: F401  (a fixture)
    CELL, CONFIG, PEAKS, ROUTED, span, traced_root)

METRIC = "train_expert_padded_call_share"
RECORD = {"trace": {"busy_s": 1.0}, "peaks": PEAKS}


def planes(*padded):
    """``ROUTED`` with ``expert_padded_calls`` on the spans that carry counts
    (one micro-step's, then two micro-steps'); None: the count left off."""
    out = copy.deepcopy(ROUTED)
    carrying = [e for e in out["/host:CPU"]["python3"]
                if "micro_steps_covered" in e[3]]
    assert len(carrying) == len(padded) == 2
    for event, n in zip(carrying, padded):
        if n is not None:
            event[3]["expert_padded_calls"] = n
    return out


@pytest.fixture
def read():
    return loader.load_reader(pb.ROOT, METRIC).read


@pytest.mark.parametrize("padded, want", [
    ((4, 8), 100.0),            # every layer-call of three micro-steps
    ((4, 5), 75.0),             # three of the last eight took the worst case
    ((0, 0), 0.0),              # none fitted: the metric says so, not None
], ids=["every_call", "three_on_the_worst_case", "no_call"])
def test_reads_the_padded_calls_over_the_layer_calls(
        read, traced_root, padded, want):
    assert read(RECORD) is None                      # no trace file
    traced_root(planes(*padded))
    assert read({"trace": None, "peaks": PEAKS}) is None    # an untraced run
    # depth 4 (num_hidden_layers.train) x 3 counted micro-steps
    assert read(RECORD) == pytest.approx(want)


@pytest.mark.parametrize("trace", [
    planes(None, None),
    {**ROUTED, "/host:CPU": {"python3": [
        span("pb:traced", 0, 1000),
        span("ds:train.micro", 0, 10, step=4, micro_step=4),
        span("ds:train.apply", 10, 20, step=4, micro_step=4)]}},
], ids=["the_parent", "no_counts"])
def test_gives_nothing_without_the_count(read, traced_root, trace):
    traced_root(trace)
    assert read(RECORD) is None


def test_gives_nothing_where_the_configuration_states_no_training_depth(
        read, traced_root, monkeypatch):
    traced_root(planes(4, 8))
    real = loader.load_json
    monkeypatch.setattr(loader, "load_json", lambda path: {
        **real(path), "num_hidden_layers": {"serve": 16}}
        if path.endswith(CONFIG + ".json") else real(path))
    assert read(RECORD) is None


def test_the_share_of_the_counts():
    share = loader.load_reader(pb.ROOT, METRIC).share
    assert share({"expert_padded_calls": 80, "micro_steps_covered": 20},
                 4) == 100.0
    assert share({"expert_padded_calls": 0, "micro_steps_covered": 0},
                 4) is None


def test_the_manifest_lists_it_for_the_routed_training_cell():
    manifest = pb.read_manifest(pb.ROOT)
    entry = loader.find(manifest["per_layer"], METRIC, "metric")
    assert entry == {
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "expert layer",
        "moves": "train_tokens_per_s_per_chip", "workloads": [CELL]}
