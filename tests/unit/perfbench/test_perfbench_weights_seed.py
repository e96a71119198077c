"""What ``--seed`` decides.  A configuration whose file states a
``weights_seed`` makes its weights from that and its inputs from ``--seed``
(``longcat_flash_1chip``: the cell whose WORK moved with the weights' draw
and stood still once the draw did; PERF.md section 2, PR 53); every other
configuration makes both from ``--seed``."""

import json
import os

import numpy as np
import pytest

import pb_helpers as pb

#: the configurations of BENCHMARK.json that state one, and the cell of each
#: (``smallthinker_21b_1chip`` was tried and does NOT: its inputs move the
#: work as far as its weights do, PERF.md section 2)
STATED = {"longcat_flash_1chip": "longcat_flash_serve_agent"}


def _configs():
    return [(c["name"], c["file"]) for c in pb.read_manifest(pb.ROOT)["configs"]]


@pytest.mark.parametrize("config,seed,want", [
    ({"weights_seed": 5300001106}, 7, 5300001106),
    ({"weights_seed": 5300001106}, 2**31 + 5, 5300001106),
    ({}, 7, 7),
    ({}, 2**31 + 5, 2**31 + 5)])
def test_the_weights_seed_is_the_files_or_the_runs(config, seed, want):
    from perfbench import harness
    ctx = harness.Context(config=config, seed=seed)
    assert harness.weights_seed(ctx) == want


@pytest.mark.parametrize("name,file", _configs(), ids=[n for n, _ in _configs()])
def test_only_the_configuration_it_steadied_states_one(name, file):
    body = json.load(open(os.path.join(pb.ROOT, file)))
    if name in STATED:
        assert isinstance(body["weights_seed"], int)
        assert 0 <= body["weights_seed"] < 2**63
        # the reason, beside every other thing the file assumes
        assert "--seed" in body["assumed"]["weights_seed"]
    else:
        assert "weights_seed" not in body


def test_no_tiny_preset_states_one():
    folder = os.path.join(pb.ROOT, "perfbench", "configs")
    for f in sorted(os.listdir(folder)):
        if f.startswith("tiny_"):
            assert "weights_seed" not in json.load(
                open(os.path.join(folder, f))), f


class _Stop(Exception):
    pass


def _keys_of_runs(tmp_path, monkeypatch, cell, stated, seeds):
    """The PRNG key each run of ``cell`` hands to what makes the weights,
    with the configuration's file stating ``stated`` (or nothing)."""
    from perfbench import loader, weights
    root = pb.tiny_root(tmp_path, [cell])
    path = os.path.join(root, "perfbench", "configs", cell[1] + ".json")
    body = json.load(open(path))
    if stated is not None:
        body["weights_seed"] = stated
        json.dump(body, open(path, "w"))
    seen = []

    def stop(key):
        seen.append(np.asarray(key).tolist())
        raise _Stop

    if cell[3] == "serve":
        monkeypatch.setattr(weights, "seeded_weights",
                            lambda shapes, key, **kw: stop(key))
    else:
        train = loader.load_part(root, "jobs", "train")
        monkeypatch.setattr(train, "_build_engine",
                            lambda ctx, model, rules, key, sample: stop(key))
    for seed in seeds:
        with pytest.raises(_Stop):
            pb.run(root, cell[0], seed=seed)
    return seen


@pytest.mark.parametrize("cell", [
    ("t_serve", "tiny_mistral", "tiny_chat", "serve"),
    ("t_train", "tiny_mistral", "tiny_train", "train")],
    ids=["serve", "train"])
def test_a_stated_weights_seed_makes_the_weights_of_every_seed(
        tmp_path, monkeypatch, cell):
    from perfbench import harness
    seeds = (3, 2**31 + 11)
    stated = _keys_of_runs(tmp_path / "a", monkeypatch, cell, 5300000501,
                           seeds)
    want = np.asarray(harness.fold_seed(5300000501)).tolist()
    assert stated == [want, want]
    plain = _keys_of_runs(tmp_path / "b", monkeypatch, cell, None, seeds)
    assert plain == [np.asarray(harness.fold_seed(s)).tolist() for s in seeds]
    assert plain[0] != plain[1]


def test_the_inputs_still_come_from_the_runs_seed():
    """Token ids of the serving requests and of the check prompts, and the
    training batches, are drawn from ``--seed`` whatever the file states."""
    from perfbench import loader, traffic_gen
    traffic = loader.load_json(loader.part_path(
        pb.ROOT, "traffic", "agent_closed32", "json"))
    a = traffic_gen.RequestStream(traffic, 16384, 5).next(0)
    b = traffic_gen.RequestStream(traffic, 16384, 6).next(0)
    assert len(a[0]) == len(b[0]) and a[1] == b[1] and a[0] != b[0]
    assert traffic_gen.check_requests(traffic, 16384, 5) != \
        traffic_gen.check_requests(traffic, 16384, 6)
