"""Every job end to end at tiny size on the CPU, through the tests' door."""

import json

import pytest

import pb_helpers as pb

CELLS = [("t_train", "tiny_mistral", "tiny_train", "train"),
         ("t_serve", "tiny_mistral", "tiny_chat", "serve"),
         ("x_train", "tiny_mixtral", "tiny_train", "train"),
         ("x_serve", "tiny_mixtral", "tiny_chat", "serve")]
E2E = {"train": {"train_tokens_per_s_per_chip", "setup_s"},
       "serve": {"serve_tokens_per_s", "setup_s"}}
SPAN_METRICS = {"train": {"train_host_ms_per_step"},
                "serve": {"serve_step_ms_p50", "serve_ttft_ms_p95",
                          "serve_tpot_ms_p95", "serve_queue_ms_p95"}}


@pytest.mark.parametrize("cell", CELLS, ids=[c[0] for c in CELLS])
@pytest.mark.parametrize("trace", [0, 1])
def test_job_runs_end_to_end_at_tiny_size(tmp_path, cell, trace, capsys):
    name, config, _, job = cell
    root = pb.tiny_root(tmp_path, [cell])
    rc, result, last = pb.run(root, name, seed=3_000_000_019, trace=trace)
    printed = capsys.readouterr().out
    assert rc == 0, printed
    assert json.loads(last) == result
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True, printed
    assert result["attempted"] > 0 and result["failed"] == 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(result["device"])
    if trace == 0:
        assert set(result["metrics"]) == E2E[job]
        assert "breakdown" not in result
    else:
        # the CPU has no device plane: span and counter metrics only, and
        # the readers that find no trace return nothing
        assert SPAN_METRICS[job] <= set(result["metrics"])
        assert not any(m.startswith(("mosaic_share", "device_idle_share"))
                       for m in result["metrics"])
    for m in result["metrics"].values():
        assert m["value"] >= 0 and m["unit"]
    # every check printed its observed value and its tolerance
    checks = [l for l in printed.splitlines() if l.startswith("CHECK ")]
    assert len(checks) >= 6 and all("must be" in l for l in checks)
    assert "compilations_in_window: observed 0" in printed
    # the tolerances a run used, and the file they came from, once
    setup = [l for l in printed.splitlines() if l.startswith("INFO setup: ")]
    assert len(setup) == 1
    setup = json.loads(setup[0].split(": ", 1)[1])
    assert setup["tolerances_from"] == f"perfbench/configs/{config}.json"
    assert all(f"must be <= {tol:g}" in printed
               for key, tol in setup["tolerances"].items()
               if key != "serve.router_margin")
    # only a reference that states its routing is compared by the routed rule
    routed = job == "serve" and config == "tiny_mixtral"
    assert ("CHECK serve.routed_left_out_share" in printed) == routed
    assert ("serve.router_margin" in setup["tolerances"]) == routed
    # the window names its slowest host calls, so that a stall explains itself
    window = [l for l in printed.splitlines()
              if l.startswith("INFO window: ")][-1]
    slowest = json.loads(window.split(": ", 1)[1])["slowest_host_calls"]
    assert len(slowest) == 3 and slowest[0][1] >= slowest[2][1] > 0
    assert all(at >= 0 for _, _, at in slowest)


def test_serving_tails_are_over_the_windows_own_requests(tmp_path, capsys):
    """Time to first token over the requests SUBMITTED inside the window (a
    request of the ramp carries set-up waits), time per output token over
    every request that streamed there, in flight at the close or not."""
    root = pb.tiny_root(tmp_path, [CELLS[1]])
    rc, result, _ = pb.run(root, "t_serve", seed=7, seconds=2.0, trace=1)
    assert rc == 0 and result["correct"]
    window = [l for l in capsys.readouterr().out.splitlines()
              if l.startswith("INFO window: ")][-1]
    w = json.loads(window.split(": ", 1)[1])
    assert w["n_ttft"] == w["submitted_in_window"] > 0
    # every submission of the window either follows a completion in it or
    # fills a session the ramp left free; none is the ramp's own
    assert w["submitted_in_window"] <= w["completed"] + 4
    assert 0 <= w["still_waiting_for_first_token"] <= 4
    # in-flight requests count: more samples than completed requests alone
    assert w["n_tpot"] >= w["completed"] - 1 and w["n_tpot"] > 0
    assert w["ttft_ms_p95"] >= w["ttft_ms_p50"] > 0
    assert result["metrics"]["serve_ttft_ms_p95"]["value"] == \
        pytest.approx(w["ttft_ms_p95"])


def test_same_seed_same_inputs_other_seed_same_work_other_tokens():
    from perfbench import loader, traffic_gen
    traffic = loader.load_json(loader.part_path(
        pb.ROOT, "traffic", "chat_closed64", "json"))
    n = traffic["sessions"]

    def waves(seed, count=3):
        stream = traffic_gen.RequestStream(traffic, 32000, seed)
        return [[stream.next(s) for s in range(n)] for _ in range(count)]

    a, b, c = waves(11), waves(11), waves(2**32 - 1)
    assert a == b
    shape = lambda wave: sorted((len(p), m) for p, m in wave)
    for wa, wc in zip(a, c):
        # the same work in the same order, wave by wave
        assert [(len(p), m) for p, m in wa] == [(len(p), m) for p, m in wc]
        assert len(set(shape(wa))) > 32              # and a real mix
    assert a[0][0][0] != c[0][0][0]                  # other token ids
    lo, hi = traffic["prompt_len"]["min"], traffic["prompt_len"]["max"]
    pairs = [r for w in a for r in w]
    assert all(lo <= len(p) <= hi and traffic["output_len"]["min"] <= m
               <= traffic["output_len"]["max"] for p, m in pairs)
    assert max(len(p) + m for p, m in pairs) < 4096
    checks = traffic_gen.check_requests(traffic, 32000, 5)
    assert [len(p) for p in checks] == [hi, 512, lo]
