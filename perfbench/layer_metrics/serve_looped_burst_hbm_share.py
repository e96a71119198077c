"""Per-layer metric ``serve_looped_burst_hbm_share``: how near one iteration
of a LOOPED model's decode burst comes to the time the chip's memory needs for
what the iteration MUST move.

A decode iteration computes one row a sequence, so it is bound by bytes.  It
must read every layer's weights once a PASS (the same weights,
``total_ut_steps`` times: nothing of 4.9 GB stays on the chip between two
passes), the head's matrix once, and the cached keys and values of every live
sequence in every entry (one a (pass, layer) pair).  From the configuration's
sizes, not from the program; the embedding's few rows, the norms' inputs and
the rows written are left out (under 0.1 %), so the share reads a little LOW.
The time is the accepted ``serve_burst_iteration_device_ms`` reader's.
"""

import os

from perfbench.loader import load_file

_here = os.path.dirname(os.path.abspath(__file__))
_iteration = load_file(os.path.join(_here,
                                    "serve_burst_iteration_device_ms.py"))
_experts = load_file(os.path.join(_here, "serve_moe_experts_ms_per_step.py"))
BYTES = 2                           # bfloat16 weights and cache


def layer_params(sizes):
    """One layer: q, k, v and o, the SwiGLU's three, four norms."""
    d, dh = sizes["hidden_size"], sizes["head_dim"]
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    return 2 * d * heads * dh + 2 * d * kv * dh \
        + 3 * d * sizes["intermediate_size"] + 4 * d


def cache_token_bytes(sizes):
    """K and V of one token in every (pass, layer) entry."""
    return sizes["total_ut_steps"] * sizes["depth"] * 2 \
        * sizes["num_key_value_heads"] * sizes["head_dim"] * BYTES


def must_move_bytes(sizes, live_tokens):
    """Bytes ONE decode iteration must move with ``live_tokens`` cached
    tokens under its rows: passes x the layers' weights, the head, the cached
    tokens x a token's bytes."""
    weights = sizes["total_ut_steps"] * sizes["depth"] * layer_params(sizes)
    head = sizes["hidden_size"] * sizes["vocab_size"]
    return BYTES * (weights + head) \
        + live_tokens * cache_token_bytes(sizes)


def burst_keys(context_tokens, live_tokens, k):
    """The cached tokens under the rows of a burst's ``k`` iterations,
    summed: ``context_tokens`` is the running sequences' context once the
    burst is through, ``live_tokens`` its rows (sequences x ``k``); iteration
    ``j`` of a sequence reads the context it started with and ``j + 1``."""
    seqs = live_tokens // k
    return k * (context_tokens - live_tokens) + seqs * k * (k + 1) // 2


def read(record):
    """Over the WHOLE bursts of the traced stretch: (the bytes their
    iterations must move / ``hbm_bytes_per_s``) over their device time, in %;
    the device time is ``serve_burst_iteration_device_ms`` x the iterations.
    None without a whole burst, the peaks, or a configuration that states
    ``total_ut_steps``."""
    from perfbench import step_trace
    ms = _iteration.read(record)
    config, peaks = _experts.traced_config(record), record.get("peaks")
    if not ms or not config or not peaks or "total_ut_steps" not in config:
        return None
    t = step_trace.traced(record)
    bursts = [r for r in step_trace.whole(t, t["kinds"][1])
              if "context_tokens" in r["counts"]]
    iterations = sum(r["burst_k"] for r in bursts)
    if not iterations:
        return None
    moved = sum(
        r["burst_k"] * must_move_bytes(config, 0)
        + burst_keys(int(r["counts"]["context_tokens"]),
                     int(r["counts"]["live_tokens"]), r["burst_k"])
        * cache_token_bytes(config) for r in bursts)
    return 100.0 * moved / peaks["hbm_bytes_per_s"] / (ms * iterations / 1e3)
