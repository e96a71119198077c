"""Per-layer metric ``serve_gdn_chunk_roofline_share``: how near the delta
rule's CHUNK form (the scope ``ds.gdn_chunk`` inside ``ds.gdn_rule``: the runs
of several tokens, matrix products over chunks of 64) comes to the chip's
roofline.

What the FORM must do whatever implements it, a token of a Gated DeltaNet
layer (``rule_chunk_tokens`` is summed over those layers by the count
itself), with ``C`` = 64 the chunk, ``dk`` / ``dv`` the key and value head
sizes, a value head:

* the products as ``deepspeed_tpu/models/qwen3_next.py`` writes them, a
  chunk: ``(beta k) k^T``, ``w = T (beta k e^G)`` and ``q k^T`` (``2 C^2 dk``
  each), ``u = T (beta v)`` and ``tril(q k^T) v'`` (``2 C^2 dv`` each), ``w S``,
  ``(q e^G) S`` and the state's update ``k^T v'`` (``2 C dk dv`` each), and
  ``T`` by forward substitution (``2 C^3 / 3``); divided by ``C``;
* the bytes: q and k of its key head's share, v in, the output out, g and
  beta, float32 (the rule's type): the state itself is read and written a
  RUN, not a token, and is left out, so the share reads a little LOW.

The floor is ``max(operations / the bf16 matrix peak, bytes / HBM
bandwidth)`` (``peaks.json`` lists no float32 matrix peak: products that
read float32 at full precision cost several bfloat16 passes, so the share
reads LOW against what bounds them in truth) and cannot pass 100 %.
"""

import os

from perfbench.loader import load_file

_experts = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "serve_moe_experts_ms_per_step.py"))
CHUNK = 64
BYTES = 4                       # the rule's inputs and output: float32


def must_compute_flops_a_token(heads, dk, dv, chunk=CHUNK):
    """Operations of the chunk form's products a token of ONE layer."""
    a_chunk = 2 * chunk * chunk * (3 * dk + 2 * dv) \
        + 3 * 2 * chunk * dk * dv + 2 * chunk ** 3 / 3
    return heads * a_chunk / chunk


def must_move_bytes_a_token(key_heads, heads, dk, dv):
    """Bytes a token of ONE layer brings in and takes out."""
    return (2 * key_heads * dk + 2 * heads * dv + 2 * heads) * BYTES


def read(record):
    """Over the traced steps that carry ``rule_chunk_tokens``: max(operations
    / bfloat16 peak, bytes / HBM bandwidth) of those tokens over the measured
    time under ``ds.gdn_chunk`` of all the traced steps, in %.  None without
    the scope, the count or the configuration's widths."""
    got = _experts.scope_ms(record, "SCOPE_GDN_CHUNK")
    config, peaks = _experts.traced_config(record), record.get("peaks")
    if not got or not config or not peaks \
            or "linear_num_value_heads" not in config:
        return None
    ms, steps = got
    tokens = sum(int(c["rule_chunk_tokens"]) for c in steps
                 if "rule_chunk_tokens" in c)
    if not tokens or not ms:
        return None
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    floor_s = tokens * max(
        must_compute_flops_a_token(hv, dk, dv) / peaks["bf16_flops_per_s"],
        must_move_bytes_a_token(hk, hv, dk, dv) / peaks["hbm_bytes_per_s"])
    return 100.0 * floor_s / (ms / 1e3)
