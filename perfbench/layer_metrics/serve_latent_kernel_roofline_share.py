"""Per-layer metric ``serve_latent_kernel_roofline_share``: how near the paged
LATENT kernel (``ds_paged_latent``, multi-head latent attention in its
absorbed form) comes to the chip's roofline.

What its calls MUST move and compute, from the steps' own counts (not what
the kernel happens to move): every page load brings ``block_size`` latent
rows of ``kv_lora_rank + qk_rope_head_dim`` values ONCE, for the scores and
the values both; every live row's ``num_attention_heads`` queries come in as
long as a latent row and go out ``kv_lora_rank`` long; every (row, key) pair
costs a head one product of a latent row's length (the score) and one of
``kv_lora_rank`` (the value).  2 bytes an element; the widths are those of
the configuration whose trace is read; the peaks are ``peaks.json``'s.
"""

import os

from perfbench.loader import load_file

_experts = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "serve_moe_experts_ms_per_step.py"))
BYTES = 2                       # bfloat16
KERNEL = "ds_paged_latent"


def must_move_bytes(page_loads, rows, block_size, heads, rank, rope):
    """Bytes the kernel's calls must move: ``page_loads`` pages of
    ``block_size`` latent rows, and ``rows`` buffer rows' queries in and
    latent outputs out, ``heads`` each."""
    return (page_loads * block_size * (rank + rope)
            + rows * heads * (2 * rank + rope)) * BYTES


def must_compute_flops(keys, heads, rank, rope):
    """Operations of ``keys`` (row, key) pairs: a score over the latent row
    and a value over its first ``rank``, for each of ``heads``."""
    return keys * heads * (2 * rank + rope) * 2


def read(record):
    """Over the traced steps: sum of max(bytes / HBM bandwidth, flops / peak)
    over the measured time inside ``ds_paged_latent`` of the first chip, in
    %.  ``grid_pages`` and ``live_tokens`` of a step are ONE layer's call
    (every layer reads alike), ``latent_keys`` is summed over the layers.
    None without the kernel, the counts or the configuration's widths."""
    from perfbench import program_trace, serve_trace
    s, t = program_trace.summary(record), serve_trace.traced(record)
    config, peaks = _experts.traced_config(record), record.get("peaks")
    if not s or not t or not config or not peaks:
        return None
    ms = sum(v for k, v in s.get("device_ms_by_kernel", {}).items()
             if k.startswith(KERNEL))
    steps = [c for c in t["steps"] if "latent_keys" in c]
    if not ms or not steps:
        return None
    try:
        heads, rank, rope = (config["num_attention_heads"],
                             config["kv_lora_rank"],
                             config["qk_rope_head_dim"])
    except KeyError:
        return None
    floor_s = sum(max(
        must_move_bytes(
            config["depth"] * int(c["grid_pages"]),
            config["depth"] * int(c.get("absorbed_rows", c["live_tokens"])),
            int(c["block_size"]), heads, rank, rope)
        / peaks["hbm_bytes_per_s"],
        must_compute_flops(int(c["latent_keys"]), heads, rank, rope)
        / peaks["bf16_flops_per_s"]) for c in steps)
    return 100.0 * floor_s / (ms / 1e3)
