"""Per-layer metric ``serve_gdla_absorbed_roofline_share``: how near the paged
LATENT kernel (``ds_paged_latent``) comes to the chip's roofline where the
layers read DIFFERENTLY: grouped differential attention over a latent cache
with a window on some layers and none on others (``models/motif.py``).

What its calls MUST move and compute, from the steps' own counts.  A step of
such a model carries its counts summed over the LAYERS' calls, each layer
counted with its own pages and its own pairs (a window layer's row loads the
pages its window lies in and attends the window's keys, a full layer's its
whole context): ``grid_pages`` page loads, each ``block_size`` latent rows of
``kv_lora_rank + qk_rope_head_dim`` values ONCE, for scores and values both;
``absorbed_rows`` rows a call, ``depth`` calls, each row's
``num_attention_heads`` queries (the noise heads too: they read the cache
like any other) in as long as a latent row and out ``kv_lora_rank`` long;
``latent_keys`` (row, key) pairs, each a head's product over a latent row's
length (the score) and one over ``kv_lora_rank`` (the value).  2 bytes an
element; the widths are the traced configuration's; the peaks are
``peaks.json``'s.
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
    """Over the traced steps that carry the counts by layer kind
    (``grid_pages_full``): sum of max(bytes / HBM bandwidth, operations /
    bfloat16 peak) over the measured time inside ``ds_paged_latent`` of the
    first chip, in %.  None without the kernel, the counts or the
    configuration's widths: a parent of this metric's PR, a latent cache
    whose layers read alike, a cache that is not latent."""
    from perfbench import program_trace, serve_trace
    s, t = program_trace.summary(record), serve_trace.traced(record)
    config, peaks = _experts.traced_config(record), record.get("peaks")
    if not s or not t or not config or not peaks:
        return None
    ms = sum(v for k, v in s.get("device_ms_by_kernel", {}).items()
             if k.startswith(KERNEL))
    steps = [c for c in t["steps"]
             if "latent_keys" in c and "grid_pages_full" in c]
    if not ms or not steps:
        return None
    try:
        heads, rank, rope = (config["num_attention_heads"],
                             config["kv_lora_rank"],
                             config["qk_rope_head_dim"])
    except KeyError:
        return None
    floor_s = sum(max(
        must_move_bytes(int(c["grid_pages"]),
                        config["depth"] * int(c["absorbed_rows"]),
                        int(c["block_size"]), heads, rank, rope)
        / peaks["hbm_bytes_per_s"],
        must_compute_flops(int(c["latent_keys"]), heads, rank, rope)
        / peaks["bf16_flops_per_s"]) for c in steps)
    return 100.0 * floor_s / (ms / 1e3)
