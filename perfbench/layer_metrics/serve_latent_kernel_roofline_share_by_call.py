"""Per-layer metric ``serve_latent_kernel_roofline_share_by_call``: how near
the paged LATENT kernel (``ds_paged_latent``) comes to the chip's roofline in
a model that calls it MORE THAN ONCE a layer.

``serve_latent_kernel_roofline_share`` multiplies ONE call's pages and rows
by the configuration's depth; a layer with two attentions has two cache
entries and makes two calls, and that reader would count half the bytes.  The
operations and bytes are that reader's own two functions, loaded from its
file; the calls are the configuration's depth x ``cache_entries_per_layer``
(a key of its file; absent: 1, and the two readers agree).
"""

import os

from perfbench.loader import load_file

_here = os.path.dirname(os.path.abspath(__file__))
_latent = load_file(os.path.join(_here,
                                 "serve_latent_kernel_roofline_share.py"))
_experts = load_file(os.path.join(_here, "serve_moe_experts_ms_per_step.py"))


def read(record):
    """Over the traced steps: sum of max(bytes / HBM bandwidth, flops / peak)
    over the measured time inside ``ds_paged_latent`` of the first chip, in
    %.  ``grid_pages`` and ``absorbed_rows`` of a step are ONE call's (every
    call reads alike), ``latent_keys`` is summed over all the calls.  None
    without the kernel, the counts or the configuration's widths."""
    from perfbench import program_trace, serve_trace
    s, t = program_trace.summary(record), serve_trace.traced(record)
    config, peaks = _experts.traced_config(record), record.get("peaks")
    if not s or not t or not config or not peaks:
        return None
    ms = sum(v for k, v in s.get("device_ms_by_kernel", {}).items()
             if k.startswith(_latent.KERNEL))
    steps = [c for c in t["steps"] if "latent_keys" in c]
    if not ms or not steps:
        return None
    try:
        heads, rank, rope = (config["num_attention_heads"],
                             config["kv_lora_rank"],
                             config["qk_rope_head_dim"])
    except KeyError:
        return None
    calls = config["depth"] * int(config.get("cache_entries_per_layer", 1))
    floor_s = sum(max(
        _latent.must_move_bytes(
            calls * int(c["grid_pages"]),
            calls * int(c.get("absorbed_rows", c["live_tokens"])),
            int(c["block_size"]), heads, rank, rope)
        / peaks["hbm_bytes_per_s"],
        _latent.must_compute_flops(int(c["latent_keys"]), heads, rank, rope)
        / peaks["bf16_flops_per_s"]) for c in steps)
    return 100.0 * floor_s / (ms / 1e3)
