"""Per-layer metric ``serve_gdla_chunk_roofline_share``: how near the
EXPANDED reader of the paged latent cache (``ds_paged_mla_chunk``: the rows
of a prefill chunk's long runs) comes to the chip's roofline where the keys
and values are those of K/V GROUPS and some layers read a window alone:
grouped differential attention over a latent cache (``models/motif.py``).

What the FORM must move and compute for a step's expanded rows, whatever
implements it, from the step's own counts (summed over the LAYERS' calls,
each layer counted with its own window) and the configuration's widths (``H``
= ``num_attention_heads`` query heads, the noise heads among them, in ``G`` =
``num_key_value_heads`` groups; ``rank`` = ``kv_lora_rank``, ``rope``,
``nope`` = ``head_dim`` - ``rope``, ``value``; 2 bytes an element; the peaks
are ``peaks.json``'s):

* bytes = 2 x (``context_tokens`` x (rank + rope) + depth x
  ``expanded_rows`` x H x (nope + rope + value)): the latent pages a run's
  rows may see read ONCE (not once a head), every expanded row's queries
  read and its outputs written once;
* operations = 2 x (H x ``expanded_keys`` x (nope + rope + value) + G x
  ``context_tokens`` x rank x (nope + value)): a score over ``nope + rope``
  and a value over ``value`` for every (row, key) pair a row SEES (a window
  layer's row: its window's pairs), and the keys ``c W_uk`` and values ``c
  W_uv`` of the G groups made from the latent rows ONCE a run (not once a
  query head: five heads read one group's).

``context_tokens`` = ``expanded_pages`` / H x ``block_size``: the count is
the pages the calls' loops bring in, once a head; a run's context (under a
window: from the block that holds its first row's first key) is walked in
blocks of 8 pages, so it reads up to 7 pages a run and layer OVER the pages
its rows see; the weights a call reads are left out (reads lower, never
higher).

Time and counts are matched step by step, as
``serve_mla_chunk_kernel_roofline_share`` does: the WHOLE ragged steps of the
joined table (``perfbench/step_trace.py``), each with the launched step's own
counts and the chip-0 time of the leaf ops named ``ds_paged_mla_chunk*``
inside its execution.
"""

import os

from perfbench.loader import load_file

_experts = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "serve_moe_experts_ms_per_step.py"))
BYTES = 2                       # bfloat16
KERNEL = "ds_paged_mla_chunk"


def widths_of(config):
    """``(H, G, rank, rope, nope, value)`` of a configuration file."""
    rope = int(config["qk_rope_head_dim"])
    return (int(config["num_attention_heads"]),
            int(config["num_key_value_heads"]), int(config["kv_lora_rank"]),
            rope, int(config["head_dim"]) - rope, int(config["v_head_dim"]))


def must_move_bytes(context_tokens, rows, heads, rank, rope, nope, value):
    """Bytes the form must move: ``context_tokens`` latent rows read once,
    ``rows`` rows' queries in and outputs out, ``heads`` each."""
    return (context_tokens * (rank + rope)
            + rows * heads * (nope + rope + value)) * BYTES


def must_compute_flops(keys, context_tokens, heads, groups, rank, rope, nope,
                       value):
    """Operations of ``keys`` (row, key) pairs, a score and a value each for
    each of ``heads``, and of making ``groups`` groups' keys and values of
    ``context_tokens`` latent rows."""
    return 2 * (heads * keys * (nope + rope + value)
                + groups * context_tokens * rank * (nope + value))


def floor_s(counts, depth, widths, peaks):
    """The least time the chip could take for one step's expanded rows."""
    heads, groups, rank, rope, nope, value = widths
    rows = depth * int(counts["expanded_rows"])
    tokens = int(counts["expanded_pages"]) // heads * int(counts["block_size"])
    return max(
        must_move_bytes(tokens, rows, heads, rank, rope, nope, value)
        / peaks["hbm_bytes_per_s"],
        must_compute_flops(int(counts["expanded_keys"]), tokens, *widths)
        / peaks["bf16_flops_per_s"])


def read(record):
    """Over the whole traced ragged steps that hold an expanded row and
    carry the counts by layer kind (``grid_pages_full``): sum of max(bytes /
    HBM bandwidth, operations / bfloat16 peak) over the time of the first
    chip inside ``ds_paged_mla_chunk``, in %.  None without the join, the
    kernel, an expanded row, the counts or the configuration's widths."""
    from perfbench import step_trace
    t = step_trace.traced(record)
    config, peaks = _experts.traced_config(record), record.get("peaks")
    if not t or not config or not peaks:
        return None
    try:
        widths = widths_of(config)
    except (KeyError, TypeError):
        return None
    rows = [r for r in step_trace.whole(t, t["kinds"][0])
            if int(r["counts"].get("expanded_rows", 0))
            and "grid_pages_full" in r["counts"]]
    ms = sum(r["kernel_ms"].get(KERNEL, 0.0) for r in rows)
    if not ms:
        return None
    floor = sum(floor_s(r["counts"], config["depth"], widths, peaks)
                for r in rows)
    return 100.0 * floor / (ms / 1e3)
