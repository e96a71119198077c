"""Per-layer metric ``serve_mla_chunk_kernel_roofline_share``: how near the
EXPANDED reader of the paged latent cache (``ds_paged_mla_chunk``: the rows
of a prefill chunk's long runs, since PR 51) comes to the chip's roofline.

What the FORM must move and compute for a step's expanded rows, whatever
implements it, from the step's own counts and the configuration's widths
(``H`` heads, ``rank`` = ``kv_lora_rank``, ``rope``, ``nope``, ``value``; 2
bytes an element; the peaks are ``peaks.json``'s):

* bytes = 2 x calls x (``context_tokens`` x (rank + rope)
  + ``expanded_rows`` x H x (nope + rope + value)): a run's latent pages
  read ONCE (not once a head), every expanded row's queries read and its
  output written once;
* operations = 2 x H x (``expanded_keys`` x (nope + rope + value)
  + calls x ``context_tokens`` x rank x (nope + value)): a score over
  ``nope + rope`` and a value over ``value`` for every (row, key) pair (640 a
  pair and head at the published widths), and a run's keys ``c W_uk`` and
  values ``c W_uv`` made from its latent rows once (262 144 a context token
  and head).

``expanded_rows`` and ``expanded_pages`` of a step are ONE call's,
``expanded_keys`` is summed over the cache's entries; calls = depth x the
file's ``cache_entries_per_layer`` (absent: 1), as the by-call reader has
it.  ``context_tokens`` = ``expanded_pages`` / H x ``block_size``: the count
is the pages one call's loops bring in, once a head, and a run's context is
walked in blocks of 8 pages, so it reads up to 7 pages a run OVER the
context's own (under 1 % of the floor at contexts of thousands of tokens,
where the pairs are three quarters of the operations); the weights ``W_uk``,
``W_uv`` a call reads are left out (reads lower, never higher).

Time and counts are matched step by step: the WHOLE ragged steps of the
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
WIDTHS = ("num_attention_heads", "kv_lora_rank", "qk_rope_head_dim",
          "qk_nope_head_dim", "v_head_dim")


def must_move_bytes(context_tokens, rows, heads, rank, rope, nope, value):
    """Bytes the form must move: ``context_tokens`` latent rows read once,
    ``rows`` rows' queries in and outputs out, ``heads`` each."""
    return (context_tokens * (rank + rope)
            + rows * heads * (nope + rope + value)) * BYTES


def must_compute_flops(keys, context_tokens, heads, rank, rope, nope, value):
    """Operations of ``keys`` (row, key) pairs, a score and a value each,
    and of making the keys and values of ``context_tokens`` latent rows, for
    each of ``heads``."""
    return 2 * heads * (keys * (nope + rope + value)
                        + context_tokens * rank * (nope + value))


def floor_s(counts, calls, widths, peaks):
    """The least time the chip could take for one step's expanded rows."""
    heads = widths[0]
    rows = calls * int(counts["expanded_rows"])
    tokens = calls * (int(counts["expanded_pages"]) // heads) \
        * int(counts["block_size"])
    return max(
        must_move_bytes(tokens, rows, *widths) / peaks["hbm_bytes_per_s"],
        must_compute_flops(int(counts["expanded_keys"]), tokens, *widths)
        / peaks["bf16_flops_per_s"])


def read(record):
    """Over the whole traced ragged steps that hold an expanded row: sum of
    max(bytes / HBM bandwidth, operations / bfloat16 peak) over the time of
    the first chip inside ``ds_paged_mla_chunk``, in %.  None without the
    join, the kernel, an expanded row or the configuration's widths: a
    parent of PR 51, a cache that is not latent, a stretch of decode rows."""
    from perfbench import step_trace
    t = step_trace.traced(record)
    config, peaks = _experts.traced_config(record), record.get("peaks")
    if not t or not config or not peaks:
        return None
    try:
        widths = tuple(int(config[k]) for k in WIDTHS)
    except KeyError:
        return None
    calls = config["depth"] * int(config.get("cache_entries_per_layer", 1))
    rows = [r for r in step_trace.whole(t, t["kinds"][0])
            if int(r["counts"].get("expanded_rows", 0))]
    ms = sum(r["kernel_ms"].get(KERNEL, 0.0) for r in rows)
    if not ms:
        return None
    floor = sum(floor_s(r["counts"], calls, widths, peaks) for r in rows)
    return 100.0 * floor / (ms / 1e3)
