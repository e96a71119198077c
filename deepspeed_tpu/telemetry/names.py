"""The names the program writes into a ``jax.profiler`` trace — one table
that the emit sites, the docs and the readers (``perfbench/program_trace.py``,
``tools``) all import.  Pure constants: importing this module imports nothing.

Three kinds of name:

* **host spans** (``telemetry.scope``): ``ds:<name>`` trace annotations on
  the host's line, with their counts as the event's ``stats``.  Written
  always; they cost well under a microsecond when no profiler session is
  open.
* **device ops**: every Pallas kernel is a ``pallas_call(name="ds_...")``,
  so its HLO instruction (the device event's name) starts with that name;
  ``jax.named_scope("ds.<layer>")`` puts the layer into the op's scope
  path (``jit(ds_micro_flat)/jvp(ds.lm_head_loss)/dot_general``), where
  JAX's own ``jvp(`` / ``transpose(`` / ``rematted_computation`` markers
  tell forward, backward and recomputation apart.
* **programs**: the jitted programs' ``__name__`` (the ``XLA Modules``
  line shows ``jit_<name>(<id>)``).

docs/observability.md and docs/kernels.md say what each covers.
"""

#: prefix of every host span the program writes
SPAN_PREFIX = "ds:"

# ---- training host spans (counts on each: step, micro_step)
TRAIN_SHARD_BATCH = "train.shard_batch"   # host batch -> device arrays
TRAIN_MICRO = "train.micro"               # call of the loss+grad program
TRAIN_BACKWARD = "train.backward"         # the whole backward() call
TRAIN_ACCUMULATE = "train.accumulate"     # fold grads into the accumulator
TRAIN_APPLY = "train.apply"               # call of the optimizer program
TRAIN_REPORT = "train.report"             # metrics, scheduler, hooks

# ---- serving host spans.  A scheduler turn launches the next engine step
# and THEN fetches the one launched the turn before (serving/scheduler.py).
# An engine step's life crosses two turns, so every span of it carries the
# step's id, ``launch`` (the engine's count of its launches, ragged steps and
# bursts in one sequence, from 1): its ``serve.launch``, the ``serve.fetch``
# that waits for it, the ``serve.dispatch`` that streams its tokens, the
# ``serve.finished`` of a request it ends.  The turn, ``serve.step``, carries
# the counts of the step it LAUNCHED, that step's id as ``launch`` and the
# id of the step it collected as ``fetched``
SERVE_STEP = "serve.step"                 # one ServingScheduler.step (a turn)
SERVE_ADMIT = "serve.admit"               # admission gate
SERVE_BUILD_BATCH = "serve.build_batch"   # pack the token budget (numpy)
SERVE_LAUNCH = "serve.launch"             # host arrays -> device, the jit
#                                           call, and the step's token array
#                                           enqueued behind it
SERVE_FETCH = "serve.fetch"               # np.asarray of a launched step's
#                                           tokens: the one wait for the
#                                           device (in a turn that runs ahead:
#                                           for the step BEFORE the one it
#                                           launched; its ``launch`` says
#                                           which)
SERVE_DISPATCH = "serve.dispatch"         # callbacks, lifecycle, flush
# one short span per request event (count: uid)
SERVE_ADMITTED = "serve.admitted"
SERVE_FINISHED = "serve.finished"         # + tokens, launch
SERVE_PREEMPTED = "serve.preempted"

TRAIN_SPANS = (TRAIN_SHARD_BATCH, TRAIN_MICRO, TRAIN_BACKWARD,
               TRAIN_ACCUMULATE, TRAIN_APPLY, TRAIN_REPORT)
SERVE_STEP_CHILDREN = (SERVE_ADMIT, SERVE_BUILD_BATCH, SERVE_LAUNCH,
                       SERVE_FETCH, SERVE_DISPATCH)

#: ``kind`` of a ``ds:serve.step`` and of a ``ds:serve.launch``: one ragged
#: engine step, or a fused multi-token decode burst
KIND_RAGGED = "ragged"
KIND_BURST = "burst"
#: the counts of a ``ds:serve.step`` (docs/observability.md says what each
#: counts); the batch builder makes them, the scheduler's span carries them
SERVE_STEP_COUNTS = ("step", "kind", "running", "queued", "token_budget",
                     "live_tokens", "prefill_tokens", "decode_tokens",
                     "grid_pages", "row_pages", "short_pages",
                     # of grid_pages, the pages that a multi-page item of
                     # the paged kernel loaded (a block of a long run)
                     "block_pages",
                     "burst_k", "preempts",
                     # 1: launched while the step before was unfetched (the
                     # device had it queued when that one ended); 0: the
                     # first step after an idle scheduler or an exhaustion,
                     # and every step whose tokens the host draws
                     "launched_ahead",
                     # what the cache holds, and what a window-plus-summary
                     # cache (EvaByte) did in the step
                     "context_tokens", "held_blocks", "block_size",
                     "summary_pages",
                     "chunks_closed", "windows_closed")
#: a launched engine step's id (``InferenceEngineV2.launches`` when it was
#: launched) on every span of its life; on the turn's ``serve.step`` the
#: step it launched (absent: it launched none)
COUNT_LAUNCH = "launch"
#: on a ``serve.step``: the id of the step the turn collected (the newer
#: one where it collected two; absent: none)
COUNT_FETCHED = "fetched"
#: on a ``serve.fetch`` that brings counts made on the device: how many
#: launches' counts it brings (its own step's and those of the steps before
#: it that fetched nothing: they add)
COUNT_LAUNCHES_COVERED = "launches_covered"
#: the ids a ``serve.step`` may carry beside ``SERVE_STEP_COUNTS``
SERVE_STEP_IDS = (COUNT_LAUNCH, COUNT_FETCHED)
#: further counts that only some models' steps carry: ``grid_pages_window``
#: and ``grid_pages_full`` of a model whose layers read differently (a window
#: on some, none on others), and what a model with an expert layer counted ON
#: THE DEVICE, fetched with the tokens a request waits for (a step that fetches
#: nothing leaves its counts to the next that does: they add).  They are the
#: ``serve.fetch``'s own stats, the fetch of the step that counted them; the
#: turn in which they arrive also adds them to its ``serve.step``
COUNT_EXPERT_COPIES = "expert_copies"         # (row, expert) pairs on a held
#                                               expert, summed over layers
COUNT_EXPERT_ACTIVE = "expert_active"         # held experts with a copy,
#                                               summed over layers
#: a router some of whose experts have NO WEIGHTS (identity experts: the
#: weighted copy of the layer's input, computed where the token lives): the
#: (live row, routed layer, chosen id past the real experts) triples
COUNT_ZERO_EXPERT_COPIES = "zero_expert_copies"
#: TRAINING counts the same two on the device inside the micro-step, the
#: fullest held expert's copies, summed over layers (what the grouped products
#: of a layer wait on), and the layer-calls whose copies ran in per-expert
#: padded blocks (``moe/held_experts.in_blocks``: the fullest expert fitted
#: one; the others took the worst case's buffer).  They leave the program
#: beside the loss and are the
#: stats of a LATER step's ``train.micro`` span, the first whose call finds
#: the array ready (the loop never waits for the device), with the
#: number of micro-steps whose counts it brings (they add)
COUNT_EXPERT_ROWS_MAX = "expert_rows_max"
COUNT_EXPERT_PADDED_CALLS = "expert_padded_calls"
COUNT_MICROS_COVERED = "micro_steps_covered"

#: a model with a LATENT cache (multi-head latent attention), whose rows
#: read it in one of two forms by the length of their run: the live rows of
#: one call that took the absorbed form (``ds_paged_latent``) and the
#: expanded one (``ds_paged_mla_chunk``); the (row, key) pairs each form's
#: rows attend, summed over the cache's entries (``latent_keys``: the
#: absorbed rows'); the latent pages one call of the expanded kernel brings
#: in (the absorbed kernel's are ``grid_pages``).  Where the model states a
#: window a layer (``layer_windows``) the pairs, ``expanded_pages`` and the
#: page counts are summed over the LAYERS' calls, each layer's rows seeing
#: its own window, and ``grid_pages_window`` / ``grid_pages_full`` are the
#: absorbed kernel's loads in the window layers and in the full ones
COUNT_LATENT_KEYS = "latent_keys"
COUNT_ABSORBED_ROWS = "absorbed_rows"
COUNT_EXPANDED_ROWS = "expanded_rows"
COUNT_EXPANDED_KEYS = "expanded_keys"
COUNT_EXPANDED_PAGES = "expanded_pages"

#: a model with RECURRENT layers (state-space: a fixed row a sequence slot in
#: the cache): summed over those layers, the state rows a step's runs read
#: (a run that starts at position 0 reads none) and write, the tokens their
#: scans walk, and the bytes of one sequence's row over all of them.  Made by
#: the batch builder, so they are the launched step's own counts on its
#: turn's ``serve.step`` (as ``latent_keys``; nothing is counted on the device)
COUNT_STATE_ROWS_READ = "state_rows_read"
COUNT_STATE_ROWS_WRITTEN = "state_rows_written"
COUNT_SCAN_TOKENS = "scan_tokens"
COUNT_STATE_ROW_BYTES = "state_row_bytes"
#: a model whose recurrent layers are a GATED DELTA RULE (a matrix state a
#: head, ``models/qwen3_next.py``) takes a run in one of two forms by its
#: length: summed over those layers, the tokens of runs of ONE token (one
#: update of the slot's row: every live row of a burst, a decode row beside a
#: chunk) and the tokens of longer runs (matrix products over chunks)
COUNT_RULE_SLOT_TOKENS = "rule_slot_tokens"
COUNT_RULE_CHUNK_TOKENS = "rule_chunk_tokens"

#: a LOOPED model (``models/ouro.py``: one stack of layers run several times
#: a token): counted ON THE DEVICE, the live rows x the passes of the stack
#: they ran (every row all of them while no row leaves the loop early), and
#: the pass the exit gate's distribution expects a live row to leave after,
#: summed over the live rows, in 1/256ths; and, from the batch builder, the
#: bytes the cache keeps of ONE token over all its entries (the engine's own
#: count of its buffers, as ``state_row_bytes`` is)
COUNT_LOOP_ROW_PASSES = "loop_row_passes"
COUNT_GATE_EXIT_PASSES_Q8 = "gate_exit_passes_q8"
COUNT_CACHE_TOKEN_BYTES = "cache_token_bytes"

# ---- jitted programs (``XLA Modules`` events are ``jit_<name>(<id>)``)
PROGRAM_MICRO = "ds_micro_"               # + the micro-step variant
PROGRAM_APPLY = "ds_apply_update"
PROGRAM_ACCUMULATE = "ds_accumulate"
PROGRAM_RAGGED_STEP = "ds_ragged_step_"   # + the architecture
PROGRAM_DECODE_BURST = "ds_decode_burst"

# ---- named scopes inside the compiled programs.  The flax models name
# attention and MLP themselves (module names in the scope path); the serving
# steps are plain functions and set the two scopes.
SCOPE_EMBED = "ds.embed"
SCOPE_LM_HEAD_LOSS = "ds.lm_head_loss"    # training: head and loss, both paths
SCOPE_LM_HEAD = "ds.lm_head"              # serving: final norm, last-token logits
SCOPE_ATTENTION = "ds.attn"               # serving: qkv, rotary, cache, paged, o
SCOPE_MLP = "ds.mlp"                      # serving: the MLP or expert block
SCOPE_MOE_ROUTER = "ds.moe_router"        # inside ds.mlp: router, top-k
SCOPE_MOE_EXPERTS = "ds.moe_experts"      # inside ds.mlp: gather, grouped
#                                           matmuls and weighted scatter-add
#                                           of the held experts
SCOPE_MOE_SHARED = "ds.moe_shared"        # inside ds.mlp: the shared experts
SCOPE_MOE_ZERO = "ds.moe_zero"            # inside ds.mlp: the identity
#                                           experts' weighted copy of the
#                                           branch's input
SCOPE_DENSE_FFN = "ds.dense_ffn"          # serving: the dense feed-forwards
#                                           of a layer whose expert branch
#                                           (ds.mlp) runs BESIDE them
SCOPE_NORM = "ds.norm"                    # serving: rms / layer norms
SCOPE_KV_CACHE = "ds.kv_cache"            # serving, inside ds.attn: everything a
#                                           step spends to put its K/V into the
#                                           paged cache (the scatter; encoding
#                                           and scales on the quantized path)
SCOPE_MLA_DOWN = "ds.mla_down"            # serving, inside ds.attn: the two
#                                           low-rank projections, their norms
#                                           and the rotary of q_r and k_r
SCOPE_MLA_ABSORB = "ds.mla_absorb"        # serving, inside ds.attn: q_n into
#                                           the latent space and the latent
#                                           output out of it (W_uk, W_uv)
SCOPE_DIFF_ATTN = "ds.diff_attn"          # serving, inside ds.attn: grouped
#                                           differential attention's own
#                                           parts: lambda's projection, the
#                                           noise heads' outputs subtracted
#                                           from the signal heads', the
#                                           element-wise output gate
SCOPE_MHC = "ds.mhc"                      # serving, a multi-stream residual
#                                           (hyper-connections): a sublayer's
#                                           three mappings, the Sinkhorn
#                                           sweeps, the read of the streams
#                                           and the write back to them
SCOPE_POLYNORM = "ds.polynorm"            # serving, inside ds.mlp: PolyNorm
#                                           on a feed-forward's gate (in an
#                                           expert layer also under
#                                           ds.moe_experts / ds.moe_shared)
SCOPE_SSM = "ds.ssm"                      # serving: a Mamba mixer, the twin
#                                           of ds.attn; inside it:
SCOPE_SSM_PROJ = "ds.ssm_proj"            # in_proj, x_proj, the inner norms,
#                                           dt_proj + softplus, gate, out_proj
SCOPE_SSM_CONV = "ds.ssm_conv"            # the causal convolution over a
#                                           run's rows and its slot's last
#                                           rows, and their write-back
SCOPE_SSM_SCAN = "ds.ssm_scan"            # the recurrence of either kind of
#                                           step: the kernel ds_selective_scan
#                                           or a burst's update of every
#                                           slot, the state's read and write
SCOPE_GDN = "ds.gdn"                      # serving: a Gated DeltaNet mixer,
#                                           the twin of ds.attn; inside it:
SCOPE_GDN_PROJ = "ds.gdn_proj"            # the two input products, the
#                                           rule's inputs (L2 norms, g,
#                                           beta), the gated norm, out_proj
SCOPE_GDN_CONV = "ds.gdn_conv"            # the causal convolution over a
#                                           run's rows and its slot's last
#                                           rows, and their write-back
SCOPE_GDN_RULE = "ds.gdn_rule"            # the delta rule, both forms, the
#                                           state's read and write; inside:
SCOPE_GDN_SLOT = "ds.gdn_slot"            # the one-token form: one update of
#                                           every slot's row
SCOPE_GDN_CHUNK = "ds.gdn_chunk"          # the chunk form: the loop over a
#                                           step's chunks of 64 rows
SCOPE_ATTN_GATE = "ds.attn_gate"          # serving, inside ds.attn: a gated
#                                           attention's own parts: the
#                                           per-head norms of q and k and the
#                                           sigmoid gate on the output
SCOPE_UT_PASS = "ds.ut_pass"              # serving, a looped model: ONE pass
#                                           of the stack (all its layers);
#                                           inside it:
SCOPE_UT_NORM = "ds.ut_norm"              # the final norm BETWEEN two passes
#                                           (the last pass's is ds.lm_head's)
SCOPE_EXIT_GATE = "ds.exit_gate"          # serving, a looped model: the exit
#                                           gate on a pass's output and the
#                                           exit distribution's bookkeeping
SCOPE_EVA_SUMMARY = "ds.eva_summary"      # serving: pooling the chunks a step
#                                           completes, and their scatter
# training, inside the flax module ``self_attn`` (whose ops stay the class
# ``attention``): the attention block's parts
SCOPE_ATTN_PROJ = "ds.attn_proj"          # the q, k, v products and the o
#                                           product
SCOPE_ATTN_ROTARY = "ds.attn_rotary"      # both apply_rotary calls
SCOPE_ATTN_KV_REPEAT = "ds.attn_kv_repeat"  # K/V repeated to the query heads
SCOPE_ATTN_CORE = "ds.attn_core"          # attention_core (or the Ulysses /
#                                           ring layer): layout changes,
#                                           shard_map edges, the ds_flash_*
#                                           kernels
SCOPE_GRAD_CAST = "ds.grad_cast"          # training: the micro-step's cast of
#                                           the gradients to the accumulator's
#                                           dtype (runtime/engine.py)
MODULE_ATTENTION = "self_attn"            # flax module name (training)
MODULE_MLP = "mlp"

# ---- Pallas kernels: ``pallas_call(name=...)`` prefixes by family
KERNEL_PREFIX = "ds_"
KERNEL_FLASH = "ds_flash_"                # fwd, bwd_dq, bwd_dkv (+ _bias_)
KERNEL_PAGED = "ds_paged_"                # runs (run-tiled), decode (per token),
#                                           latent (a latent cache's reader)
KERNEL_SCAN = "ds_selective_scan"         # a Mamba-1 recurrence over runs
KERNEL_OPTIMIZER = "ds_fused_"            # adam, lion, lamb_phase1/2

#: JAX's own markers in a scope path
MARK_TRANSPOSE = "transpose("             # backward
MARK_REMAT = "rematted_computation"       # recomputed forward
