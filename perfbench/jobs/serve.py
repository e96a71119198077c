"""Job ``serve``: a closed loop of sessions through ``ServingScheduler``.

Set-up (all of it counted in ``setup_s``): seeded bf16 weights made on the
device; the scheduler built through ``serving.build_serving_engine``; every
member of the serving program family warmed by requests crafted to hit it;
three seeded requests streamed through ``submit`` / ``step`` and compared with
the plain float32 reference's teacher-forced logits; the sessions started and
run until every session's first request has streamed its first token (the
prefill wave of a cold start is set-up, not traffic).  Then the timed window.

What the window reports.  ``serve_tokens_per_s`` is every token streamed
inside it over its length: the one end-to-end metric, because a closed loop
of more sessions than the system can keep busy is a saturation cell.  The
tails are per-layer metrics there (``ttft_ms``, ``tpot_ms``, ``queue_ms`` in
the record), taken over the window's own requests: time to first token over
the requests SUBMITTED inside the window (one submitted during the ramp
carries set-up waits and is left out; one still waiting at the close counts
with the wait it has had), time per output token over every request that
streamed two or more tokens inside the window, in flight at its close or not.
"""

import time

import numpy as np

#: The reference comparison, in standard deviations of the reference's logits
#: at the position: (largest reference logit - reference logit of the engine's
#: token) / std.  The rule: tolerance = TOL_FACTOR x the worst value measured
#: over every seed run of the configuration, and never under TOL_FLOOR (the
#: gap is exactly 0 wherever the engine's token is the reference's argmax, so
#: a handful of seeds can measure 0).  What was measured is data of the
#: configuration: ``measured_worst["serve.logit_gap"]`` of its file, with
#: where it was measured (``ctx.measured_worst``).  tests/unit/perfbench
#: applies the same rule to the error measured on the CPU at tiny size and
#: shows what it rejects.
TOL_FACTOR = 3.0
TOL_FLOOR = 0.02
#: least share of generated positions where the engine's token IS the
#: reference's argmax
ARGMAX_SHARE_MIN = 0.8
#: A routed (mixture-of-experts) reference states its routing
#: (``logits_and_routing_at``).  Top-k routing is not continuous: a token
#: whose k-th and (k+1)-th router logits lie closer than the engine's rounding
#: error goes to other experts in the engine than in the float32 reference,
#: and both are right.  A position whose router margin is under TOL_FACTOR x
#: ``measured_worst["serve.router_margin"]`` at ONE layer is judged against
#: the better of two float32 answers (the reference's, and the reference's
#: with the two experts exchanged at that layer for that token alone); one
#: with such a margin at two or more layers is left out and counted.  The
#: shares of a run's positions judged so and left out are held like every
#: other number of the comparison: to TOL_FACTOR x the worst share measured
#: over the configuration's seeds (``serve.routed_two_answer_share``,
#: ``serve.routed_left_out_share``), and never to less than this floor: the
#: counts are small whole numbers, and a dozen seeds can measure 0.
ROUTED_SHARE_FLOOR = 0.05
#: A flip of an EARLIER token reaches a position through attention, the more
#: weakly the longer the context.  Measured at tiny size over 400 seeds a
#: context length (README.md, "The routed rule's two ranges", has the table):
#: judged against its own tokens' second answers alone, a run's worst gap in
#: contexts of 40 to 132 tokens is two to four times what all tokens' second
#: answers leave, and past 300 tokens no more than that.  So in a request of
#: at most this many tokens every token under the margin gives a second answer
#: to the positions after it; in a longer one only the compared positions' own
#: tokens do, where a forward a near-tie of every token would be hundreds of
#: forwards (``serve.logit_gap`` is measured with what an earlier flip leaves
#: in).  Both ranges are run by tests/unit/perfbench/test_perfbench_routed.py.
EARLIER_FLIP_CONTEXT = 256


def tolerances(ctx):
    """``{check: tolerance}`` of the reference comparison; read before
    anything is built, so that a configuration without a measured worst fails
    at once, by name."""
    tols = {"serve.logit_gap": max(
        TOL_FACTOR * ctx.measured_worst("serve.logit_gap"), TOL_FLOOR)}
    if hasattr(ctx.reference, "logits_and_routing_at"):
        tols["serve.router_margin"] = \
            TOL_FACTOR * ctx.measured_worst("serve.router_margin")
        for share in ("serve.routed_two_answer_share",
                      "serve.routed_left_out_share"):
            tols[share] = min(1.0, max(
                TOL_FACTOR * ctx.measured_worst(share), ROUTED_SHARE_FLOOR))
    return tols


def build_scheduler(ctx, model, params):
    """The scheduler as the configuration's ``program.serve.engine`` lays it
    out (block size, token budget, burst, cache blocks, admission cap: the
    deployment's settings), sized for the longest context of the traffic."""
    from deepspeed_tpu.serving import build_serving_engine
    t, eng = ctx.traffic, ctx.config["program"]["serve"]["engine"]
    block = int(eng["block_size"])
    longest = t["prompt_len"]["max"] + t["output_len"]["max"]
    sm = {"max_tracked_sequences": 2 * int(eng["max_concurrent"]),
          "max_ragged_sequence_count": int(eng["max_concurrent"]) + 1,
          "max_context": -(-longest // block) * block,
          "block_size": block,
          "num_blocks": int(eng["num_blocks"]),
          "max_ragged_batch_size": int(eng["token_budget"])}
    engine_config = {"dtype": "bfloat16", "state_manager": sm,
                     "decode_burst": int(eng["decode_burst"])}
    return build_serving_engine(
        model, params=params, engine_config=engine_config,
        serving_config={"max_concurrent": int(eng["max_concurrent"])})


def stream(sched, requests):
    """Submit all, step until idle; the streamed tokens of each."""
    out = [[] for _ in requests]
    for i, (prompt, n) in enumerate(requests):
        sched.submit(prompt, max_new_tokens=n,
                     on_token=lambda t, done, i=i: out[i].append(t))
    sched.drain()
    return out


def warm_programs(ctx, sched, vocab):
    """Hit every member of the program family: a ragged step that is all
    prefill (a prompt longer than the budget), a lone decode step with one
    token left, a decode burst of each power of two up to the cap, and a
    mixed prefill + decode step."""
    rng = np.random.default_rng(0)
    eng = ctx.config["program"]["serve"]["engine"]
    cap, budget = int(eng["decode_burst"]), int(eng["token_budget"])
    longest = ctx.traffic["prompt_len"]["max"]
    prompt = lambda n: rng.integers(0, vocab, size=min(n, longest)).tolist()
    # 1 + (cap + cap/2 + ... + 2) new tokens: first token by the prefill
    # step, then a burst of each power of two
    bursts = 1 + sum(1 << k for k in range(1, max(cap, 1).bit_length()))
    stream(sched, [(prompt(budget + budget // 6), bursts)])
    stream(sched, [(prompt(40), 2)])                  # lone decode, k < 2
    stream(sched, [(prompt(20), 8), (prompt(budget - 60), 3),
                   (prompt(5), 4)])


def position_gaps(logits, toks):
    """Per position: ``(gap, hit)``.  The gap is (largest reference logit -
    reference logit of the engine's token) / std of the reference logits at
    that position; a hit is a position where the engine's token is the
    reference's argmax."""
    import jax.numpy as jnp
    chosen = jnp.take_along_axis(
        logits, jnp.asarray(toks, jnp.int32)[:, None], axis=-1)[:, 0]
    gap = (jnp.max(logits, axis=-1) - chosen) / jnp.std(logits, axis=-1)
    hit = np.asarray(jnp.argmax(logits, axis=-1)) == np.asarray(toks)
    return np.asarray(gap, np.float64), hit


def logit_gaps(logits_at, params, sizes, prompts, produced):
    """For each streamed request, against the reference's teacher-forced
    logits at the generated positions: ``(prompt length, worst gap, positions
    where the engine's token is the reference's argmax, positions)``."""
    rows = []
    for prompt, toks in zip(prompts, produced):
        ids = np.asarray(prompt + toks[:-1], np.int32)
        at = np.arange(len(prompt) - 1, len(prompt) - 1 + len(toks))
        gap, hit = position_gaps(logits_at(params, ids, at, sizes), toks)
        rows.append((len(prompt), float(np.max(gap)), int(hit.sum()),
                     len(toks)))
    return rows


def routed_logit_gaps(routing_at, params, sizes, prompts, produced, margin):
    """``logit_gaps`` for a reference that states its routing.  Every (token,
    layer) of a request whose router margin is under ``margin`` gives a second
    float32 answer: the reference with that token's two experts exchanged at
    that layer, for that token alone.  A position is judged against the better
    of the reference and the second answers of its OWN token and of the tokens
    before it (an earlier token's flip reaches it through attention); of those
    only in a request of at most EARLIER_FLIP_CONTEXT tokens.  A position whose
    own token is under the margin at two or more layers is left out.  Rows:
    ``(prompt length, worst gap, argmax positions, positions judged, of those
    with an own second answer, positions left out, second answers tried)``."""
    rows = []
    for prompt, toks in zip(prompts, produced):
        ids = np.asarray(prompt + toks[:-1], np.int32)
        at = np.arange(len(prompt) - 1, len(prompt) - 1 + len(toks))
        first = 0 if len(ids) <= EARLIER_FLIP_CONTEXT else int(at[0])
        logits, margins = routing_at(params, ids, np.arange(first, len(ids)),
                                     sizes)
        gap, hit = position_gaps(logits[at - first], toks)
        near = np.asarray(margins) < margin               # [tokens, layers]
        own = near[at - first].sum(axis=1)
        for t, layer in zip(*np.nonzero(near)):
            later = at >= first + t        # the positions this flip reaches
            # at every compared position, so that one shape is compiled
            other, _ = routing_at(params, ids, at, sizes,
                                  flip=(int(layer), int(first + t)))
            g, h = position_gaps(other, toks)
            gap = np.where(later, np.minimum(gap, g), gap)
            hit = hit | (later & h)
        judged = own < 2
        rows.append((len(prompt), float(np.max(gap, where=judged, initial=0)),
                     int(hit[judged].sum()), int(judged.sum()),
                     int((own == 1).sum()), int((~judged).sum()),
                     int(near.sum())))
    return rows


def judge(checks, rows, tols):
    """The comparison's CHECK lines from its rows (``logit_gaps`` or
    ``routed_logit_gaps``)."""
    if "serve.router_margin" in tols:
        two, left = sum(r[4] for r in rows), sum(r[5] for r in rows)
        positions = sum(r[3] for r in rows) + left
        checks.at_most(
            "serve.routed_two_answer_share", two / positions,
            tols["serve.routed_two_answer_share"],
            f"own router margin under {tols['serve.router_margin']:g} at one "
            f"layer: {two}/{positions} positions; "
            f"{sum(r[6] for r in rows)} second answers tried")
        checks.at_most(
            "serve.routed_left_out_share", left / positions,
            tols["serve.routed_left_out_share"],
            f"under it at two or more layers: {left}/{positions} positions")
    for n_prompt, gap, hits, n, *_ in rows:
        checks.at_most(
            f"serve.logit_gap_prompt{n_prompt}", gap, tols["serve.logit_gap"],
            f"argmax at {hits}/{n} positions")
    checks.at_most(
        "serve.argmax_miss_share",
        1.0 - sum(r[2] for r in rows) / max(sum(r[3] for r in rows), 1),
        1.0 - ARGMAX_SHARE_MIN)


def reference_check(ctx, sched, sizes, tols):
    from perfbench.traffic_gen import check_requests
    new = int(ctx.traffic["check_new_tokens"])
    prompts = check_requests(ctx.traffic, sizes["vocab_size"], ctx.seed)
    produced = stream(sched, [(p, new) for p in prompts])
    for p, toks in zip(prompts, produced):
        ctx.checks.equal(f"serve.check_tokens_prompt{len(p)}", len(toks), new)
    if "serve.router_margin" in tols:
        rows = routed_logit_gaps(
            ctx.reference.logits_and_routing_at, sched.engine.params, sizes,
            prompts, produced, tols["serve.router_margin"])
    else:
        rows = logit_gaps(ctx.reference.logits_at, sched.engine.params, sizes,
                          prompts, produced)
    judge(ctx.checks, rows, tols)


class Session:
    __slots__ = ("t_submit", "want", "times", "uid")


def run(ctx):
    import jax
    from perfbench import weights
    from perfbench.harness import fold_seed, percentile, weights_seed
    from perfbench.traffic_gen import RequestStream
    from deepspeed_tpu.serving import AdmissionQueueFull

    traffic, config = ctx.traffic, ctx.config
    tols = tolerances(ctx)
    model, _ = ctx.arch.build(config, "serve")
    sizes = ctx.arch.reference_sizes(config, "serve")
    vocab = sizes["vocab_size"]

    t0 = time.perf_counter()
    params = weights.seeded_weights(ctx.arch.param_shapes(model),
                                    fold_seed(weights_seed(ctx)))
    jax.block_until_ready(params)
    t_weights = time.perf_counter() - t0
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    sched = build_scheduler(ctx, model, params)
    del params

    t0 = time.perf_counter()
    warm_programs(ctx, sched, vocab)
    t_warm = time.perf_counter() - t0
    t0 = time.perf_counter()
    reference_check(ctx, sched, sizes, tols)
    t_check = time.perf_counter() - t0

    # ---- the sessions
    requests = RequestStream(traffic, vocab, ctx.seed)
    n_sessions = int(traffic["sessions"])
    clock = time.perf_counter
    free = list(range(n_sessions))
    live = {}            # session -> Session
    sent = []            # every Session submitted, in order
    done = []            # those whose reply is complete
    refused = 0

    streamed = [0]       # tokens handed to callers, all requests

    def on_token(s):
        def cb(tok, finished):
            rec = live[s]
            rec.times.append(clock())
            streamed[0] += 1
            if finished:
                done.append(rec)
                del live[s]
                free.append(s)
        return cb

    def fill():
        nonlocal refused
        while free:
            s = free.pop()
            prompt, want = requests.next(s)
            rec = Session()
            rec.t_submit, rec.want, rec.times = clock(), want, []
            live[s] = rec
            try:
                rec.uid = sched.submit(
                    prompt, max_new_tokens=want, on_token=on_token(s))
                sent.append(rec)
            except AdmissionQueueFull:
                refused += 1
                del live[s]

    t0 = time.perf_counter()
    fill()
    first_wave = list(live.values())
    while any(not r.times for r in first_wave):       # the first prefill wave
        sched.step()
        fill()
    t_ramp = time.perf_counter() - t0
    ctx.info("setup", params_m=round(n_params / 1e6, 1),
             depth=sizes["num_hidden_layers"], weights_s=round(t_weights, 2),
             warm_s=round(t_warm, 2), reference_check_s=round(t_check, 2),
             ramp_s=round(t_ramp, 2), tolerances=tols,
             tolerances_from=ctx.config_file,
             weights_seed=weights_seed(ctx),
             compiles=ctx.compiles.summary())

    # ---- the timed window
    spans = ctx.spans
    mark = ctx.compiles.mark()
    done_before = len(done)
    streamed_before = streamed[0]
    refused_before = refused
    preempt_before = sched.preemptions
    step_ms = []
    trace_s = float(traffic.get("trace_seconds", 3.0))
    trace_at = min(2.0, ctx.seconds / 4) if ctx.trace else None
    traced = None
    raised = 0
    n_steps = 0          # scheduler steps begun inside the window
    last_step = None     # (start, end, tokens) of the newest scheduler step
    t_window = clock()
    setup_s = t_window - ctx.t_process_start
    while True:
        now = clock()
        if ctx.trace and traced is None and now - t_window >= trace_at:
            ctx.profiler.start()
            traced = jax.profiler.TraceAnnotation("pb:traced")
            traced.__enter__()
        elif ctx.profiler.on and now - ctx.profiler.t_start >= trace_s:
            traced.__exit__(None, None, None)
            ctx.profiler.stop()
        if now - t_window >= ctx.seconds and not ctx.profiler.on:
            break
        try:
            t_s, n_s = clock(), streamed[0]
            with spans.span("sched_step"):
                emitted = sched.step()
            n_steps += 1
            last_step = (t_s, clock(), streamed[0] - n_s)
            if emitted:
                step_ms.append(1e3 * (last_step[1] - t_s))
        except Exception as e:
            print(f"sched.step raised {type(e).__name__}: {e}", flush=True)
            raised += 1
            if raised > 3:
                break
        with spans.span("submit"):
            fill()
    window_s = clock() - t_window
    in_window = ctx.compiles.since(mark)

    finished = done[done_before:]
    wrong_len = sum(len(r.times) != r.want for r in finished)
    failed = wrong_len + (refused - refused_before) + raised
    attempted = len(finished) + (refused - refused_before) + raised
    tokens = streamed[0] - streamed_before     # all tokens of the window
    # The window closes at the first step boundary past --seconds, and a step
    # lasts 0.3-0.7 s here: which boundary that is moves the plain quotient
    # tokens / window_s by 1 % from run to run (PR 23 measured 182.1, 183.9 and
    # 185.3 tokens/s for the same schedule).  So the step that straddles the
    # nominal end counts pro rata, and the rate is over exactly --seconds.
    rate = tokens / window_s
    t_nominal = t_window + ctx.seconds
    if last_step and last_step[0] < t_nominal < last_step[1]:
        t0_, t1_, n_ = last_step
        rate = (tokens - n_ * (t1_ - t_nominal) / (t1_ - t0_)) / ctx.seconds
    t_close = t_window + window_s
    mine = [r for r in sent if r.t_submit >= t_window]
    waiting = sum(not r.times for r in mine)
    ttft = [1e3 * ((r.times[0] if r.times else t_close) - r.t_submit)
            for r in mine]
    tpot = []
    for r in sent:
        inside = [t for t in r.times if t >= t_window]
        if len(inside) > 1:
            tpot.append(1e3 * (inside[-1] - inside[0]) / (len(inside) - 1))
    queue_ms = []
    for r in mine:
        q = sched.query(r.uid)
        if q is not None and q.t_admit is not None:
            queue_ms.append(1e3 * (q.t_admit - q.t_submit))
    ctx.checks.equal("serve.wrong_length_replies", wrong_len, 0)
    ctx.checks.equal("serve.refused_or_raised", failed - wrong_len, 0)
    ctx.checks.equal("serve.compilations_in_window", len(in_window), 0,
                     str(in_window[:3]))
    e2e = {"serve_tokens_per_s": rate, "setup_s": setup_s}
    ctx.info("window", window_s=window_s, completed=len(finished),
             requests_per_s=len(finished) / window_s, tokens=tokens,
             sched_steps=n_steps,
             tokens_of_completed=sum(len(r.times) for r in finished),
             serve_tokens_per_s=rate, submitted_in_window=len(mine),
             still_waiting_for_first_token=waiting,
             ttft_ms_p50=percentile(ttft, 50),
             ttft_ms_p95=percentile(ttft, 95), n_ttft=len(ttft),
             tpot_ms_p50=percentile(tpot, 50),
             tpot_ms_p95=percentile(tpot, 95), n_tpot=len(tpot),
             queue_ms_p95=percentile(queue_ms, 95),
             preemptions=sched.preemptions - preempt_before,
             peak_running=sched.peak_running,
             slowest_host_calls=spans.slowest(t_window),
             compilations_in_window=len(in_window), setup_s=setup_s)
    return {
        "job": "serve", "attempted": attempted, "failed": failed,
        "window_s": window_s, "completed": len(finished),
        "n_chips": len(ctx.devices), "end_to_end": e2e,
        "ttft_ms": ttft, "tpot_ms": tpot, "queue_ms": queue_ms,
        "step_ms": step_ms,
        "preemptions": sched.preemptions - preempt_before,
        "trace": ctx.profiler.reduce(1) if ctx.trace else None,
    }
