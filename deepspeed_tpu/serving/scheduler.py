"""Continuous in-flight batching scheduler — the production request path
over :class:`~deepspeed_tpu.inference.v2.InferenceEngineV2`.

FastGen-class serving loop (reference ``mii``/DeepSpeed-FastGen): an
admission queue feeds a token-budget engine that keeps a mixed batch of
prefill chunks and decode tokens in flight every iteration.  What this
layer adds over the raw engine:

* **admission with KV-pressure backpressure** — a request is admitted only
  when the block pool can hold its prompt plus decode headroom
  (``ServingConfig.kv_admit_reserve_tokens`` / ``kv_free_block_floor``);
  a bounded queue turns overload into a typed
  :class:`AdmissionQueueFull` instead of unbounded memory growth;
* **LIFO preemption-and-requeue** — when the engine raises
  :class:`~deepspeed_tpu.inference.v2.KVCacheExhausted` (a *capacity*
  signal, typed precisely so bugs don't get preempted around), the most
  recently admitted request is evicted: its blocks are flushed and it
  re-enters the admission queue at the FRONT with its full token history,
  so re-admission recomputes the KV prefix and greedy decoding continues
  token-identically;
* **prefill/decode disaggregation** — the engine packs decode tokens
  first and prefill chunks after them (``engine_v2._build_batch``); the
  scheduler books each iteration as a ``ds:serve.step`` span whose counts say what
  it held (``prefill`` / ``decode`` / ``mixed`` in the recorder's phase
  column), and fuses multi-token decode bursts when every in-flight
  sequence is in pure decode;
* **a turn that runs one step ahead of the device** — ``step`` launches
  engine step n+1 before it fetches step n's tokens
  (``launch_step`` / ``collect_step``), so the host's turn (callbacks,
  admission, the batch builder, the launch) runs beside a step and not
  between two; the decode rows' ids are taken on the device, an end by EOS
  is found one step late and its stale row dropped, and where the host
  draws the tokens the same loop collects before it launches;
* **streaming** — per-token ``on_token(token, done)`` callbacks as tokens
  are produced, not when the request completes;
* **observability + health** — per-request TTFT/TBT histograms,
  queue-depth/KV-occupancy/preemption gauges on the PR 6 telemetry spine,
  and a PR 3 watchdog heartbeat per scheduler step for replica health.
"""

import os
import time
from collections import deque

from .. import telemetry
from ..telemetry import names
from ..elasticity.watchdog import HEARTBEAT_DIR_ENV, HeartbeatWriter
from ..inference.v2.ragged import KVCacheExhausted
from ..utils.logging import logger
from .config import ServingConfig
from .request import Request, RequestState


class AdmissionQueueFull(RuntimeError):
    """The bounded admission queue rejected a submit — caller-visible
    backpressure (shed load upstream or retry later)."""


class ServingScheduler:
    """Drives one :class:`InferenceEngineV2` as a continuously batched
    serving replica.  Single-threaded by design: ``submit`` enqueues,
    ``step`` runs one engine iteration, ``drain``/``serve`` loop for you —
    a thread or asyncio wrapper owns the loop in a real deployment (the
    engine is synchronous per step, see ``engine_v2.py`` module docstring).
    """

    def __init__(self, engine, config=None, clock=time.perf_counter):
        if config is None:
            config = ServingConfig()
        elif isinstance(config, dict):
            config = ServingConfig(**config)
        self.engine = engine
        self.config = config
        self._clock = clock
        self._queue = deque()          # Request admission queue (FIFO)
        self._running = {}             # uid -> Request (admitted, holds KV)
        self._all = {}                 # uid -> Request (every submit)
        self._next_uid = 0
        self._admit_ticket = 0         # LIFO preemption key source
        self._step_index = 0
        self.preemptions = 0
        self.completed = 0
        self.tokens_generated = 0
        self.peak_running = 0          # max concurrently admitted sequences
        self.steps_launched_ahead = 0  # engine steps launched on top of one
        self.bursts_of_one = 0         # decode-only turns of ONE iteration
        # (LaunchedStep, its launch time): given to the device, its tokens
        # not yet fetched; at most one between turns
        self._in_flight = None
        self._t_collected = 0.0        # when the newest fetch returned
        # in-flight cap: the engine has max_seqs slots, slot 0 reserved
        self._max_concurrent = min(
            int(config.max_concurrent),
            engine.state_manager.max_seqs - 1)
        hb_dir = config.heartbeat_dir or os.environ.get(HEARTBEAT_DIR_ENV)
        self._heartbeat = HeartbeatWriter(
            hb_dir, rank=config.heartbeat_rank) if hb_dir else None

    # ---------------------------------------------------------------- submit
    def submit(self, prompt, max_new_tokens=32, eos_token_id=None,
               on_token=None, uid=None):
        """Queue a request; returns its uid.  Raises
        :class:`AdmissionQueueFull` when the bounded queue is at depth."""
        depth = self.config.max_queue_depth
        if depth and len(self._queue) >= depth:
            raise AdmissionQueueFull(
                f"admission queue at max_queue_depth={depth} "
                f"({len(self._running)} running) — shed load or retry")
        if uid is None:
            uid = self._next_uid
        if isinstance(uid, int):
            # explicit uids may be any hashable the engine accepts; only
            # ints advance the auto-uid counter
            self._next_uid = max(self._next_uid, uid + 1)
        if uid in self._all and self._all[uid].state is not RequestState.DONE:
            raise ValueError(f"uid {uid!r} is already live "
                             f"({self._all[uid].state.name})")
        req = Request(uid=uid, prompt=[int(t) for t in prompt],
                      max_new_tokens=int(max_new_tokens),
                      eos_token_id=eos_token_id, on_token=on_token,
                      t_submit=self._clock())
        self._all[uid] = req
        self._queue.append(req)
        if telemetry.enabled:
            telemetry.counter("serving/requests_submitted",
                              help="requests accepted into the admission "
                              "queue").inc()
        return uid

    def query(self, uid):
        """The :class:`Request` record (live or finished) for ``uid``."""
        return self._all.get(uid)

    # ------------------------------------------------------------- admission
    def _admit_blocks_needed(self, req):
        """Blocks the admission gate charges a request for: its (resume)
        prompt plus decode headroom."""
        reserve = self.config.kv_admit_reserve_tokens
        if reserve is None:
            reserve = self.engine.kv_cache.block_size   # one decode block
        return self.engine.kv_cache.peak_blocks_for(
            len(req.resume_tokens) + int(reserve))

    def _outstanding_claims(self):
        """Blocks the already-running sequences are still expected to take
        from the pool (their token history + decode reserve, minus what
        they physically hold) — the engine only materializes blocks at
        schedule time, so the admission gate must count claims, not just
        the instantaneous free list."""
        sm = self.engine.state_manager
        reserve = self.config.kv_admit_reserve_tokens
        if reserve is None:
            reserve = self.engine.kv_cache.block_size
        total = 0
        for uid in self._running:
            seq = sm.get_sequence(uid)
            total += max(0, self.engine.kv_cache.peak_blocks_for(
                len(seq.tokens) + seq.owed + int(reserve),
                start=seq.seen_tokens) - len(seq.blocks))
        return total

    def _admit(self):
        sm = self.engine.state_manager
        while self._queue and len(self._running) < self._max_concurrent:
            req = self._queue[0]
            need = self._admit_blocks_needed(req)
            free = (sm.free_blocks - int(self.config.kv_free_block_floor)
                    - self._outstanding_claims())
            if self._running and need > free:
                # KV pressure: hold admission until blocks free up.  With
                # NOTHING running the head request is admitted regardless —
                # chunked prefill + the engine's deferral can still serve a
                # prompt bigger than the instantaneous free pool, and an
                # impossible request must fail loudly, not deadlock quietly.
                break
            self._queue.popleft()
            self.engine.put([req.uid], [req.resume_tokens])
            req.transition(RequestState.PREFILL)
            req.t_admit = self._clock()
            req.admit_order = self._admit_ticket
            self._admit_ticket += 1
            self._running[req.uid] = req
            self.peak_running = max(self.peak_running, len(self._running))
            telemetry.mark(names.SERVE_ADMITTED, uid=req.uid)
            if telemetry.enabled:
                telemetry.counter("serving/requests_admitted",
                                  help="admission-queue → engine "
                                  "transitions (re-admissions included)"
                                  ).inc()

    # ------------------------------------------------------------ preemption
    def _preempt_one(self):
        """Evict the most recently admitted request (LIFO) and requeue it
        at the FRONT of the admission queue with its full token history.
        Returns False when there is nothing sensible to evict (≤1 running —
        evicting the only runner cannot free enough to run it)."""
        if len(self._running) <= 1:
            return False
        victim = max(self._running.values(), key=lambda r: r.admit_order)
        self.engine.flush([victim.uid])
        del self._running[victim.uid]
        victim.transition(RequestState.EVICTED)
        victim.preemptions += 1
        self.preemptions += 1
        victim.transition(RequestState.QUEUED)
        self._queue.appendleft(victim)
        telemetry.mark(names.SERVE_PREEMPTED, uid=victim.uid)
        logger.info(
            "serving: preempted uid %s (%d produced, %d prompt tokens) "
            "under KV pressure — requeued at front", victim.uid,
            len(victim.produced), len(victim.prompt))
        if telemetry.enabled:
            telemetry.counter("serving/preemptions",
                              help="LIFO evictions under KV pressure").inc()
        return True

    # ----------------------------------------------------------------- steps
    def _launch_burst(self):
        """Launch a fused decode when EVERY in-flight sequence is in pure
        decode (same eligibility as ``generate``'s burst path): such a turn
        never runs the budget-wide ragged step, a least remainder of one is a
        burst of ONE iteration (``engine.min_burst``).  ``k`` is at most the
        least remainder: no row ends inside a burst.  Eligibility and ``k``
        are counts: a sequence's one pending token may be the one the step
        in flight is choosing.  Returns the engine's :class:`LaunchedStep` or
        None (ineligible / pool too tight)."""
        cap = int(self.engine._config.decode_burst or 0)
        if cap < 2 or not self._running:
            return None
        cfg = self.config
        if cfg.do_sample and not (
                self.engine._config.decode_burst_sampling
                and cfg.seed is not None):
            return None   # host-RNG sampling keeps the per-step loop
        sm = self.engine.state_manager
        k, uids = cap, []
        for req in self._running.values():
            seq = sm.get_sequence(req.uid)
            if seq.done:
                continue    # its last token is in flight: no further row
            if seq.n_pending != 1:
                return None
            k = min(k, req.remaining_tokens - seq.owed)
            uids.append(req.uid)
        if not uids:
            return None
        return self.engine.launch_burst(
            uids, max_tokens=k, do_sample=cfg.do_sample,
            temperature=cfg.temperature, top_k=cfg.top_k, top_p=cfg.top_p,
            rng=cfg.seed)

    def step(self):
        """One scheduler turn: admit → build and LAUNCH the next engine step
        (preempting under KV exhaustion) → collect the step launched in the
        turn before → stream its tokens.  The host runs one step ahead of
        the device, which then always holds a queued program when the
        running one ends.  Returns {uid: [tokens]} streamed in this turn
        (empty when idle, and in the first turn after an idle scheduler).

        What runs ahead follows from where tokens are chosen.  Chosen on
        the device (greedy; a burst's device-PRNG sampling), the next step
        takes them there (``engine_v2._take_chosen``) and the host fetches
        them a turn later.  Drawn by the host (``do_sample`` through the
        ragged step), a step is collected in the turn that launched it, and
        nothing is in flight while the host draws.  What cannot be known
        ahead is handled one step late, never guessed: a request that ends
        by EOS has one stale row in the step in flight, whose token is
        dropped; one that ends by length gets no row past its last token.

        A working turn is one ``ds:serve.step`` span in any profiler capture
        (``telemetry/names.py``), with the children admit / build_batch /
        launch / fetch / dispatch and, as its counts, what the engine step
        LAUNCHED in it held (``InferenceEngineV2.last_step_counts``) and
        ``launched_ahead``; its fetch is the wait for the step before.  A
        launched step's id (``LaunchedStep.index``) is the ``launch`` of its
        launch, fetch and dispatch spans, whichever turns they fall in; the
        turn carries it as ``launch`` and the id of the step it collected as
        ``fetched``."""
        self._step_index += 1
        if self._heartbeat is not None:
            self._heartbeat.beat(self._step_index)
        if self.idle:
            self._export_gauges()
            return {}
        if telemetry.enabled:
            telemetry.begin_step(self._step_index)
        with telemetry.scope(names.SERVE_STEP, cat="serve",
                             step=self._step_index) as span:
            with telemetry.scope(names.SERVE_ADMIT):
                self._admit()
            emitted = {}
            if self._running or self._in_flight is not None:
                self._run_step(span, emitted)
        self._export_gauges(n_tokens=sum(len(v) for v in emitted.values()))
        return emitted

    def _run_step(self, span, emitted):
        cfg = self.config
        preempts = 0
        ids = {}        # the turn's launch / fetched (names.SERVE_STEP_IDS)
        while True:
            ahead = self._in_flight is not None
            try:
                step = self._launch_burst()
                if step is None and ahead and cfg.do_sample:
                    # the host draws this step's tokens: today's draws in
                    # today's order, so nothing is in flight while it does
                    self._collect(emitted, ids)
                    continue
                if step is None:
                    step = self.engine.launch_step(
                        do_sample=cfg.do_sample,
                        temperature=cfg.temperature, top_k=cfg.top_k,
                        top_p=cfg.top_p, rng=cfg.seed)
                break
            except KVCacheExhausted as e:
                if ahead:
                    # what is in flight may end requests and return their
                    # blocks, and a victim never has an unfetched token
                    self._collect(emitted, ids)
                    continue
                preempts += 1
                if preempts > int(self.config.max_preemptions_per_step) \
                        or not self._preempt_one():
                    raise KVCacheExhausted(
                        e.wanted_blocks, e.free_blocks,
                        detail="not recoverable by preemption — the "
                        "request needs more blocks than the pool holds "
                        "(raise state_manager.num_blocks or lower "
                        "max_context)") from e
        t_launched = self._clock()
        counts = step.counts if step is not None else {}
        held = dict(running=len(self._running), queued=len(self._queue))
        if ahead:
            self._collect(emitted, ids)     # the step launched the turn before
        if step is not None:
            ids[names.COUNT_LAUNCH] = step.index
            self._in_flight = (step, t_launched)
            self.steps_launched_ahead += ahead
            if ahead and telemetry.enabled:
                telemetry.counter("serving/steps_launched_ahead",
                                  help="engine steps launched while the one "
                                  "before was still unfetched").inc()
            if step.burst_k == 1:
                self.bursts_of_one += 1
                if telemetry.enabled:
                    telemetry.counter("serving/bursts_of_one",
                                      help="decode-only turns run as a burst "
                                      "of one iteration, not as a ragged step "
                                      "at the full token budget").inc()
            for seq in step.seqs:
                req = self._running.get(seq.uid)
                if req is not None and req.remaining_tokens <= seq.owed:
                    # ends by length with the tokens in flight (a count, not
                    # a guess): no row past its last token
                    seq.done = True
        if step is not None and not (
                step.sample is None and self.engine.launches_programs
                and self._has_more_to_launch()):
            # nothing can run ahead of it (the host draws its tokens), or
            # nothing is left to: the turn collects its own step
            self._collect(emitted, ids)
        # the recorder's phase column keeps its prefill|decode|mixed name,
        # now derived from what the step really held
        phase = ("mixed" if counts.get("prefill_tokens")
                 and counts.get("decode_tokens") else
                 "prefill" if counts.get("prefill_tokens") else "decode")
        span.set(phase=phase, preempts=preempts, launched_ahead=int(ahead),
                 **held, **counts, **ids)

    def _has_more_to_launch(self):
        """Whether a next turn would find rows to run: a running request
        that does not end with the tokens in flight.  (A queued one waits
        for what the running ones hold: the sooner they are collected, the
        sooner it is admitted.)"""
        sm = self.engine.state_manager
        return any(not sm.get_sequence(uid).done for uid in self._running)

    def _collect(self, emitted, ids):
        """Fetch the tokens of the step in flight and stream them; adds to
        ``emitted`` and names the step in ``ids`` as the turn's
        ``fetched``."""
        (step, t_launch), self._in_flight = self._in_flight, None
        ids[names.COUNT_FETCHED] = step.index
        results = self.engine.collect_step(step)
        # the device took the step up when it was launched, or when the one
        # before it ended: what _dispatch amortizes a burst's tokens over
        t_start, self._t_collected = max(t_launch, self._t_collected), \
            self._clock()
        with telemetry.scope(names.SERVE_DISPATCH, launch=step.index):
            for uid, toks in self._dispatch(results, t_start,
                                            step.index).items():
                emitted.setdefault(uid, []).extend(toks)

    def _dispatch(self, results, t_launch, launch):
        """Book engine output into request records: streaming callbacks,
        lifecycle transitions, completion + immediate flush (blocks return
        to the pool the moment a request finishes).  Burst results arrive
        as a list, k-at-a-time from one engine call (a list of one from a
        burst of one); their timestamps interpolate over
        [t_launch, now] so the TBT accounting reflects per-token cost, not
        k−1 fabricated zero gaps plus one burst-sized one.  ``launch``: the
        id of the engine step whose output this is, on the
        ``ds:serve.finished`` of a request it ends."""
        now = self._clock()
        sm = self.engine.state_manager
        emitted = {}
        for uid, toks in results.items():
            req = self._running.get(uid)
            if req is None:      # flushed between schedule and dispatch
                continue
            burst = not isinstance(toks, int)
            if not burst:
                toks = [toks]
            out = emitted.setdefault(uid, [])
            for i, tok in enumerate(toks):
                t_tok = (now if not burst else
                         t_launch + (i + 1) * (now - t_launch) / len(toks))
                done = ((req.eos_token_id is not None
                         and tok == req.eos_token_id)
                        or len(req.produced) + 1 >= req.max_new_tokens)
                if req.state is RequestState.PREFILL:
                    req.transition(RequestState.DECODE)
                req.record_token(tok, t_tok, done)
                out.append(int(tok))
                if telemetry.enabled:
                    telemetry.counter("serving/tokens_generated",
                                      help="tokens streamed to callers"
                                      ).inc()
                self.tokens_generated += 1
                if done:
                    # overshoot past EOS inside a burst window is garbage
                    # the flush drops; ``produced`` truncates exactly
                    req.transition(RequestState.DONE)
                    sm.get_sequence(uid).done = True
                    self.engine.flush([uid])
                    del self._running[uid]
                    self.completed += 1
                    telemetry.mark(names.SERVE_FINISHED, uid=uid,
                                   tokens=len(req.produced), launch=launch)
                    if telemetry.enabled:
                        telemetry.counter("serving/requests_completed",
                                          help="requests finished (EOS or "
                                          "max_new_tokens)").inc()
                        if req.ttft is not None:
                            telemetry.observe("serving/ttft_seconds",
                                              req.ttft,
                                              help="submit → first token")
                        for gap in req.token_gaps:
                            telemetry.observe("serving/tbt_seconds", gap,
                                              help="decode inter-token gap")
                    break
                if not burst:
                    # per-step decode feedback (the burst path already
                    # extended the engine-side token history on device)
                    sm.get_sequence(uid).tokens.append(int(tok))
        return emitted

    def _export_gauges(self, n_tokens=0):
        if not telemetry.enabled:
            return
        sm = self.engine.state_manager
        total = self.engine.kv_cache.num_blocks - 1   # minus garbage block
        used = total - sm.free_blocks
        telemetry.gauge("serving/queue_depth",
                        help="requests waiting for admission"
                        ).set(len(self._queue))
        telemetry.gauge("serving/running_sequences",
                        help="requests holding KV blocks"
                        ).set(len(self._running))
        telemetry.gauge("serving/kv_free_blocks").set(sm.free_blocks)
        telemetry.gauge("serving/kv_occupancy_frac",
                        help="used / usable KV blocks"
                        ).set(used / total if total else 0.0)
        if telemetry.get_recorder() is not None:
            try:
                from ..runtime.utils import memory_usage_snapshot
                snap = memory_usage_snapshot()
                telemetry.record_hbm(
                    {k: snap[k] for k in ("live_bytes", "peak_bytes",
                                          "limit_bytes")})
            except Exception:
                pass   # telemetry must never kill a serving step
            telemetry.end_step(metrics={
                "tokens": n_tokens,
                "serve_running": len(self._running),
                "serve_queue_depth": len(self._queue),
            })

    # ----------------------------------------------------------- convenience
    @property
    def idle(self):
        """No queued and no running work, and no step in flight."""
        return not self._queue and not self._running \
            and self._in_flight is None

    def drain(self, max_steps=100_000):
        """Step until every submitted request completes."""
        steps = 0
        while not self.idle:
            self.step()
            steps += 1
            if steps >= max_steps:
                raise RuntimeError(
                    f"serving drain did not converge in {max_steps} steps "
                    f"({len(self._queue)} queued, {len(self._running)} "
                    "running)")
        return steps

    def serve(self, prompts, max_new_tokens=32, eos_token_id=None):
        """Batch convenience (tests/bench): submit all, drain, return the
        produced tokens in submit order."""
        uids = [self.submit(p, max_new_tokens=max_new_tokens,
                            eos_token_id=eos_token_id) for p in prompts]
        self.drain()
        return [self._all[u].produced for u in uids]


def build_serving_engine(model, params=None, engine_config=None,
                         serving_config=None):
    """One-call replica: ``InferenceEngineV2`` + :class:`ServingScheduler`.
    ``engine_config`` may carry ``kv_cache_dtype: "int8"|"fp8"`` for the
    quantized paged-KV mode."""
    from ..inference.v2 import InferenceEngineV2
    engine = InferenceEngineV2(model, params=params, config=engine_config)
    return ServingScheduler(engine, config=serving_config)
