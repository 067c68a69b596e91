"""LLMEngine — the TPU-native generation front end.

The user surface of the serving subsystem (ROADMAP item 1): a
causal LM plus a paged KV cache, a continuous-batching scheduler,
and two compiled programs — per-bucket prefill and ONE fixed-shape
decode step covering all `max_batch` slots — that together serve
many concurrent mixed-length requests. What the engine knows of the
model (its parameters, the cache's pools and row widths, the
programs) it reads from the RUNNER the model's type selects
(`model_runner.runner_for`: GPT-2, `mla_runner` for a model with
latent-attention layers, `state_runner` for one that keeps a state
of fixed size a sequence beside its keys and values: that state
lives in per-SLOT arrays behind the paged pools, `cache.pools`
carries both through every program, and a prefill is told the slot
it fills); a runner without a verify or tail program makes
`spec_k > 1` / `prefix_cache` raise at construction:

    engine = LLMEngine(model)
    engine.add_request([1, 2, 3], SamplingParams(max_new_tokens=8),
                       on_token=stream_cb)          # streaming
    outs = engine.generate([[1, 2, 3], [7, 8]])     # run-to-drain

Per engine `step()`: admit+prefill whatever the scheduler lets in,
grow block tables across block boundaries (evicting under pool
pressure), then ONE decode dispatch for the whole batch — inactive
slots ride along pointed at the NULL block. Stop conditions
(eos/stop ids/max_new_tokens/max_seq_len) apply host-side on the
returned tokens; finished requests free their blocks before the next
admission pass.

Compiled-step contract: every program is a `jit.Program` over the
runner's step with the pools DONATED (the engine re-adopts the
returned pools each dispatch), named `serve_decode:<Model>`,
`serve_prefill:<Model>[#n]`, ...; the Program counts its dispatches,
spans its first one `compile/<name>` and records its footprint. On
an accelerator that compile goes through JAX's persistent
compilation cache (`jit.persistent_cache`), so a serving replica
restarting against the same model and pool loads its programs
instead of compiling them. Prefill compiles once per block-rounded
prompt-length bucket, so prompt-length cardinality is
`max_seq_len / block_size`, not `max_seq_len`.

Failure path: a RESOURCE_EXHAUSTED dispatch (real, or injected at
the `serve_decode` chaos site) evicts the youngest request and
retries — serving degrades to a smaller batch instead of dying.

Speculative decoding (`spec_k`/`PADDLE_SERVE_SPEC_K` > 1): a small
DRAFT model — by default the target's first `draft_layers` blocks
sharing its embeddings and head (`model_runner.draft_params`) —
proposes k-1 tokens with k cheap batched dispatches against its own
twin pools, then ONE fixed-shape `verify_step` dispatch runs the
target over all k slots (pending token + proposals) via the
multi-query paged-attention kernel. The engine emits the longest
prefix of proposals that AGREE with the target's own position-seeded
choices, plus the first disagreeing target token — rejection-free
greedy verification: every emitted token is the target's own choice
for its position, so the stream is token-identical to k=1 at ANY
temperature, and a bad draft only costs speed (1..k tokens per
verify). `serve/spec/{proposed,accepted}` + `serve/hist/accept_len`
price the win. Spec paths dispatch through block tables widened by
one guaranteed-NULL column so near-`max_seq_len` overflow slots
clamp their garbage writes into the null block.

Prefix caching (`prefix_cache`/`PADDLE_SERVE_PREFIX_CACHE`): full
immutable prompt blocks are content-hashed after prefill; a later
request whose prompt chains onto published blocks admits with those
blocks mapped copy-on-write and prefills ONLY the uncached tail
(`prefill_tail_step`), with `serve/prefix/{hits,blocks_shared,
prefill_tokens_saved}` counting the saved work. Both features are
OFF by default and their disarmed paths leave the k=1 decode/prefill
programs untouched (the HLO-identity bench contract).

Lifecycle (ISSUE 13 — the failure-policy ring):

  * `drain(timeout_s)` — stop admitting (new intake sheds with
    `EngineOverloaded`), run RUNNING requests to completion, then
    EXPORT whatever is left (prompt + generated-so-far + sampling)
    for token-exact re-admission elsewhere (`import_request` —
    position-keyed sampling seeds make replay deterministic on ANY
    engine). `serve/drains`, `serve_drain` chaos site + flight span.
  * `generate(timeout_s=)` — raises `EngineTimeout` with the engine
    state summary attached instead of hanging to drain forever. The
    bound is judged BETWEEN dispatches; a dispatch wedged inside XLA
    is the watchdog's jurisdiction, which is why
  * `arm_incident_export()` registers a PR-3/6 incident hook: a
    watchdog-detected wedge (stuck `serve_decode` span) fences the
    engine and performs an emergency drain-and-export — in-flight
    requests become `emergency_exports` a router/operator replays on
    a healthy replica instead of dying with the wedged one.
  * A FENCED engine (`_fenced`) no-ops `step()`: after a failover
    exported its requests, a zombie thread waking from the wedge
    cannot double-serve them.

Telemetry: `serve/{requests,tokens,prefill_us,decode_us,evictions,
queue_depth,drains,kv_blocks/*}` counters plus `serve_prefill`/
`serve_decode`/`serve_drain` flight spans, all through the PR-1/PR-3
monitor hub. `heartbeat` is stamped at every completed dispatch —
the router's per-replica health signal.
"""
from __future__ import annotations

import contextlib
import functools
import math
import time

import numpy as np

from ...core import monitor as _cmon
from ...jit.program import Program, arm_compile_cache, specialised
from ...monitor import chaos as _chaos
from ...monitor import flight as _flight
from ...monitor import memory as _memory
from ...monitor import perf as _perf
from ...monitor import sanitize as _san
from ...monitor import trace as _trace
from . import model_runner as _mr
from .kv_cache import (NULL_BLOCK, PagedKVCache, env_max_batch,
                       env_prefix_cache, env_spec_draft, env_spec_k)
from .scheduler import (EngineOverloaded, EXPORTED, FINISHED,
                        Request, SamplingParams, Scheduler)

__all__ = ["LLMEngine", "EngineTimeout"]

# kind -> (the runner's step, the program's label, the argument that
# holds the pools it donates, whether it takes the kernel switches).
# The draft model runs the same steps over its own twin pools.
_PROGRAM_KINDS = {
    "decode": ("decode_step", "serve_decode", 3, True),
    "prefill": ("prefill_step", "serve_prefill", 3, False),
    "prefill_tail": ("prefill_tail_step", "serve_prefill_tail", 4, False),
    "verify": ("verify_step", "serve_verify", 3, True),
    "draft": ("decode_step", "serve_draft", 3, True),
    "draft_prefill": ("prefill_step", "serve_draft_prefill", 3, False),
    "draft_tail": ("prefill_tail_step", "serve_draft_prefill_tail", 4,
                   False),
}


class _Programs:
    """The engine's compiled programs, `jit.Program`s by (kind,
    width), each built where first needed from the runner's step of
    its kind. `width` is the padded length of a prefill bucket or of
    a prefix-cache tail (each its own compiled program); None for the
    fixed-shape ones. Names: `<label>:<Model>`; a prefill bucket
    after the first `#n` in the order first run, a tail `@<width>`."""

    def __init__(self, runner, model_name, **kernel):
        self._runner = runner
        self._model = model_name
        self._kernel = kernel
        self._table = {}

    def label(self, kind):
        return f"{_PROGRAM_KINDS[kind][1]}:{self._model}"

    def __call__(self, kind, width=None):
        prog = self._table.get((kind, width))
        if prog is None:
            step, _, pools, kernel = _PROGRAM_KINDS[kind]
            kw = self._kernel if kernel else {
                "block_size": self._kernel["block_size"]}
            name = self.label(kind)
            if kind.endswith("tail"):
                name = f"{name}@{width}"
            elif width is not None:
                name = specialised(name, sum(
                    k == kind for k, _ in self._table))
            prog = self._table[kind, width] = Program(
                functools.partial(getattr(self._runner, step), **kw),
                name, donate_argnums=(pools,))
        return prog


class EngineTimeout(TimeoutError):
    """`generate(timeout_s=)` ran out of budget with work still live.
    Carries the engine's state summary in `.engine_state` — what was
    waiting/running and how stale the heartbeat was, so the caller
    (or the incident report) sees WHERE generation stood instead of
    a bare hang-turned-timeout."""

    def __init__(self, msg, engine_state=None):
        super().__init__(msg)
        self.engine_state = engine_state or {}


class LLMEngine:
    """Continuous-batching generation engine over one causal LM."""

    def __init__(self, model, max_batch=None, block_size=None,
                 num_blocks=None, pool_bytes=None, dtype=None,
                 static_batching=False, use_kernel=None,
                 max_queue=None, spec_k=None,
                 draft_layers=None, prefix_cache=None,
                 max_seq_len=None, run_ahead=False):
        arm_compile_cache()
        # everything the engine knows of the model it reads from the
        # runner its type selects (model_runner.runner_for)
        self.runner = runner = _mr.runner_for(model)
        self.params, self.config = runner.params, runner.config
        cfg = self.config
        self.max_batch = int(max_batch or env_max_batch())
        # a deployment's limit on prompt + answer, at most the
        # model's positions (which a rotary model publishes in the
        # hundreds of thousands: table widths follow THIS number)
        self.max_seq_len = int(min(max_seq_len or cfg.max_seq_len,
                                   cfg.max_seq_len))
        # speculative-decode width: 1 = off (the verify kernel
        # unrolls its query slots, so k is capped at 8)
        self.spec_k = max(1, min(
            8, int(spec_k if spec_k is not None else env_spec_k())))
        if self.spec_k > 1:
            n_draft = int(draft_layers if draft_layers is not None
                          else env_spec_draft())
            if n_draft <= 0:         # auto: half the target's depth
                n_draft = max(1, cfg.num_layers // 2)
            self.draft_layers = min(n_draft, cfg.num_layers)
        else:
            self.draft_layers = 0
        self.prefix_cache = bool(
            prefix_cache if prefix_cache is not None
            else env_prefix_cache())
        # a window a cache group, where the runner's model has
        # attentions of several kinds (state_runner; else one group)
        groups = getattr(runner, "cache_groups", (None,))
        if len(groups) > 1 and (self.spec_k > 1 or self.prefix_cache):
            raise NotImplementedError(
                f"spec_k={self.spec_k} / prefix_cache="
                f"{self.prefix_cache}: this model keeps window layers' "
                "rows in cache groups whose blocks are freed behind the "
                "window, so a shared prefix would lose blocks a later "
                "sharer needs and a rejected draft's rows could not be "
                "taken back; serve it with spec_k=1, prefix_cache=False")
        name = type(runner).__name__
        if self.spec_k > 1 and (runner.verify_step is None
                                or runner.draft_params is None):
            raise NotImplementedError(
                f"spec_k={self.spec_k}: {name} has no verify program "
                "or draft model; serve this model with spec_k=1")
        if self.prefix_cache and runner.prefill_tail_step is None:
            raise NotImplementedError(
                f"prefix_cache: {name} has no tail-prefill program; "
                "serve this model with prefix_cache=False")
        self.cache = PagedKVCache(
            runner.pool_layers, rows=runner.pool_rows,
            block_size=block_size, num_blocks=num_blocks,
            pool_bytes=pool_bytes, dtype=dtype,
            draft_layers=self.draft_layers,
            prefix_cache=self.prefix_cache,
            slot_state=runner.slot_state, max_batch=self.max_batch,
            groups=groups, max_seq_len=self.max_seq_len)
        self.block_size = self.cache.block_size
        # fixed table width: enough slots for a max-length sequence
        self.max_blocks_per_seq = math.ceil(
            self.max_seq_len / self.block_size)
        self.scheduler = Scheduler(self.cache, self.max_batch,
                                   self.max_seq_len,
                                   static_batching=static_batching,
                                   max_queue=max_queue,
                                   spec_tokens=self.spec_k)
        self._requests = {}          # req_id -> Request (all states)
        if use_kernel is None:
            # no switch: the runner answers from platform, mesh and
            # shape (pallas.paged_attention.paged_decode_supported);
            # an explicit use_kernel is the tests' override
            from ...incubate.nn import pallas as _pl

            use_kernel = runner.kernel_supported(self.block_size)
            self._kernel_interpret = _pl.interpret_mode()
        else:
            self._kernel_interpret = False
        self.use_kernel = bool(use_kernel)
        # the pools (one argument, a tuple) ride the layer scan's
        # carry and are only ever scattered into
        # (model_runner._scan_layers_paged), so every program donates
        # them: its output pools are its input buffers, with no
        # second copy of the pools among its temporaries
        self._programs = _Programs(
            runner, type(model).__name__, block_size=self.block_size,
            use_kernel=self.use_kernel,
            interpret=self._kernel_interpret)
        # the draft model of speculative decode (spec_k > 1 only; the
        # k=1 programs stay byte-identical either way)
        self._draft_params = None
        if self.spec_k > 1:
            self._draft_params = runner.draft_params(
                self.params, self.draft_layers)
            _cmon.stat_set("serve/spec/k", self.spec_k)
        self._steps = 0              # engine steps begun (span id)
        self._oom_streak = 0         # consecutive OOM'd dispatches
        # (signature, device arrays): the next decode step's inputs,
        # prepared while the last one ran (_prepare_ahead)
        self._ahead = None
        # run_ahead: hand the device the next decode dispatch before
        # the last one's tokens are fetched, wherever the next step
        # is certain to decode the same batch (_fetch_decode). Off
        # unless asked for: one accepted benchmark cell ends with its
        # backlog, and an engine a twentieth faster ends it before
        # that cell's profiler starts (PERF.md section 7); the
        # default turns with that cell's size.
        self.run_ahead = bool(run_ahead)
        # (signature, tokens, stats, compiled): the decode dispatch
        # handed over so (_run_ahead); the next step fetches it
        self._inflight = None
        # finished requests kept for result retrieval — bounded so a
        # long-lived replica's host memory doesn't grow with total
        # traffic (generate() releases its own as it returns)
        self._keep_finished = 256
        # -- resilience state (ISSUE 13) ------------------------------
        # stamped at every COMPLETED dispatch: the router's health
        # signal (a wedged dispatch stops the clock; an idle engine's
        # stale beat is fine — health checks gate on has_unfinished)
        self.heartbeat = time.monotonic()
        # fenced = this engine's requests were exported elsewhere; a
        # zombie thread waking from a wedge must not keep serving
        self._fenced = False
        # emergency drain-and-export landing zone (incident hook)
        self.emergency_exports = None
        self._incident_armed = False
        # live introspection (/tracez): register this engine's trace
        # spool weakly — a collected engine simply drops off the
        # page; total fallback because a debug surface must never
        # fail engine construction
        try:
            from ...monitor import server as _mserver

            _mserver.add_trace_source(self.export_traces)
        except Exception:
            pass

    # -- request intake ----------------------------------------------
    def add_request(self, prompt_ids, sampling=None, on_token=None,
                    req_id=None):
        """Queue one request; returns its id. `on_token(req, token)`
        streams every generated token as its dispatch completes. A
        FENCED engine refuses intake — its step() no-ops, so a
        queued request would silently strand forever."""
        self._check_fenced()
        req = Request(prompt_ids, sampling=sampling,
                      on_token=on_token, req_id=req_id)
        self.scheduler.add(req)
        self._requests[req.req_id] = req
        self._prune_finished()
        _cmon.stat_add("serve/requests", 1)
        return req.req_id

    def _prune_finished(self):
        """Cap retained FINISHED/ABORTED requests at
        `_keep_finished` (oldest dropped first) — results live until
        read or displaced, never forever."""
        done = [rid for rid, r in self._requests.items()
                if r.finished]
        for rid in done[:max(0, len(done) - self._keep_finished)]:
            # finished entries only: their blocks were released by
            # scheduler.finish/abort before they ever became prunable
            del self._requests[rid]  # noqa: PTA072

    def release_request(self, req_id):
        """Drop a finished request's retained record (results
        consumed). Live requests must be aborted first."""
        req = self._requests.get(req_id)
        if req is not None and req.finished:
            # finished-only guard above: blocks already released
            del self._requests[req_id]  # noqa: PTA072

    def abort_request(self, req_id):
        req = self._requests.get(req_id)
        if req is not None and not req.finished:
            self.scheduler.abort(req)

    def get_request(self, req_id):
        return self._requests[req_id]

    def has_unfinished(self):
        return self.scheduler.has_work()

    # -- the engine loop ---------------------------------------------
    def step(self):
        """One engine iteration: admissions (each prefilled, its
        first token emitted) + one decode dispatch for the running
        batch. Returns {req_id: token} emitted this step. A fenced
        engine (requests exported after a wedge/failover) no-ops —
        its tokens would double-serve requests replaying elsewhere."""
        if self._fenced:
            return {}
        self._steps += 1
        with _flight.span("serve/step", step=self._steps):
            return self._step()

    def _step(self):
        """step() inside its span `serve/step` (id `step`). Children:
        `serve/schedule` (with a `serve/prefill` for each admission),
        `serve/decode/prepare`, `serve/decode` around
        `serve/decode/enqueue` (> `serve/decode/put`, the inputs'
        transfer) and `serve/decode/fetch` (> `serve/decode/ahead`,
        the next inputs built beside the device, then
        `serve/decode/wait`, the wait alone), `serve/decode/emit`,
        and `serve/evict` where a dispatch ran out of memory. A step
        that finds its dispatch in flight (`_run_ahead`) has no
        prepare, and its `serve/decode` holds fetch > ahead, enqueue
        > put (the next dispatch), fetch > wait."""
        emitted = {}

        def _on_admit(req):
            # prefill AS each request admits — a fault later in the
            # same admission pass can't strand an admitted request
            # with never-written K/V
            self._emit(req, self._prefill(req), emitted)

        with _flight.span("serve/schedule"):
            admitted = self.scheduler.schedule(on_admit=_on_admit)
        if not admitted and not self.scheduler.running \
                and self.scheduler.waiting:
            # an idle engine that can't admit its queue head will
            # never make progress — a pool sized below one request's
            # footprint must be LOUD, not a silent spin
            head = self.scheduler.waiting[0]
            need = self.cache.blocks_needed(head.context_len,
                                            self.scheduler._lookahead)
            if need > self.cache.num_blocks - 1:
                raise RuntimeError(
                    f"KV pool too small: {head.req_id} needs {need} "
                    f"block(s) but the pool has only "
                    f"{self.cache.num_blocks - 1} usable — raise "
                    "PADDLE_SERVE_POOL_BYTES or num_blocks")
        if self.scheduler.running or self._inflight is not None:
            self._decode_batch(emitted)
        return emitted

    def generate(self, prompts, sampling=None, timeout_s=None):
        """Submit `prompts` (lists of token ids) and run the engine
        to drain; returns each prompt's generated ids, in order.

        `timeout_s` bounds the WHOLE drain: when it elapses with work
        still live, raises `EngineTimeout` carrying
        `state_summary()` instead of looping forever (a queue the
        pool can't serve, a steady stream of evict/readmit churn).
        The bound is judged between dispatches — a dispatch wedged
        INSIDE XLA is the watchdog's jurisdiction (see
        `arm_incident_export`).

        A request that EXPIRES (deadline_s) returns its partial —
        for a never-admitted request, empty — output list in place:
        deadline misses are a normal outcome under SLO load, counted
        under serve/deadline_aborts. Callers that must distinguish
        expiry per request should use add_request() + get_request()
        and read `state`."""
        ids = [self.add_request(p, sampling=sampling)
               for p in prompts]
        deadline = (time.monotonic() + timeout_s
                    if timeout_s is not None else None)
        while self.has_unfinished() and not self._fenced:
            self.step()
            if deadline is not None and self.has_unfinished() \
                    and time.monotonic() > deadline:
                raise EngineTimeout(
                    f"generate() exceeded timeout_s={timeout_s} "
                    f"with {len(self.scheduler.running)} running / "
                    f"{len(self.scheduler.waiting)} waiting",
                    engine_state=self.state_summary())
        exported = [i for i in ids
                    if self._requests[i].state == EXPORTED]
        if exported:
            # an incident hook fenced this engine mid-generate and
            # exported the work — partial outputs must not read as
            # completed generations
            raise EngineTimeout(
                f"engine fenced mid-generate: {len(exported)} "
                "request(s) were emergency-exported (see "
                "emergency_exports) — replay them on a healthy "
                "engine", engine_state=self.state_summary())
        outs = [self._requests[i].output_ids for i in ids]
        for i in ids:                # results consumed: release
            self.release_request(i)
        return outs

    # -- prefill -----------------------------------------------------
    def _prefill(self, req):
        """Causal forward over the (re)admitted request's context —
        prompt plus any generation an eviction preserved — writing
        its K/V and sampling the next token. With prefix caching on
        and a cache hit at admission, only the uncached TAIL runs
        (`_prefill_tail`); either way the request's full immutable
        blocks are published for later sharers, and with speculation
        armed the draft model prefills its twin pools over the same
        table."""
        import jax.numpy as jnp

        ctx = req.prompt_ids + req.output_ids
        plen = len(ctx)
        if self.prefix_cache and req.cached_tokens:
            return self._prefill_tail(req, ctx, plen)
        padded = self.cache.blocks_for_tokens(plen) * self.block_size
        ids = np.zeros((1, padded), np.int32)
        ids[0, :plen] = ctx
        table = self.cache.block_table(req.req_id,
                                       self.max_blocks_per_seq)
        s = req.sampling
        prefill = self._programs("prefill", padded)
        # a runner with per-slot state is told the slot this request
        # will decode in: its prefill writes the state there
        slot = ()
        if self.cache.slot_state:
            slot = (np.int32(req.slot),)
            _cmon.stat_add("serve/state/slot_writes", 1)
        t0 = time.perf_counter()
        with _flight.in_flight("serve_prefill", req.req_id,
                               req=req.trace_id or req.req_id,
                               padded=padded, tokens=plen), \
                prefill.dispatch():
            tok, self.cache.pools, stats = prefill.bind(
                self.params, jnp.asarray(ids), np.int32(plen),
                self.cache.pools, jnp.asarray(table),
                np.float32(s.temperature), np.int32(s.top_k),
                np.uint32(_mr.seed_for(s.seed, plen)), *slot)()
            tok = int(tok)
            self._count_stats(stats, padded)
            if self._draft_params is not None:
                draft = self._programs("draft_prefill", padded)
                with draft.dispatch():
                    _, self.cache.draft_pools, _ = draft.bind(
                        self._draft_params, jnp.asarray(ids),
                        np.int32(plen), self.cache.draft_pools,
                        jnp.asarray(table),
                        np.float32(0.0), np.int32(0), np.uint32(0))()
                req._spec_gap = False
        dur_us = int((time.perf_counter() - t0) * 1e6)
        _cmon.stat_add("serve/prefill_us", dur_us)
        # a bucket's first dispatch compiled: that sample stays out
        # of the dispatch histogram (it would poison the p99) but is
        # still counted in serve/prefill_us
        if not prefill.compiled() and _perf.dispatch_timing_enabled():
            # `int(tok)` above already blocked on the dispatch —
            # this wall time is device time, not the enqueue
            _perf.observe_dispatch(self._programs.label("prefill"),
                                   dur_us)
        prefill.capture()
        if _trace._armed:
            # replayed > 0 marks an eviction-recompute or a failover/
            # drain replay leg (the preserved output_ids re-prefill)
            _trace.note(req, "prefill", tokens=plen, dur_us=dur_us,
                        replayed=len(req.output_ids))
        self.cache.register_prefix(req.req_id, ctx)
        self.heartbeat = time.monotonic()
        return tok

    def _prefill_tail(self, req, ctx, plen):
        """Prefix-cache hit: the leading `req.cached_tokens` (a block
        multiple, capped below plen) already sit in shared blocks —
        compile/dispatch over the TAIL only. The tail writes land
        exclusively in the request's private blocks (admission caps
        sharing below the full context, so the tail is never empty);
        with the serving sanitizer armed, `check_cow` proves it."""
        import jax.numpy as jnp

        cached = req.cached_tokens
        tail = ctx[cached:]
        t_pad = (self.cache.blocks_for_tokens(plen) * self.block_size
                 - cached)
        ids = np.zeros((1, t_pad), np.int32)
        ids[0, :len(tail)] = tail
        table = self.cache.block_table(req.req_id,
                                       self.max_blocks_per_seq)
        if getattr(_san, "_serving", False):
            private = self.cache.allocator.owned(
                req.req_id)[cached // self.block_size:]
            for bid in private:
                self.cache.allocator.check_cow(bid)
        s = req.sampling
        prefill = self._programs("prefill_tail", t_pad)
        t0 = time.perf_counter()
        with _flight.in_flight("serve_prefill", req.req_id,
                               req=req.trace_id or req.req_id,
                               padded=t_pad, tokens=len(tail),
                               cached=cached), \
                prefill.dispatch():
            tok, self.cache.pools, stats = prefill.bind(
                self.params, jnp.asarray(ids), np.int32(cached),
                np.int32(plen), self.cache.pools,
                jnp.asarray(table), np.float32(s.temperature),
                np.int32(s.top_k),
                np.uint32(_mr.seed_for(s.seed, plen)))()
            tok = int(tok)
            self._count_stats(stats, t_pad)
            if self._draft_params is not None:
                draft = self._programs("draft_tail", t_pad)
                with draft.dispatch():
                    _, self.cache.draft_pools, _ = draft.bind(
                        self._draft_params, jnp.asarray(ids),
                        np.int32(cached), np.int32(plen),
                        self.cache.draft_pools,
                        jnp.asarray(table), np.float32(0.0),
                        np.int32(0), np.uint32(0))()
                req._spec_gap = False
        dur_us = int((time.perf_counter() - t0) * 1e6)
        _cmon.stat_add("serve/prefill_us", dur_us)
        _cmon.stat_add("serve/prefix/prefill_tokens_saved", cached)
        prefill.capture()
        if _trace._armed:
            _trace.note(req, "prefill", tokens=len(tail),
                        cached=cached, dur_us=dur_us,
                        replayed=len(req.output_ids))
        self.cache.register_prefix(req.req_id, ctx)
        self.heartbeat = time.monotonic()
        return tok

    # -- decode ------------------------------------------------------
    def _batch_arrays(self, ahead=0):
        """Fixed-shape [max_batch] dispatch inputs; inactive slots
        decode garbage against the NULL block and are dropped on the
        host side. `ahead=1`: the inputs of the step AFTER the one in
        flight, every context one token longer; `ids`, the one input
        that waits for that step's tokens, stays zero."""
        b = self.max_batch
        ids = np.zeros((b,), np.int32)
        pos = np.zeros((b,), np.int32)
        tables = np.full((b, self.max_blocks_per_seq), NULL_BLOCK,
                         np.int32)
        if len(self.cache.groups) > 1:   # a table a cache group
            tables = np.repeat(tables[None], len(self.cache.groups), 0)
        lens = np.ones((b,), np.int32)
        temp = np.zeros((b,), np.float32)
        topk = np.zeros((b,), np.int32)
        seeds = np.zeros((b,), np.uint32)
        for slot, req in self.scheduler.running.items():
            # the last token and the length, not the joined lists:
            # this runs every step for every slot, whatever the
            # context's length
            n = req.context_len + ahead
            if not ahead:
                ids[slot] = (req.output_ids or req.prompt_ids)[-1]
            pos[slot] = n - 1
            tables[..., slot, :] = self.cache.block_table(
                req.req_id, self.max_blocks_per_seq)
            lens[slot] = n
            s = req.sampling
            temp[slot] = s.temperature
            topk[slot] = s.top_k
            seeds[slot] = _mr.seed_for(s.seed, n)
        return ids, pos, tables, lens, temp, topk, seeds

    def _signature(self, ahead=0):
        """What the dispatch inputs are a function of: the slots'
        requests, their lengths (`ahead` tokens from now) and the
        pool's numbering of blocks."""
        return (self.cache.epoch,) + tuple(
            (slot, req.req_id, req.context_len + ahead)
            for slot, req in self.scheduler.running.items())

    def _prepare_ahead(self):
        """Called with a decode dispatch in flight: builds the NEXT
        step's inputs, all but `ids`, and starts their transfer, so
        that the device waits for neither between two steps. Nothing
        here depends on the tokens in flight: every running request
        will be one token longer. Skipped (the next step prepares as
        ever) when a request is certain to end with this token, or
        when growing the tables now would have to evict. Kept under
        the signature the next step must still show."""
        import jax

        self._ahead = None
        sched, cache = self.scheduler, self.cache
        grow, by_length = 0, True
        for req in sched.running.values():
            n, s = req.context_len, req.sampling
            if len(req.output_ids) + 1 >= s.max_new_tokens \
                    or n + 2 > self.max_seq_len:
                return
            if s.eos_token_id is not None or s.stop_token_ids:
                by_length = False
            grow += cache.short(req.req_id, n + 2)
        if not cache.allocator.can_alloc(grow):
            return
        self._grow_tables(2)
        # by_length: no token's VALUE ends a request of this batch, so
        # it is certain now that the next step decodes this batch
        self._ahead = (self._signature(ahead=1),
                       jax.device_put(self._batch_arrays(ahead=1)[1:]),
                       by_length)

    def _grow_tables(self, new_tokens):
        """Every running request's tables made to cover `new_tokens`
        more tokens. In a cache of several groups that is also where
        the window groups' blocks that fell behind go back to the
        free list, and the upkeep has a span of its own,
        `serve/decode/blocks` (inside `serve/decode/prepare` or
        `serve/decode/ahead`)."""
        sched = self.scheduler
        with _flight.span("serve/decode/blocks") \
                if len(self.cache.groups) > 1 else contextlib.nullcontext():
            for req in list(sched.running.values()):
                sched.ensure_capacity(req, new_tokens=new_tokens)

    def _next_arrays(self):
        """This step's dispatch inputs: what `_prepare_ahead` made
        while the last step ran, if the batch is still what it was
        made for (no request finished, aborted, evicted or admitted
        since, no block renumbered), with the tokens now known as
        `ids`; else built from scratch."""
        ahead, self._ahead = self._ahead, None
        if ahead is None or ahead[0] != self._signature():
            return self._batch_arrays()
        ids = np.zeros((self.max_batch,), np.int32)
        for slot, req in self.scheduler.running.items():
            ids[slot] = req.output_ids[-1]
        return (ids,) + ahead[1]

    def _enqueue_decode(self, arrays, sig):
        """Hand one decode dispatch over `arrays` to the device. What
        is then in flight: (`sig`, the signature of the batch it
        decodes; its tokens and stats, on the device; whether the
        call compiled)."""
        import jax

        decode = self._programs("decode")
        # the first dispatch's `compile/serve_decode:<Model>` covers
        # the transfer too, and the capture lies inside it
        with decode.dispatch(), _flight.span("serve/decode/enqueue"):
            # the seven small arrays in ONE batched transfer: each
            # transfer of its own costs a round of the runtime's
            # latency with the device idle (0.2-0.3 ms on a v5e host)
            with _flight.span("serve/decode/put"):
                ids, pos, tables, lens, temp, topk, seeds = \
                    jax.device_put(arrays)
            toks, self.cache.pools, stats = decode.bind(
                self.params, ids, pos, self.cache.pools, tables,
                lens, temp, topk, seeds)()
            compiled = decode.compiled()
            decode.capture()
        self._count_target_dispatch(sig)
        return sig, toks, stats, compiled

    def _run_ahead(self, toks):
        """Called with a decode dispatch in flight whose tokens, still
        on the device, are `toks`, and the next step's inputs
        prepared: hands the device the NEXT dispatch now, fed those
        tokens where they are, so that it goes from one step to the
        next without the host (the fetch, `_emit` for every sequence,
        the scheduler and the caller's own code then run beside it).
        A request aborted or evicted in between rides along in that
        dispatch as an inactive slot does; its token is dropped when
        fetched (`_decoded_for`). An out-of-memory here makes room as
        in `_decode_batch` and leaves the next step to dispatch as
        ever."""
        (sig, arrays, _), self._ahead = self._ahead, None
        try:
            if _chaos._armed:
                _chaos.hit("serve_decode",
                           batch=len(self.scheduler.running))
            self._inflight = self._enqueue_decode((toks,) + arrays, sig)
        except Exception as e:
            self._evict_for_oom(e)

    def _fetch_decode(self, inflight):
        """Wait for the dispatch `inflight` and return its tokens;
        before that, with the device busy, prepare the next step's
        inputs and, in an engine made with `run_ahead=True`, hand the
        next dispatch over (`_run_ahead`) where it is certain that
        the next step decodes this very batch: no request ends with
        the token in flight (not by length: `_prepare_ahead`; not by
        the token's value: no request of the batch names one) and
        the batch is full, so that nothing can be admitted."""
        _, toks, stats, compiled = inflight
        with _flight.span("serve/decode/fetch"):
            # the wait for the device, used: the next step's inputs
            with _flight.span("serve/decode/ahead"):
                self._prepare_ahead()
            ahead = self._ahead
            if not self.run_ahead or compiled or ahead is None \
                    or not ahead[2] \
                    or len(self.scheduler.running) < self.max_batch:
                return self._wait_decode(toks, stats)
        self._run_ahead(toks)
        with _flight.span("serve/decode/fetch"):
            return self._wait_decode(toks, stats)

    def _wait_decode(self, toks, stats):
        """Block until a decode dispatch's tokens and stats are on the
        host: `serve/decode/wait` holds the wait alone (a wait span:
        its ring record says what the host's scheduler did to this
        thread meanwhile), the counting follows it."""
        import jax

        with _flight.wait_span("serve/decode/wait"):
            toks, stats = jax.device_get((toks, stats))
        self._count_stats(stats, self.max_batch)
        return toks

    def _count_target_dispatch(self, sig=()):
        """One target dispatch (decode or verify) into the counters:
        `serve/sample/steps_{greedy,drawn,ranked}`, the case its
        sampler takes for this batch, and `serve/attn/steps`, with
        `serve/attn/steps_paged` beside it when the program attends
        through the block tables in the Pallas kernel; for a model
        with state-space layers `serve/state/ssm_steps`, the layers
        the dispatch updates, with `serve/state/ssm_steps_kernel`
        beside it where the state kernel updates them (the runner's
        answer, from the predicate the traced program asked). In a cache of
        several groups also `serve/attn/steps_windowed` and, summed
        over the sequences of the dispatch (`sig`, its signature):
        `serve/kv/{full,window}_blocks_held`, what they hold by kind
        of group, and `serve/kv/window_blocks_least`, the window
        groups' blocks that hold a position this dispatch's query
        can see."""
        case = _mr.sample_case(
            req.sampling for req in self.scheduler.running.values())
        _cmon.stat_add(
            "serve/sample/steps_" + _mr.SAMPLE_CASES[case], 1)
        _cmon.stat_add("serve/attn/steps", 1)
        if self.use_kernel:
            _cmon.stat_add("serve/attn/steps_paged", 1)
        scans = self.runner.scan_layers
        if scans:
            _cmon.stat_add("serve/state/ssm_steps", scans)
            if self.runner.scan_kernel():
                _cmon.stat_add("serve/state/ssm_steps_kernel", scans)
        cache = self.cache
        if len(cache.groups) > 1:
            _cmon.stat_add("serve/attn/steps_windowed", 1)
            full = held = least = 0
            running = self.scheduler.running
            for slot, rid, n in sig[1:]:
                req = running.get(slot)
                if req is not None and req.req_id == rid:
                    f, w = cache.held(rid)
                    full, held = full + f, held + w
                    least += cache.window_blocks_least(n)
            _cmon.stat_add("serve/kv/full_blocks_held", full)
            _cmon.stat_add("serve/kv/window_blocks_held", held)
            _cmon.stat_add("serve/kv/window_blocks_least", least)

    def _count_stats(self, stats, tokens):
        """What a program over `tokens` rows returned beside its
        tokens, into the counters. `moe_counts` [expert layers,
        experts held]: the live tokens each expert of each layer took
        in this dispatch. `moe_picks` [expert layers, 2], from a
        router wider than the experts held here: the live tokens'
        top-k picks, and those of them that fell on zero-compute
        experts; without it every pick is an assignment.
        `serve/moe/layer_steps_kernel` counts the layers beside
        `layer_steps` where that program multiplied its groups in the
        Pallas grouped matmul (the runner's answer, from the
        predicate the traced program asked)."""
        counts = stats.get("moe_counts")
        if counts is None:
            return
        counts = np.asarray(counts)
        choices, zero = np.reshape(
            stats.get("moe_picks", (counts.sum(), 0)), (-1, 2)).sum(0)
        _cmon.stat_add("serve/moe/choices", int(choices))
        _cmon.stat_add("serve/moe/zero_choices", int(zero))
        _cmon.stat_add("serve/moe/assignments", int(counts.sum()))
        _cmon.stat_add("serve/moe/experts_hit",
                       int((counts > 0).sum()))
        _cmon.stat_add("serve/moe/layer_steps", counts.shape[0])
        if self.runner.experts_kernel(tokens):
            _cmon.stat_add("serve/moe/layer_steps_kernel",
                           counts.shape[0])
        _cmon.stat_add("serve/moe/max_load",
                       int(counts.max(axis=-1).sum()))

    def _pools_deleted(self):
        """Did a failed DONATING dispatch consume the pools? (A real
        RESOURCE_EXHAUSTED mid-execution deletes donated buffers —
        retrying with them is the PTA041 use-after-donate crash.)"""
        try:
            return any(p.is_deleted() for p in self.cache.pools
                       + (self.cache.draft_pools or ()))
        except Exception:
            return False

    def _decode_batch(self, emitted):
        """Grow tables, dispatch once (or take up the dispatch the
        last step handed over ahead, `_run_ahead`), apply stop
        conditions. An OOM (real or chaos-injected) evicts the
        youngest request and retries with the smaller batch; if the
        failed dispatch consumed the DONATED pools, rebuild them and
        replay every running request (position-keyed sampling makes
        the replay token-exact). A persistent OOM re-raises after
        max(3, max_batch) consecutive failed dispatches instead of
        spinning on evict/readmit forever."""
        if self.spec_k > 1:
            return self._spec_decode_batch(emitted)
        inflight, self._inflight = self._inflight, None
        if inflight is not None \
                and not next(self._decoded_for(inflight[0]), None):
            inflight = None           # every request of it has left
            if not self.scheduler.running:
                return
        if inflight is None:
            # snapshot the batch, but re-check membership per request:
            # growing request A can evict request B later in the
            # snapshot, and growing an evicted B would strand blocks
            # on a request the dispatch no longer covers
            with _flight.span("serve/decode/prepare"):
                self._grow_tables(1)
                if not self.scheduler.running:
                    return
                arrays = self._next_arrays()
        t0 = time.perf_counter()
        try:
            with _flight.in_flight("serve_decode", "decode",
                                   batch=len(self.scheduler.running)):
                if inflight is None:
                    if _chaos._armed:
                        _chaos.hit("serve_decode",
                                   batch=len(self.scheduler.running))
                    inflight = self._enqueue_decode(
                        arrays, self._signature())
                toks = self._fetch_decode(inflight)
        except Exception as e:
            # what was handed over after the failed dispatch fails
            # with it
            self._inflight = None
            if not self._evict_for_oom(e):
                return                # next step() re-prefills
            return self._decode_batch(emitted)
        self._oom_streak = 0
        self.heartbeat = time.monotonic()
        decode_us = int((time.perf_counter() - t0) * 1e6)
        _cmon.stat_add("serve/decode_us", decode_us)
        if not inflight[3] and _perf.dispatch_timing_enabled():
            # the fetch blocked: measured device time for the
            # roofline, like prefill; a dispatch that compiled stays
            # out of the histogram
            _perf.observe_dispatch(self._programs("decode").name,
                                   decode_us)
        with _flight.span("serve/decode/emit"):
            # a request that left its slot since the dispatch
            # (aborted, evicted, exported) gets none
            for req in list(self._decoded_for(inflight[0])):
                self._emit(req, int(toks[req.slot]), emitted)

    def _decoded_for(self, sig):
        """The requests a dispatch of signature `sig` decoded for that
        are still what they were then: in that slot, at that
        length."""
        running = self.scheduler.running
        for slot, rid, n in sig[1:]:
            req = running.get(slot)
            if req is not None and req.req_id == rid \
                    and req.context_len == n:
                yield req

    def _evict_for_oom(self, e):
        """What a decode dispatch that failed with `e` costs: re-raises
        anything but an out-of-memory, and that too once it persists;
        else makes room, as the span `serve/evict`. True: a victim was
        evicted, dispatch again with the smaller batch. False: the
        failed dispatch had consumed the donated pools, so every
        running request was evicted and the pools rebuilt. An
        out-of-memory that the compiler raised (the program cannot
        fit, whatever the batch holds) counts `serve/compile_oom`."""
        if not _memory.is_oom_error(e):
            raise e
        if _memory.is_compile_oom_error(e):
            _cmon.stat_add("serve/compile_oom", 1)
        self._oom_streak += 1
        if self._oom_streak > max(3, self.max_batch):
            raise e
        with _flight.span("serve/evict"):
            _cmon.stat_add("serve/oom_evictions", 1)
            if self._pools_deleted():
                _cmon.stat_add("serve/pool_resets", 1)
                _flight.record("serve_pool_reset",
                               batch=len(self.scheduler.running))
                for req in list(self.scheduler.running.values()):
                    self.scheduler.evict(req)
                self.cache.reset_pools()
                return False
            victim = self.scheduler._pick_victim()
            if victim is None:
                raise e
            self.scheduler.evict(victim)
            return True

    # -- speculative decode (spec_k > 1) -----------------------------
    def _wide_tables(self, tables):
        """Spec dispatch tables carry ONE extra guaranteed-NULL
        column: a near-`max_seq_len` slot whose position overflows
        the real table width clamps into the null block (XLA gather
        clamps out-of-range indices) instead of corrupting an at-cap
        sequence's own last block."""
        wide = np.full(
            (tables.shape[0], self.max_blocks_per_seq + 1),
            NULL_BLOCK, np.int32)
        wide[:, :-1] = tables
        return wide

    def _check_spec_cow(self, running):
        """PTA074 runtime half (armed only): every block a spec round
        writes through — the realign/pending position onward — must
        be exclusively owned. Shared prefix blocks all precede the
        write frontier, so a trip here is a refcount/COW bug, not
        load."""
        if not getattr(_san, "_serving", False):
            return
        for req in running.values():
            lo = req.context_len - (2 if req._spec_gap else 1)
            for bid in self.cache.allocator.owned(
                    req.req_id)[lo // self.block_size:]:
                self.cache.allocator.check_cow(bid)

    def _draft_propose(self, running, wide_j):
        """k batched draft-model decode dispatches -> k-1 proposed
        tokens per running request.

        Step 0 is the REALIGN step: a request whose previous round
        accepted every proposal has one context position whose draft
        KV was never written (the verify step only writes TARGET KV).
        Re-feeding ctx[-2] at its own position rewrites that slot
        idempotently; requests without the gap re-feed ctx[-1]
        (duplicating step 1's write — same value, discarded output),
        keeping the dispatch fixed-shape. Steps 1..k-1 feed the
        pending token then each proposal onward, every write landing
        in the request's private tail — position-keyed seeds make
        the proposals deterministic across replays."""
        import jax.numpy as jnp

        b = self.max_batch
        r_ids = np.zeros((b,), np.int32)
        r_pos = np.zeros((b,), np.int32)
        r_lens = np.ones((b,), np.int32)
        zeros_f = np.zeros((b,), np.float32)
        zeros_i = np.zeros((b,), np.int32)
        zeros_u = np.zeros((b,), np.uint32)
        for slot, req in running.items():
            ctx = req.prompt_ids + req.output_ids
            back = 2 if req._spec_gap else 1
            r_ids[slot] = ctx[-back]
            r_pos[slot] = len(ctx) - back
            r_lens[slot] = len(ctx) - back + 1
        draft = self._programs("draft")
        _, self.cache.draft_pools, _ = draft.bind(
            self._draft_params, jnp.asarray(r_ids),
            jnp.asarray(r_pos), self.cache.draft_pools,
            wide_j, jnp.asarray(r_lens),
            jnp.asarray(zeros_f), jnp.asarray(zeros_i),
            jnp.asarray(zeros_u))()
        drafts = {slot: [] for slot in running}
        ids = np.zeros((b,), np.int32)
        pos = np.zeros((b,), np.int32)
        lens = np.ones((b,), np.int32)
        temp = np.zeros((b,), np.float32)
        topk = np.zeros((b,), np.int32)
        seeds = np.zeros((b,), np.uint32)
        for slot, req in running.items():
            ctx = req.prompt_ids + req.output_ids
            ids[slot] = ctx[-1]
            pos[slot] = len(ctx) - 1
            lens[slot] = len(ctx)
            s = req.sampling
            temp[slot] = s.temperature
            topk[slot] = s.top_k
            seeds[slot] = _mr.seed_for(s.seed, len(ctx))
        for _ in range(self.spec_k - 1):
            toks, self.cache.draft_pools, _ = draft.bind(
                self._draft_params, jnp.asarray(ids),
                jnp.asarray(pos), self.cache.draft_pools,
                wide_j, jnp.asarray(lens),
                jnp.asarray(temp), jnp.asarray(topk),
                jnp.asarray(seeds))()
            toks = np.asarray(toks)
            for slot, req in running.items():
                d = int(toks[slot])
                drafts[slot].append(d)
                ids[slot] = d
                pos[slot] += 1
                lens[slot] += 1
                seeds[slot] = _mr.seed_for(req.sampling.seed,
                                           int(lens[slot]))
        return drafts

    def _dispatch_verify(self, running, drafts, wide_j, arrays):
        """ONE fixed-shape target dispatch over all k slots: slot 0
        the pending token, slots 1.. the draft proposals. Returns
        [B, k] target choices, each sampled with the SAME
        position-keyed seed the k=1 engine would use."""
        import jax.numpy as jnp

        _, pos, _, lens, temp, topk, _ = arrays
        b = self.max_batch
        k = self.spec_k
        v_ids = np.zeros((b, k), np.int32)
        v_seeds = np.zeros((b, k), np.uint32)
        for slot, req in running.items():
            ctx = req.prompt_ids + req.output_ids
            v_ids[slot, 0] = ctx[-1]
            for t, d in enumerate(drafts[slot]):
                v_ids[slot, t + 1] = d
            for t in range(k):
                v_seeds[slot, t] = _mr.seed_for(req.sampling.seed,
                                                len(ctx) + t)
        with _flight.span("serve/decode/enqueue"):
            toks, self.cache.pools, _ = self._programs("verify").bind(
                self.params, jnp.asarray(v_ids), jnp.asarray(pos),
                self.cache.pools, wide_j, jnp.asarray(lens),
                jnp.asarray(temp), jnp.asarray(topk),
                jnp.asarray(v_seeds))()
        with _flight.span("serve/decode/fetch"):
            self._count_target_dispatch()
            with _flight.wait_span("serve/decode/wait"):
                return np.asarray(toks)

    def _spec_decode_batch(self, emitted):
        """One speculative round: k draft dispatches propose, one
        verify dispatch checks all proposals, the engine emits the
        longest agreeing prefix plus the first corrected token —
        1..k tokens per round, all of them the target's own
        position-seeded choices (token-identical to k=1). OOM
        handling mirrors `_decode_batch`: evict-and-retry, or
        rebuild-and-replay when a donating dispatch consumed the
        pools."""
        import jax.numpy as jnp

        k = self.spec_k
        with _flight.span("serve/decode/prepare"):
            for req in list(self.scheduler.running.values()):
                # k-aware growth, capped so an almost-finished
                # sequence never asks for blocks past max_seq_len's
                # table width
                self.scheduler.ensure_capacity(req, new_tokens=min(
                    k, max(1, self.max_seq_len - req.context_len)))
            if not self.scheduler.running:
                return
            arrays = self._batch_arrays()
            wide_j = jnp.asarray(self._wide_tables(arrays[2]))
            running = dict(self.scheduler.running)
            self._check_spec_cow(running)
        draft, verify = self._programs("draft"), self._programs("verify")
        t0 = time.perf_counter()
        try:
            with _flight.in_flight("serve_decode", "spec_decode",
                                   batch=len(running), k=k):
                if _chaos._armed:
                    _chaos.hit("serve_decode", batch=len(running))
                # the k draft dispatches of a round count as one
                with draft.dispatch(), \
                        _flight.span("serve/decode/draft"):
                    drafts = self._draft_propose(running, wide_j)
                if _chaos._armed:
                    rule = _chaos.hit("serve_spec_verify",
                                      batch=len(running), k=k)
                    if rule is not None:
                        # forced draft divergence: verification must
                        # reject every corrupted proposal and still
                        # emit the target's own token — degrading to
                        # >= 1 token/round, never to wrong tokens
                        vocab = self.config.vocab_size
                        drafts = {
                            slot: [(d + 1) % vocab for d in ds]
                            for slot, ds in drafts.items()}
                with verify.dispatch():
                    toks = self._dispatch_verify(running, drafts,
                                                 wide_j, arrays)
        except Exception as e:
            if not self._evict_for_oom(e):
                return                # next step() re-prefills
            return self._spec_decode_batch(emitted)
        self._oom_streak = 0
        self.heartbeat = time.monotonic()
        decode_us = int((time.perf_counter() - t0) * 1e6)
        _cmon.stat_add("serve/decode_us", decode_us)
        if not verify.compiled() and _perf.dispatch_timing_enabled():
            _perf.observe_dispatch(self._programs.label("decode"),
                                   decode_us)
        with _flight.span("serve/decode/emit"):
            for slot, req in sorted(running.items()):
                ds = drafts[slot]
                row = toks[slot]
                m = 0
                while m < len(ds) and ds[m] == int(row[m]):
                    m += 1
                _cmon.stat_add("serve/spec/proposed", len(ds))
                _cmon.stat_add("serve/spec/accepted", m)
                _cmon.hist_observe("serve/hist/accept_len", m + 1)
                # all proposals accepted -> one draft-KV position was
                # never written (verify writes only TARGET KV); the
                # next round's realign step fills it
                req._spec_gap = (m == len(ds))
                for t in range(m + 1):
                    self._emit(req, int(row[t]), emitted)
                    if req.finished:
                        break

    # -- token emission / stop conditions ----------------------------
    def _emit(self, req, token, emitted):
        now = time.perf_counter()
        req.output_ids.append(token)
        req.token_times.append(now)
        emitted[req.req_id] = token
        _cmon.stat_add("serve/tokens", 1)
        # latency distributions off the token_times stream (ISSUE
        # 15): first token -> TTFT from this engine leg's arrival;
        # later tokens -> the inter-token gap a streaming client sees
        if len(req.token_times) == 1:
            _cmon.hist_observe("serve/hist/ttft_us",
                               (now - req.arrival_perf) * 1e6)
        else:
            _cmon.hist_observe(
                "serve/hist/itl_us",
                (now - req.token_times[-2]) * 1e6)
        if _trace._armed:
            # in the request's own timeline alone: one a token of
            # every sequence is not for the flight ring
            _trace.note(req, "decode", mirror=False,
                        n=len(req.output_ids))
        if req.on_token is not None:
            try:
                req.on_token(req.req_id, token)
            except Exception:
                _cmon.stat_add("serve/callback_errors", 1)
        s = req.sampling
        done = (req.stop_hit(token)
                or len(req.output_ids) >= s.max_new_tokens
                or req.context_len >= self.max_seq_len)
        if done:
            self.scheduler.finish(req, state=FINISHED)

    # -- lifecycle: drain / export / failover (ISSUE 13) -------------
    @property
    def fenced(self):
        return self._fenced

    def _check_fenced(self):
        if self._fenced:
            raise EngineOverloaded(
                "engine is fenced (its requests were exported after "
                "a wedge/failover) and will never serve again — "
                "route to another replica or build a fresh "
                "LLMEngine", engine_state=self.state_summary())

    def heartbeat_age(self, now=None):
        """Seconds since the last completed dispatch — the router's
        wedge signal (meaningful only while the engine has work)."""
        return (time.monotonic() if now is None else now) \
            - self.heartbeat

    def load_score(self):
        """Free KV blocks NET of queued-but-not-yet-admitted demand
        (prompt blocks + one decode lookahead per waiting request) —
        the router's least-loaded signal. Counting the queue makes
        back-to-back routing decisions see load the worker thread
        hasn't admitted yet. list() snapshots the deque atomically
        (C-level copy) so a concurrent admission pass can't raise
        mutated-during-iteration under the router's read."""
        lookahead = self.scheduler._lookahead
        pending = sum(
            self.cache.blocks_needed(r.context_len, lookahead)
            for r in list(self.scheduler.waiting))
        return self.cache.allocator.free_blocks - pending

    def state_summary(self):
        """Host-side snapshot of where serving stands — attached to
        EngineTimeout/shed errors and flight records so a refused or
        abandoned request names the engine state that refused it."""
        sched = self.scheduler
        return {
            "waiting": len(sched.waiting),
            "running": len(sched.running),
            "draining": sched.draining,
            "fenced": self._fenced,
            "queue_depth": len(sched.waiting),
            "free_blocks": self.cache.allocator.free_blocks,
            "used_blocks": self.cache.allocator.used_blocks,
            "oom_streak": self._oom_streak,
            "heartbeat_age_s": round(self.heartbeat_age(), 3),
            "spec_k": self.spec_k,
            "prefix_cache": self.prefix_cache,
        }

    def _export(self, req):
        """One request's replayable snapshot: everything another
        engine needs to continue it TOKEN-EXACTLY (the position-keyed
        sampling seeds make the remaining tokens a pure function of
        prompt + generated-so-far + sampling)."""
        return {
            "req_id": req.req_id,
            "prompt_ids": list(req.prompt_ids),
            "output_ids": list(req.output_ids),
            "sampling": req.sampling,
            "deadline": req.deadline,
            "evictions": req.evictions,
            # trace continuity (ISSUE 15): the importing engine keeps
            # the SAME trace_id and the timeline-so-far, so a
            # replayed request's story reads export -> import ->
            # replay in one place
            "trace_id": req.trace_id,
        }

    def export_requests(self, fence=True):
        """Snapshot + retire every live request (EXPORTED terminal
        state — blocks release NOW, so even a dead replica's
        allocator audits clean) and by default FENCE the engine so a
        zombie thread can't keep serving the originals. RUNNING
        requests export first (admission order — most progress
        resumes soonest), then the waiting queue in FIFO order.
        The exports MUST be re-added somewhere (`import_request`) or
        the requests are silently dropped — the PTA073 lint class."""
        if fence:
            self._fenced = True
        sched = self.scheduler
        running = sorted(
            sched.running.values(),
            key=lambda r: sched._admitted_at.get(r.req_id, -1))
        live = running + list(sched.waiting)
        exports = []
        for req in live:
            req.on_token = None   # zombie emits must not stream
            exp = self._export(req)
            sched.finish(req, state=EXPORTED)
            # snapshot the timeline AFTER finish so the export
            # carries its own "exported" terminal event
            exp["trace"] = list(req.trace)
            exports.append(exp)
        return exports

    def import_request(self, export, on_token=None, force=False):
        """Re-admit an exported request (failover/drain handoff):
        the preserved output_ids ride into the re-prefill exactly
        like an eviction's recompute-on-readmit, so generation
        continues where the exporting engine stopped. `force=True`
        (router failover) bypasses the drain gate and shed bound —
        the request already holds an admission promise. A fenced
        engine refuses even forced imports: it will never step."""
        self._check_fenced()
        req = Request(export["prompt_ids"],
                      sampling=export["sampling"],
                      on_token=on_token,
                      req_id=export["req_id"],
                      trace_id=export.get("trace_id"))
        req.output_ids = list(export["output_ids"])
        req.deadline = export.get("deadline")
        req.evictions = int(export.get("evictions", 0))
        if export.get("trace"):
            # continue the exporting engine's timeline (same
            # trace_id) — the ctor's fresh "add" event is replaced by
            # the full story plus this import leg
            req.trace = list(export["trace"])
        if _trace._armed:
            _trace.note(req, "import", replayed=len(req.output_ids),
                        forced=bool(force))
        self.scheduler.add(req, force=force)
        self._requests[req.req_id] = req
        return req.req_id

    def drain(self, timeout_s=None):
        """Graceful drain: stop admitting (new `add_request` sheds
        with EngineOverloaded), run RUNNING requests to completion,
        then export whatever is left — still-running requests that
        outlived `timeout_s` plus the whole waiting queue — for
        re-admission elsewhere. Returns the export list ([] when
        everything completed). The engine stays draining afterwards;
        `resume()` re-opens admission."""
        deadline = (time.monotonic() + timeout_s
                    if timeout_s is not None else None)
        with _flight.in_flight("serve_drain", "drain",
                               running=len(self.scheduler.running),
                               waiting=len(self.scheduler.waiting)):
            if _chaos._armed:
                _chaos.hit("serve_drain",
                           running=len(self.scheduler.running))
            self.scheduler.draining = True
            while self.scheduler.running and not self._fenced:
                if deadline is not None \
                        and time.monotonic() > deadline:
                    break
                self.step()
            exports = self.export_requests(fence=False)
            if self.emergency_exports:
                # the watchdog incident hook fenced this engine
                # MID-drain and already exported the in-flight work;
                # fold it into the return so the caller's "re-add
                # everything drain() returns" contract still covers
                # every request (returning [] here would read as
                # 'all completed' — the PTA073 drop class)
                exports = list(self.emergency_exports) + exports
                self.emergency_exports = None
        _cmon.stat_add("serve/drains", 1)
        _flight.record("serve_drain_done", exported=len(exports))
        return exports

    def resume(self):
        """Re-open admission after a drain (a replica rejoining the
        router pool). A FENCED engine cannot resume — its requests
        were exported and its pools may be mid-wedge; build a fresh
        engine instead."""
        if self._fenced:
            raise RuntimeError(
                "cannot resume a fenced engine — its requests were "
                "exported after a wedge/failover; create a fresh "
                "LLMEngine (the persistent compile cache makes that "
                "a warm start)")
        self.scheduler.draining = False

    # -- watchdog emergency drain-and-export -------------------------
    def arm_incident_export(self):
        """Register the PR-3/6 incident hook: when the watchdog dumps
        on a wedged dispatch (a stuck `serve_prefill`/`serve_decode`
        span), fence this engine and export its in-flight requests
        into `emergency_exports` — the autopsy bundle gains a
        REPLAYABLE workload instead of just a stack trace, and a
        router replays it on a healthy replica."""
        if not self._incident_armed:
            _flight.add_incident_hook(self._incident_export)
            self._incident_armed = True
        return self

    def disarm_incident_export(self):
        if self._incident_armed:
            _flight.remove_incident_hook(self._incident_export)
            self._incident_armed = False

    def _incident_export(self, reason):
        """Incident-hook body (best-effort by the PR-3 contract).
        Only a wedge with live work exports; an idle engine has
        nothing at stake. NO dispatches run here — the dispatch IS
        what wedged."""
        if self._fenced or not self.scheduler.has_work():
            return
        exports = self.export_requests(fence=True)
        self.emergency_exports = exports
        _cmon.stat_add("serve/drains", 1)
        _flight.record("serve_drain_done", exported=len(exports),
                       emergency=True, reason=str(reason))

    # -- trace spool (ISSUE 15) --------------------------------------
    def export_traces(self):
        """Trace spool (schema "paddle_tpu.trace/1") over every
        retained request's per-stage timeline — the input
        `python -m paddle_tpu.monitor trace` renders to a
        chrome-trace. Live requests show their story so far."""
        return _trace.export_requests(self._requests.values())

    def dump_traces(self, path):
        """Write export_traces() as JSON; returns the path."""
        import json

        with open(path, "w") as f:
            json.dump(self.export_traces(), f, default=str)
        return path

    # -- accounting --------------------------------------------------
    def check_drained(self):
        """Zero-leak audit after a drain: no live requests may remain
        and every KV block must be back on the free list. Returns the
        leak map ({} when clean) — with PADDLE_SANITIZE=serving armed
        each leak is also a PTA070 finding."""
        live = [r.req_id for r in self._requests.values()
                if not r.finished]
        leaks = self.cache.allocator.audit_leaks(live)
        return leaks
