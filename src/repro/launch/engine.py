"""Continuous-batching decode engine on pipeline megakernels.

The serving loop the paper's megakernel result plugs into: an open-loop
arrival trace feeds a slot-based scheduler that

* admits requests into free KV-cache slots, prefilling each prompt
  padded to a *shape bucket* (exact under causal masking: pad keys
  occupy only future positions, which the causal frontier excludes, and
  successive decode steps overwrite them);
* runs ONE mixed decode step per tick across every active slot — a
  ragged batch where each sequence sits at its own cache position.
  Positions are kernel *data* (the causal-mask QP/KP position-vector
  inputs), so the ragged batch reuses the same compiled kernels every
  step: one persistent grouped megakernel per (arch, shape-bucket),
  served from the on-disk kernel cache with zero steady-state
  recompiles (pinned by a cache-stats assertion);
* evicts finished sequences (request satisfied) and stalled ones (cache
  slot exhausted) to free slots for the queue.

Observability: every step records queue depth, batch occupancy and the
prefill/decode split; every admitted request gets a host-clock timeline
(arrival, start of its own prefill, first token, finish) from which the
report takes time to first token and the gap between tokens; the report
also aggregates tokens/sec, kernel-cache hit rate and the steady-state
recompile count, and serializes to JSON (``benchmarks/serve_bench.py``
gates the throughput numbers in CI).  Under ``jax.profiler`` the run
loop's phases show as host spans (``engine.run``, ``engine.tick``,
``engine.admit``, ``engine.decode``, ``engine.sync.*``, ...) on the
trace's clock, next to the device's ops; every readback of a device
value sits in one ``engine.sync.*`` span.
"""

from __future__ import annotations

import time
import warnings
from collections import deque
from dataclasses import asdict, dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
from jax.profiler import TraceAnnotation as span

from repro import resilience as RZ

# families whose padded-bucket prefill is exactly correct: causal
# attention masks the pad positions; an SSM scan would carry pad state
# forward into real tokens
_SUPPORTED_FAMILIES = ("dense", "moe")


# ---------------------------------------------------------------------------
# trace
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Request:
    """One serving request: a prompt arriving at an (open-loop) step."""
    rid: int
    prompt: Tuple[int, ...]
    max_new_tokens: int
    arrival_step: int
    # latest engine step this request may still be running at: past it,
    # a queued request is dropped and an active one evicted (partial
    # tokens recorded), each with a structured failure record.  None =
    # no deadline (the default; synthetic traces set none)
    deadline_step: Optional[int] = None
    # when the request arrived, on the host clock (``time.perf_counter``);
    # None = when ``Engine.run`` takes it from the trace into the queue
    arrival_s: Optional[float] = None


def synth_trace(n_requests: int, *, seed: int = 0,
                arrival_rate: float = 1.0,
                prompt_lens: Tuple[int, int] = (4, 24),
                gen_lens: Tuple[int, int] = (4, 16),
                vocab: int = 1000) -> List[Request]:
    """A synthetic open-loop arrival trace: geometric inter-arrival steps
    at ``arrival_rate`` requests/step (open-loop: arrivals don't wait for
    completions, so the queue genuinely builds when the engine lags),
    uniform prompt/generation lengths, uniform random tokens."""
    rng = np.random.default_rng(seed)
    reqs, step = [], 0
    for rid in range(n_requests):
        plen = int(rng.integers(prompt_lens[0], prompt_lens[1] + 1))
        glen = int(rng.integers(gen_lens[0], gen_lens[1] + 1))
        reqs.append(Request(
            rid=rid,
            prompt=tuple(int(t) for t in rng.integers(0, vocab, plen)),
            max_new_tokens=glen,
            arrival_step=step))
        # geometric inter-arrival (the discrete-step Poisson analogue)
        step += int(rng.geometric(min(1.0, arrival_rate)) - 1)
    return reqs


# ---------------------------------------------------------------------------
# report
# ---------------------------------------------------------------------------

@dataclass
class StepRecord:
    step: int
    queue_depth: int
    occupancy: int          # active slots after admission
    n_prefill: int          # requests admitted (prefilled) this step
    n_decode: int           # decode tokens emitted this step
    wall_ms: float


@dataclass
class RequestTimeline:
    """Host-clock stamps (``time.perf_counter``) of one admitted
    request.  The engine fills them in as the request goes: the object
    in a report keeps being updated by later ``run()`` calls until the
    request finishes."""
    rid: int
    arrival_s: float
    prefill_s: float                       # start of its own prefill
    first_token_s: Optional[float] = None  # first token on the host
    finish_s: Optional[float] = None       # its last token on the host
    n_tokens: int = 0

    @property
    def ttft_s(self) -> Optional[float]:
        if self.first_token_s is None:
            return None
        return self.first_token_s - self.arrival_s

    @property
    def itl_s(self) -> Optional[float]:
        """Mean gap between the request's tokens after its first."""
        if self.finish_s is None or self.n_tokens < 2:
            return None
        return (self.finish_s - self.first_token_s) / (self.n_tokens - 1)


def _percentiles_ms(values: List[float]) -> Tuple[float, float]:
    if not values:
        return 0.0, 0.0
    p50, p99 = np.percentile(values, [50, 99])
    return float(p50) * 1e3, float(p99) * 1e3


@dataclass
class ServeReport:
    """What a serving run did, aggregated for gating and dashboards."""
    n_requests: int = 0
    n_completed: int = 0
    n_evicted_stalled: int = 0
    n_rejected: int = 0
    steps: int = 0
    wall_s: float = 0.0
    prefill_tokens: int = 0
    decode_tokens: int = 0
    tokens_per_s: float = 0.0
    decode_tokens_per_s: float = 0.0
    # from the timelines of the requests admitted in this run() call:
    # arrival to first token, and the mean gap between a finished
    # request's tokens after its first
    ttft_p50_ms: float = 0.0
    ttft_p99_ms: float = 0.0
    itl_p50_ms: float = 0.0
    itl_p99_ms: float = 0.0
    mean_occupancy: float = 0.0
    max_queue_depth: int = 0
    cache_memory_hits: int = 0
    cache_disk_hits: int = 0
    cache_misses: int = 0
    cache_hit_rate: float = 0.0
    warmup_compiles: int = 0
    decode_recompiles: int = 0   # steady-state compile growth; MUST be 0
    pallas_fallbacks: int = 0
    # -- resilience counters (all zero on the clean path; pinned by
    #    check_regression.py so the fault machinery never costs it) -----
    n_poisoned: int = 0          # requests evicted by the finite-logits
                                 # guard (prefill or decode)
    n_deadline_evicted: int = 0  # requests dropped/evicted past deadline
    degradations: int = 0        # ladder demotions over the run: compile
                                 # ladder (resilience.METRICS delta) plus
                                 # tick-watchdog decode demotions
    quarantined: int = 0         # corrupt cache entries quarantined
                                 # (CacheStats delta over the run)
    # -- self-healing counters (the inverse of the watchdog) ------------
    repromotions: int = 0        # demoted decode rungs probed healthy and
                                 # swapped back in mid-run
    probes: int = 0              # half-open re-promotion probes attempted
    probe_failures: int = 0      # probes that failed (breaker re-opened
                                 # at doubled cool-down)
    decode_backend: str = ""     # the rung decode ended the run on
                                 # (e.g. "pipeline-pallas" when healed)
    # bytes each compiled program aliases from the KV cache it is given
    # over the cache's bytes, read at warm-up: 1.0 when the program
    # updates the cache in place, 0.0 when it writes a new one
    # ({"decode": ..., "insert": ...})
    cache_alias_share: Dict[str, float] = field(default_factory=dict)
    # structured failure records: {"rid", "reason", "step", ...} — one
    # per poison eviction / deadline / queue_full rejection / watchdog
    # demotion, so a failed request is triageable, not just a counter
    failures: List[dict] = field(default_factory=list)
    tokens: Dict[int, List[int]] = field(default_factory=dict)
    per_step: List[StepRecord] = field(default_factory=list)
    timeline: List[RequestTimeline] = field(default_factory=list)

    def to_json(self) -> dict:
        d = asdict(self)
        d["tokens"] = {str(k): v for k, v in self.tokens.items()}
        return d


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

@dataclass
class _Slot:
    rid: int
    pos: int                 # next cache position to write (filled length)
    remaining: int
    last_token: int
    generated: List[int]
    deadline: Optional[int] = None
    timeline: Optional[RequestTimeline] = None


def _demote_cfg(cfg):
    """One watchdog rung down for the serving model: pallas pipeline ->
    jax pipeline -> the non-pipeline xla kernels.  Returns
    ``(new_cfg, label)`` or ``(None, None)`` at the bottom.  (The
    interpreter rung is not servable here: the numpy reference kernels
    cannot trace under the engine's jitted decode step.)"""
    import dataclasses

    opts = cfg.pipeline_options
    if cfg.attn_impl != "pipeline" and cfg.mlp_impl != "pipeline":
        return None, None
    backend = opts.backend if opts is not None else cfg.pipeline_backend
    if backend == "pallas":
        new_opts = (opts.replace(backend="jax")
                    if opts is not None else None)
        return dataclasses.replace(cfg, pipeline_backend="jax",
                                   pipeline_options=new_opts), "pipeline-jax"
    return dataclasses.replace(cfg, attn_impl="xla",
                               mlp_impl="fused_ref",
                               pipeline_options=None), "xla"


def _backend_label(cfg) -> str:
    """The serving-ladder rung label of a model config, matching the
    labels ``_demote_cfg`` hands out (``pipeline-pallas`` /
    ``pipeline-jax`` / ``xla``)."""
    if cfg.attn_impl != "pipeline" and cfg.mlp_impl != "pipeline":
        return "xla"
    opts = cfg.pipeline_options
    backend = opts.backend if opts is not None else cfg.pipeline_backend
    return f"pipeline-{backend}"


class Engine:
    """Slot-based continuous-batching scheduler over ``models.lm.LM``.

    The engine owns one batched KV cache of ``max_batch`` slots.  Each
    tick admits queued requests into free slots (bucketed prefill, one
    pipeline kernel per bucket) and then advances every active slot by
    one token through a single jitted ragged decode step (positions as a
    ``(B,)`` vector).  All pipeline kernels are compiled in ``warmup()``;
    after that the run loop never compiles again — ``run()`` asserts it.
    """

    def __init__(self, cfg, *, max_batch: int = 4, max_len: int = 96,
                 prompt_buckets: Sequence[int] = (8, 16, 32),
                 sampling: str = "greedy", temperature: float = 1.0,
                 seed: int = 0, keep_per_step: bool = True,
                 strict_no_recompile: bool = True,
                 max_queue: Optional[int] = None,
                 repromote_after: Optional[int] = 8):
        import jax

        from repro import pipeline
        from repro.models import build_model

        if cfg.family not in _SUPPORTED_FAMILIES:
            raise ValueError(
                f"continuous batching supports attention-family archs "
                f"{_SUPPORTED_FAMILIES}, not family={cfg.family!r}: padded "
                "bucket prefill is exact only under causal masking")
        if sampling not in ("greedy", "categorical"):
            raise ValueError(f"unknown sampling {sampling!r}")
        self.cfg = cfg
        self.max_batch = int(max_batch)
        self.max_len = int(max_len)
        self.prompt_buckets = tuple(sorted(int(b) for b in prompt_buckets))
        if self.prompt_buckets[-1] >= self.max_len:
            raise ValueError("largest prompt bucket must leave room to "
                             f"decode (buckets={self.prompt_buckets}, "
                             f"max_len={self.max_len})")
        self.sampling = sampling
        self.temperature = float(temperature)
        self.keep_per_step = keep_per_step
        self.strict_no_recompile = strict_no_recompile
        # bounded admission: arrivals past this queue depth are rejected
        # with a structured failure record instead of building an
        # unbounded backlog.  None = unbounded (the historical behavior)
        self.max_queue = None if max_queue is None else int(max_queue)
        self._key = jax.random.key(seed)

        # -- self-healing (the inverse of the tick watchdog) ----------------
        # after `repromote_after` clean decode ticks on a demoted rung, a
        # half-open probe re-compiles the original rung off the hot path
        # and swaps it back if it passes the finite-logits guard.  None
        # disables re-promotion (the PR-9 demote-forever behavior).  The
        # ledger's clock is the engine tick counter, so breaker timing is
        # deterministic per trace; state persists under <cache>/health/.
        self.repromote_after = (None if repromote_after is None
                                else int(repromote_after))
        self._tick = 0
        self._clean_ticks = 0
        self._demote_stack: List[Tuple[object, str]] = []  # (cfg, rung)
        self.repromotions = 0
        self.probes = 0
        self.probe_failures = 0
        self.probe_compiles = 0      # compiles explained by probes
        self._ledger = None
        self._hkey = f"serve:{getattr(cfg, 'name', 'model')}:decode"
        if self.repromote_after is not None:
            if self.repromote_after <= 0:
                raise ValueError("repromote_after must be > 0 (or None "
                                 "to disable re-promotion)")
            cache = pipeline.default_cache()
            self._ledger = RZ.HealthLedger(
                cache.root / "health" if cache.disk else None,
                clock=lambda: float(self._tick))
            self._breaker_policy = RZ.ResiliencePolicy(
                breaker_threshold=1,  # one decode crash opens the breaker
                breaker_cooldown_s=float(self.repromote_after),
                breaker_cooldown_max_s=float(self.repromote_after) * 64)
            # adopt persisted breaker state from a crashed/restarted
            # predecessor: start demoted rather than re-crash the same
            # rung, and re-open the cool-down against OUR tick clock
            while True:
                lbl = _backend_label(cfg)
                if self._ledger.state(self._hkey, lbl) == "closed":
                    break
                new_cfg, _ = _demote_cfg(cfg)
                if new_cfg is None:
                    break
                self._demote_stack.append((cfg, lbl))
                self._ledger.reopen(self._hkey, lbl,
                                    float(self.repromote_after))
                warnings.warn(
                    f"serve: decode rung {lbl!r} breaker is open in the "
                    f"health ledger; starting demoted to "
                    f"{_backend_label(new_cfg)!r}", RuntimeWarning,
                    stacklevel=2)
                cfg = new_cfg
            self.cfg = cfg

        self.model = build_model(cfg)
        self.params, _ = self.model.init_params(jax.random.key(seed))
        self._jax = jax

        m, L = self.model, self.max_len
        # a profiler trace shows these programs under their module names,
        # jit__lambda, jit_decode_step and jit_insert: the benchmark's
        # trace reduction finds them by those names (bench/trace.py).
        # Insert takes the batched KV cache as a donation and writes its
        # slot in place.  Decode writes only its new rows too, but into
        # a copy: its input cache stays valid after the call
        self._prefill = jax.jit(lambda p, t: m.prefill(p, t, max_len=L))
        self._decode = jax.jit(m.decode_step)

        def insert(batched, one, slot):
            # cache leaves are (n_layers, batch, ...): splice the
            # prefilled single-sequence cache into its slot
            return jax.tree.map(
                lambda b, s: jax.lax.dynamic_update_slice_in_dim(
                    b, s.astype(b.dtype), slot, axis=1), batched, one)

        self._insert = jax.jit(insert, donate_argnums=(0,))

        self.caches = m.init_cache(self.max_batch, self.max_len)
        self.slots: List[Optional[_Slot]] = [None] * self.max_batch
        self.queue: deque = deque()
        self._arrival: Dict[int, float] = {}  # rid -> arrival, while queued
        self._warm_stats = None
        self._base_stats = None      # cache counters at warmup start
        self._base_metrics = None    # resilience.METRICS at warmup start
        self.warmup_compiles = 0
        self.pallas_fallbacks = 0
        self.watchdog_demotions = 0  # tick-level decode demotions
        self.demotion_compiles = 0   # compiles explained by demotions
                                     # (excluded from decode_recompiles)
        self.cache_alias_share: Dict[str, float] = {}

    # -- scheduling helpers -------------------------------------------------
    def _bucket(self, plen: int) -> Optional[int]:
        for b in self.prompt_buckets:
            if plen <= b:
                return b
        return None

    def _free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    def _pos_vector(self) -> np.ndarray:
        return np.asarray([s.pos if s else 0 for s in self.slots], np.int32)

    def _token_vector(self) -> np.ndarray:
        return np.asarray([s.last_token if s else 0 for s in self.slots],
                          np.int32)

    def _sample(self, logits) -> np.ndarray:
        jax, jnp = self._jax, self._jax.numpy
        lg = logits[:, -1]
        if self.sampling == "greedy":
            return np.asarray(jnp.argmax(lg, axis=-1))
        self._key, sub = jax.random.split(self._key)
        return np.asarray(jax.random.categorical(
            sub, lg / max(self.temperature, 1e-6), axis=-1))

    # -- lifecycle ----------------------------------------------------------
    def warmup(self) -> int:
        """Compile every kernel the run loop can touch — one prefill
        pipeline per prompt bucket plus the full-batch ragged decode step
        — then snapshot the kernel-cache counters.  ``run()`` pins the
        steady state against this snapshot: any later compile is a
        recompile.  Returns the number of pipeline compiles performed."""
        from repro import pipeline

        jnp = self._jax.numpy
        stats = pipeline.default_cache().stats
        before = stats.snapshot()
        self._base_stats = before
        self._base_metrics = RZ.METRICS.snapshot()
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            for b in self.prompt_buckets:
                toks = jnp.zeros((1, b), jnp.int32)
                lg, cache = self._prefill(self.params, toks)
                self.caches = self._insert(self.caches, cache, 0)
                lg.block_until_ready()
            step_args = (jnp.zeros((self.max_batch, 1), jnp.int32),
                         jnp.zeros((self.max_batch,), jnp.int32))
            lg, self.caches = self._decode(self.params, self.caches,
                                           *step_args)
            lg.block_until_ready()
            # the executables just compiled, found again by their
            # arguments (no compile): how much of the cache each aliases
            nbytes = sum(a.nbytes for a in self._jax.tree.leaves(
                self.caches))
            self.cache_alias_share = {
                name: lowered.compile().memory_analysis()
                .alias_size_in_bytes / nbytes
                for name, lowered in (
                    ("decode", self._decode.lower(
                        self.params, self.caches, *step_args)),
                    ("insert", self._insert.lower(self.caches, cache, 0)))}
        self.pallas_fallbacks = sum(
            1 for w in caught if "pallas lowering fallback" in str(w.message))
        # the decode warm-up wrote garbage at position 0 of every slot;
        # real prefills overwrite it before any slot activates
        self.warmup_compiles = stats.delta(before).compiles
        self._warm_stats = stats.snapshot()
        return self.warmup_compiles

    def _admit(self, req: Request, slot: int, report: ServeReport,
               step: int = 0) -> str:
        """Prefill ``req`` into ``slot``.  Returns a status:
        ``"ok"`` (admitted or satisfied outright), ``"rejected"`` (bad
        shape: no bucket, or prompt+generation exceed ``max_len``),
        ``"deadline"`` (its deadline passed while queued), or
        ``"poisoned"`` (the prompt prefilled to non-finite logits — the
        slot stays free, co-batched sequences never see it)."""
        plen = len(req.prompt)
        bucket = self._bucket(plen)
        arrival = self._arrival.pop(req.rid, None)
        with span("engine.admit", rid=req.rid, bucket=bucket or 0):
            if req.deadline_step is not None and step > req.deadline_step:
                report.failures.append({
                    "rid": req.rid, "reason": "deadline_queued",
                    "step": step, "deadline": req.deadline_step})
                return "deadline"
            if bucket is None or plen + req.max_new_tokens > self.max_len:
                report.failures.append({
                    "rid": req.rid, "reason": "bad_shape", "step": step,
                    "prompt_len": plen,
                    "max_new_tokens": req.max_new_tokens})
                return "rejected"
            t = time.perf_counter()
            # a request put on the queue by hand arrives when admitted
            tl = RequestTimeline(req.rid, t if arrival is None else arrival,
                                 t)
            report.timeline.append(tl)
            return self._prefill_into(req, bucket, slot, tl, report, step)

    def _prefill_into(self, req: Request, bucket: int, slot: int,
                      tl: RequestTimeline, report: ServeReport,
                      step: int) -> str:
        jnp = self._jax.numpy
        plen = len(req.prompt)
        with span("engine.prefill"):
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :plen] = req.prompt
            logits, cache = self._prefill(self.params, jnp.asarray(padded))
            # the prompt's next-token logits sit at the last REAL
            # position; pad positions to the right are causally
            # invisible to it
            row = logits[:, plen - 1:plen]
        with span("engine.sync.prefill_guard"):
            finite = bool(jnp.all(jnp.isfinite(row)))
        if not finite:
            # poison prompt: never insert its cache, never occupy a slot
            tl.finish_s = time.perf_counter()
            report.n_poisoned += 1
            report.failures.append({
                "rid": req.rid, "reason": "nonfinite_prefill",
                "step": step, "prompt_len": plen})
            return "poisoned"
        with span("engine.insert"):
            self.caches = self._insert(self.caches, cache, slot)
        with span("engine.sync.first_token"):
            tok = int(self._sample(row)[0])
        tl.first_token_s = time.perf_counter()
        tl.n_tokens = 1
        if req.max_new_tokens <= 1:
            # the prefill's token satisfies the request outright
            tl.finish_s = tl.first_token_s
            report.tokens[req.rid] = [tok]
            report.n_completed += 1
            return "ok"
        self.slots[slot] = _Slot(rid=req.rid, pos=plen,
                                 remaining=req.max_new_tokens - 1,
                                 last_token=tok, generated=[tok],
                                 deadline=req.deadline_step, timeline=tl)
        return "ok"

    def _decode_once(self):
        jnp = self._jax.numpy
        with span("engine.decode"):
            RZ.check("serve:decode")
            return self._decode(
                self.params, self.caches,
                jnp.asarray(self._token_vector()[:, None]),
                jnp.asarray(self._pos_vector()))

    def _watchdog_demote(self, err: BaseException, step: int,
                         report: ServeReport) -> None:
        """The tick-level watchdog: the decode kernel raised, so rebuild
        the decode step one ladder rung down (pallas pipeline -> jax
        pipeline -> plain xla kernels) and keep serving.  Params and the
        KV cache are impl-independent, so active sequences continue
        in place; prefill kernels (which did not fail) stay as-is.
        Raises the original error when there is no rung left."""
        from repro.models import build_model

        new_cfg, label = _demote_cfg(self.cfg)
        if new_cfg is None:
            raise err
        jax = self._jax
        if self._ledger is not None:
            # open the failed rung's breaker (threshold 1: a decode crash
            # is never cheap) so re-promotion waits out the cool-down
            old_label = _backend_label(self.cfg)
            self._demote_stack.append((self.cfg, old_label))
            self._ledger.record_failure(self._hkey, old_label, err,
                                        policy=self._breaker_policy)
        self.cfg = new_cfg
        self.model = build_model(new_cfg)
        m = self.model
        self._decode = jax.jit(m.decode_step)
        self.watchdog_demotions += 1
        report.failures.append({
            "reason": "decode_demotion", "step": step, "to": label,
            "error": f"{type(err).__name__}: {err}"})
        warnings.warn(
            f"serve watchdog: decode step failed "
            f"({type(err).__name__}: {err}); demoted decode to {label} "
            "and continuing", RuntimeWarning, stacklevel=2)

    def _probe_repromote(self, report: ServeReport, step: int,
                         stats) -> bool:
        """Half-open probe of the rung decode was demoted off: rebuild
        it and run one decode step against the live KV cache *without*
        committing its outputs (the real tick already ran).  A
        finite-logits pass swaps the healthy rung back in and closes the
        breaker; a failure re-opens it at doubled cool-down.  Probe
        compiles are explained (excluded from ``strict_no_recompile``)
        the same way demotion compiles are."""
        from repro.models import build_model

        jnp = self._jax.numpy
        old_cfg, old_label = self._demote_stack[-1]
        self.probes += 1
        before = stats.snapshot()
        try:
            spec = RZ.fire("serve:probe")
            if spec is not None and spec.kind == "raise":
                raise RZ.InjectedFault(f"serve:probe[{spec.message}]")
            model = build_model(old_cfg)
            decode = self._jax.jit(model.decode_step)
            logits, _ = decode(  # outputs discarded: probe only
                self.params, self.caches,
                jnp.asarray(self._token_vector()[:, None]),
                jnp.asarray(self._pos_vector()))
            if spec is not None and spec.kind == "nan":
                logits = logits.at[:, -1].set(jnp.nan)
            with span("engine.sync.probe_guard"):
                finite = bool(jnp.all(jnp.isfinite(logits[:, -1])))
            if not finite:
                raise RuntimeError("probe produced non-finite logits")
        except Exception as e:
            self.probe_failures += 1
            self._ledger.record_failure(self._hkey, old_label, e,
                                        policy=self._breaker_policy)
            report.failures.append({
                "reason": "probe_failed", "step": step, "rung": old_label,
                "error": f"{type(e).__name__}: {e}"})
            warnings.warn(
                f"serve: re-promotion probe of {old_label!r} failed "
                f"({type(e).__name__}: {e}); breaker re-opened at doubled "
                "cool-down", RuntimeWarning, stacklevel=2)
            self._clean_ticks = 0
            self.probe_compiles += stats.delta(before).compiles
            self._warm_stats = stats.snapshot()
            return False
        # healthy again: swap the probed decode in and close the breaker
        self.cfg, self.model, self._decode = old_cfg, model, decode
        self._demote_stack.pop()
        self.repromotions += 1
        self._ledger.record_success(self._hkey, old_label)
        report.failures.append({
            "reason": "decode_repromotion", "step": step, "to": old_label})
        warnings.warn(
            f"serve: decode rung {old_label!r} probed healthy after "
            f"{self._clean_ticks} clean ticks; re-promoted",
            RuntimeWarning, stacklevel=2)
        self._clean_ticks = 0
        self.probe_compiles += stats.delta(before).compiles
        self._warm_stats = stats.snapshot()
        return True

    def run(self, trace: Sequence[Request],
            max_steps: Optional[int] = None) -> ServeReport:
        """Drive the trace to completion (or ``max_steps``) and report."""
        with span("engine.run"):
            return self._run(trace, max_steps)

    def _run(self, trace: Sequence[Request],
             max_steps: Optional[int]) -> ServeReport:
        from repro import pipeline

        if self._warm_stats is None:
            self.warmup()
        stats = pipeline.default_cache().stats

        pending = deque(sorted(trace, key=lambda r: r.arrival_step))
        report = ServeReport(n_requests=len(trace))
        occupancy_sum = 0
        step = 0
        t_run = time.perf_counter()
        while pending or self.queue or any(self.slots):
            if max_steps is not None and step >= max_steps:
                break
            self._tick = step  # the health ledger's deterministic clock
            t0 = time.perf_counter()
            with span("engine.tick", step=step, n_active=sum(
                    s is not None for s in self.slots)):
                n_prefill, n_decode = self._step(step, pending, report,
                                                 stats)
            wall_ms = (time.perf_counter() - t0) * 1e3
            occ = sum(1 for s in self.slots if s is not None)
            occupancy_sum += occ
            report.decode_tokens += n_decode
            if self.keep_per_step:
                report.per_step.append(StepRecord(
                    step=step, queue_depth=len(self.queue), occupancy=occ,
                    n_prefill=n_prefill, n_decode=n_decode,
                    wall_ms=wall_ms))
            report.max_queue_depth = max(report.max_queue_depth,
                                         len(self.queue))
            step += 1

        report.steps = step
        report.wall_s = time.perf_counter() - t_run
        total = report.prefill_tokens + report.decode_tokens
        report.tokens_per_s = total / max(report.wall_s, 1e-9)
        report.decode_tokens_per_s = (report.decode_tokens
                                      / max(report.wall_s, 1e-9))
        report.ttft_p50_ms, report.ttft_p99_ms = _percentiles_ms(
            [t.ttft_s for t in report.timeline if t.ttft_s is not None])
        report.itl_p50_ms, report.itl_p99_ms = _percentiles_ms(
            [t.itl_s for t in report.timeline if t.itl_s is not None])
        report.mean_occupancy = occupancy_sum / max(step, 1)
        report.cache_memory_hits = stats.memory_hits
        report.cache_disk_hits = stats.disk_hits
        report.cache_misses = stats.misses
        report.cache_hit_rate = stats.hit_rate
        report.warmup_compiles = self.warmup_compiles
        report.decode_recompiles = stats.delta(self._warm_stats).compiles
        report.pallas_fallbacks = self.pallas_fallbacks
        # resilience counters over the whole engine lifetime (warmup
        # included): compile-ladder demotions + watchdog demotions, and
        # cache-integrity quarantines
        report.degradations = (RZ.METRICS.delta(self._base_metrics)
                               .demotions + self.watchdog_demotions)
        report.quarantined = stats.delta(self._base_stats).quarantined
        report.repromotions = self.repromotions
        report.probes = self.probes
        report.probe_failures = self.probe_failures
        report.decode_backend = _backend_label(self.cfg)
        report.cache_alias_share = dict(self.cache_alias_share)
        if self.strict_no_recompile and report.decode_recompiles:
            raise RuntimeError(
                f"{report.decode_recompiles} pipeline recompiles after "
                "warmup — a steady-state decode step compiled a kernel "
                "(shape bucket or batch drifted out of the warmed set)")
        return report

    def _step(self, step: int, pending: deque, report: ServeReport,
              stats) -> Tuple[int, int]:
        """One tick: take arrivals, admit into free slots, advance every
        active slot one token.  Returns ``(n_prefill, n_decode)``."""
        jnp = self._jax.numpy
        now = time.perf_counter()
        while pending and pending[0].arrival_step <= step:
            req = pending.popleft()
            if (self.max_queue is not None
                    and len(self.queue) >= self.max_queue):
                # bounded admission: reject loudly instead of building
                # an unbounded backlog
                report.n_rejected += 1
                report.failures.append({
                    "rid": req.rid, "reason": "queue_full",
                    "step": step, "queue_depth": len(self.queue)})
            else:
                self._arrival[req.rid] = (now if req.arrival_s is None
                                          else req.arrival_s)
                self.queue.append(req)
        n_prefill = 0
        for slot in self._free_slots():
            if not self.queue:
                break
            req = self.queue.popleft()
            status = self._admit(req, slot, report, step)
            if status == "ok":
                n_prefill += 1
                report.prefill_tokens += len(req.prompt)
                report.decode_tokens += 1  # the prefill's first token
            elif status == "rejected":
                report.n_rejected += 1
            elif status == "deadline":
                report.n_deadline_evicted += 1
            # "poisoned" is counted inside _admit; the slot stays free
            # either way and co-batched sequences are untouched
        active = [i for i, s in enumerate(self.slots) if s is not None]
        n_decode = 0
        if active:
            try:
                logits, caches = self._decode_once()
                self._clean_ticks += 1
            except Exception as e:  # watchdog: demote, retry once
                before = stats.snapshot()
                self._watchdog_demote(e, step, report)
                logits, caches = self._decode_once()
                # the demoted decode's compiles are explained — keep
                # strict_no_recompile armed for *unexplained* ones
                self.demotion_compiles += stats.delta(before).compiles
                self._warm_stats = stats.snapshot()
                self._clean_ticks = 0
            self.caches = caches
            spec = RZ.fire("serve:logits")
            if spec is not None and spec.kind == "nan":
                # poison exactly one co-batched row; the guard below
                # must contain it to that sequence
                logits = logits.at[active[0], -1].set(jnp.nan)
            # cheap post-step guard: one finite-check over the new
            # logits row per slot, evict poisoned sequences instead of
            # letting NaNs propagate through their KV cache
            with span("engine.sync.decode_guard"):
                fin = np.asarray(jnp.all(jnp.isfinite(logits[:, -1]),
                                         axis=-1))
            with span("engine.sync.sample"):
                sampled = self._sample(logits)
            now = time.perf_counter()
            with span("engine.retire"):
                n_decode = self._retire(active, fin, sampled, now, step,
                                        report)
        # re-promotion: after enough clean ticks on a demoted rung, let
        # the breaker admit one half-open probe of the original
        if (self._demote_stack and self._ledger is not None
                and self._clean_ticks >= self.repromote_after
                and self._ledger.decision(
                    self._hkey, self._demote_stack[-1][1]) == "probe"):
            with span("engine.repromote"):
                self._probe_repromote(report, step, stats)
        return n_prefill, n_decode

    def _retire(self, active: List[int], fin: np.ndarray,
                sampled: np.ndarray, now: float, step: int,
                report: ServeReport) -> int:
        """Slot bookkeeping after a decode step: evict poisoned rows,
        append each sampled token (at host time ``now``), free finished,
        stalled and expired slots.  Returns the tokens emitted."""

        def free(i: int, s: _Slot) -> None:
            report.tokens[s.rid] = s.generated  # whole or partial output
            if s.timeline is not None:
                s.timeline.finish_s = now
            self.slots[i] = None

        for i in active:
            if bool(fin[i]):
                continue
            s = self.slots[i]
            report.n_poisoned += 1
            report.failures.append({
                "rid": s.rid, "reason": "nonfinite_logits",
                "step": step, "pos": s.pos})
            free(i, s)
        n_decode = 0
        for i in active:
            if not bool(fin[i]):
                continue
            s = self.slots[i]
            tok = int(sampled[i])
            s.pos += 1
            s.generated.append(tok)
            s.last_token = tok
            s.remaining -= 1
            n_decode += 1
            if s.timeline is not None:
                s.timeline.n_tokens += 1
            if s.remaining <= 0 or s.pos >= self.max_len:
                # finished (request satisfied) or stalled (slot
                # exhausted): free the slot for the queue
                if s.remaining > 0:
                    report.n_evicted_stalled += 1
                else:
                    report.n_completed += 1
                free(i, s)
            elif s.deadline is not None and step >= s.deadline:
                report.n_deadline_evicted += 1
                report.failures.append({
                    "rid": s.rid, "reason": "deadline",
                    "step": step, "deadline": s.deadline})
                free(i, s)
        return n_decode
