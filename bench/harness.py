"""One benchmark run: set-up, the measured window, and the check.

The window drives the serving engine's own loop,
``repro.launch.engine.Engine.run``, one tick per call
(``max_steps=1``).  Before each tick the harness hands over the requests
that are due (open loop) or that keep the backlog full (saturated);
after it, it reads which tokens each request has from the returned
report and ``engine.slots``, and stamps them with the host clock.  The
harness has no loop of its own around the model.

Everything that belongs to one configuration, traffic mix or per-layer
metric is in a file of its own, found by the name ``BENCHMARK.json``
gives it: ``configs/<config>.json``, ``traffic/<mix>.json``,
``metrics/<metric>.py`` and ``limits/<cell>.json``.

A model family comes in as files of its own too, found by the
configuration file's ``work`` key, ``<family>``:

* ``work/<family>.py``, the work the family needs, counted from the
  ``model`` block's shapes, for the per-layer readers:
  ``decode_kernel_calls(m, contexts)`` and ``prefill_kernel_calls(m, p)``
  (lists of ``(flops, bytes)`` kernel calls), ``least_time(calls,
  peak)``, ``decode_flops(m, context)`` and ``prefill_flops(m, p)``;
  ``phases.py`` also reads ``decode_attention_call(m, contexts)`` and
  ``swiglu_call(m, tokens)`` where a family has those kernels;
* ``references/<family>.py``, the plain float32 reference that decides
  ``correct``: ``logits(model, params, tokens, quant=False)`` gives the
  float32 logits of one token sequence (rows past it padding), reading
  the published ``model`` block and the benchmark's seeded weights by
  leaf name and importing nothing of the program; ``quant=True`` gives
  the fp8 control.

A configuration file's ``model`` block is the published configuration
as it is run.  It maps to the program's ``ModelConfig`` by
``model_config``: the published keys of ``MAPPED`` give their fields;
the optional ``program`` block, ``{<ModelConfig field>: value}``, gives
the sizes the program names differently from the checkpoint (experts,
experts per token, expert width, shared experts, leading dense layers,
LoRA ranks, qk-norm, window) and any mapped field whose key the source
does not publish; ``head_dim`` alone has a default, ``hidden_size //
num_attention_heads``.  Every other published key is listed, with its
reason, in the file's ``not_mapped`` block (a key whose value the
``program`` block restates under the program's name is listed there
too).  A key or field that is none of these, a mapped field given
twice, or one given nowhere stops the run with a ValueError before
anything compiles: nothing is dropped in silence.  The registry entry
named by ``arch`` gives only what the file does not state.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import itertools
import json
import os
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent
CACHE = BENCH / ".cache"
WORK = BENCH / "work"               # work/<family>.py
REFERENCES = BENCH / "references"   # references/<family>.py
CHECK_TOKENS = 300      # served tokens the check compares, at least


# ---------------------------------------------------------------------------
# files found by name
# ---------------------------------------------------------------------------

def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def _family_file(path: Path):
    if not path.exists():
        raise FileNotFoundError(f"no {path.parent.name}/{path.name} for "
                                f"the family {path.stem!r}")
    return load_module(path)


def work_of(config: dict):
    """The family's work counts, ``work/<family>.py``."""
    return _family_file(WORK / f"{config['work']}.py")


def reference_of(config: dict):
    """The family's plain reference, ``references/<family>.py``."""
    return _family_file(REFERENCES / f"{config['work']}.py")


@dataclass
class Cell:
    name: str
    config: dict            # configs/<config>.json
    mix: dict               # traffic/<mix>.json
    chips: int
    limits: dict            # limits/<cell>.json
    end_to_end: List[dict]  # this cell's entries of BENCHMARK.json
    per_layer: List[dict]

    @property
    def saturated(self) -> bool:
        return self.mix["kind"] == "saturated"

    @property
    def work(self):
        return work_of(self.config)

    @property
    def reference(self):
        return reference_of(self.config)


def _reports(metric: dict, cell: str, e2e_here: set) -> bool:
    """A metric with a ``workloads`` list is reported in those cells; an
    end-to-end metric without one in every cell, a per-layer metric
    without one wherever the metric it moves is reported."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_here


def load_cell(name: str, spec_path: Path = CHECKOUT / "BENCHMARK.json"
              ) -> Cell:
    from bench import traffic

    spec = load_json(spec_path)
    wl = {w["name"]: w for w in spec["workloads"]}
    if name not in wl:
        raise KeyError(f"no workload {name!r} in {spec_path.name}")
    w = wl[name]
    cfg = next(c for c in spec["configs"] if c["name"] == w["config"])
    e2e = [m for m in spec["end_to_end"] if _reports(m, name, set())]
    here = {m["name"] for m in e2e}
    per = [m for m in spec["per_layer"] if _reports(m, name, here)]
    config = load_json(CHECKOUT / cfg["file"])
    work_of(config)         # a family without its work counts or its
    reference_of(config)    # reference stops here, before serving
    return Cell(name=name, config=config,
                mix=traffic.load_mix(w["traffic"]), chips=int(w["chips"]),
                limits=load_json(BENCH / "limits" / f"{name}.json"),
                end_to_end=e2e, per_layer=per)


# ---------------------------------------------------------------------------
# caches and the engine
# ---------------------------------------------------------------------------

def configure_caches() -> None:
    """JAX's compilation cache and the pipeline's kernel-plan cache, at
    fixed paths inside the checkout (a set ``JAX_COMPILATION_CACHE_DIR``
    is left to JAX)."""
    import jax

    os.environ["REPRO_KERNEL_CACHE"] = str(CACHE / "kernels")
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    # cache every program, so that no later run compiles anything
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


# published key -> ModelConfig field
MAPPED = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "head_dim": "d_head", "intermediate_size": "d_ff",
    "vocab_size": "vocab", "max_position_embeddings": "max_seq",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "attention_bias": "qkv_bias", "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "dtype",
}


def model_config(config: dict):
    """The program's ModelConfig for a configuration file (see the
    module's docstring).  Raises ValueError, naming the key or field,
    where the file leaves a published key or a mapped field unaccounted
    for, gives a field twice, or names a field ModelConfig lacks."""
    import jax.numpy as jnp

    from repro import configs
    from repro.models.common import ModelConfig

    m, name = config["model"], config.get("name", "?")
    program = config.get("program", {})
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    for fld in program:
        if fld not in fields:
            raise ValueError(f"{name}: program field {fld!r} is not a "
                             "field of ModelConfig")
    values = dict(program)
    for key, fld in MAPPED.items():
        if key in m:
            if fld in program:
                raise ValueError(f"{name}: program field {fld!r} is already "
                                 f"set by the published key {key!r}")
            values[fld] = m[key]
        elif fld not in program:
            if key != "head_dim":
                raise ValueError(f"{name}: the published key {key!r} is "
                                 "missing and the program block sets no "
                                 f"{fld!r}")
            values[fld] = values["d_model"] // values["n_heads"]
    values.update(rope_theta=float(values["rope_theta"]),
                  norm_eps=float(values["norm_eps"]),
                  qkv_bias=bool(values["qkv_bias"]),
                  tie_embeddings=bool(values["tie_embeddings"]),
                  dtype=getattr(jnp, values["dtype"]))
    not_mapped = set(config.get("not_mapped", {}))
    wrong = sorted(not_mapped & set(MAPPED) | not_mapped - set(m))
    if wrong:
        raise ValueError(f"{name}: not_mapped lists {wrong}, which are "
                         "mapped or not published keys")
    stray = sorted(set(m) - set(MAPPED) - not_mapped)
    if stray:
        raise ValueError(f"{name}: published keys {stray} are neither "
                         "mapped nor listed under not_mapped")
    return dataclasses.replace(configs.get_config(config["arch"]), **values)


def build_engine(config: dict, mix: dict, seed: int):
    """The engine as ``repro.launch.serve.build_engine`` builds it (Pallas
    backend, compile ladder capped at the grouped rung, greedy), from
    this configuration, with the benchmark's seeded weights.

    Re-promotion is off (``repromote_after=None``), so the engine keeps
    no health ledger on disk and never starts demoted because an earlier
    run opened a breaker; a demotion inside a run still happens and is
    caught by the check (``numbers``)."""
    import jax

    from repro import configs, pipeline, resilience
    from repro.launch.engine import Engine

    from bench import weights

    options = pipeline.CompileOptions(
        backend="pallas",
        resilience=resilience.ResiliencePolicy(max_rung="grouped"))
    mc = configs.with_pipeline(model_config(config), options=options)
    e = mix["engine"]
    engine = Engine(mc, max_batch=e["max_batch"], max_len=e["max_len"],
                    prompt_buckets=tuple(e["prompt_buckets"]),
                    sampling="greedy", seed=seed % 2 ** 31,
                    repromote_after=None)
    like = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        engine.params)
    engine.params = None
    engine.params = weights.make(like, seed)
    return engine


def backend(cfg) -> str:
    """What serves the model: the pipeline backend when attention and the
    MLP both run through the pipeline, else "xla"."""
    if cfg.attn_impl != "pipeline" or cfg.mlp_impl != "pipeline":
        return "xla"
    opts = cfg.pipeline_options
    return opts.backend if opts is not None else cfg.pipeline_backend


def warm(engine, vocab: int) -> None:
    """Compile everything the window can touch: the engine's own warm-up
    (a prefill per bucket and the decode step), then one request per
    bucket through ``Engine.run`` for the host path's small programs."""
    from repro.launch.engine import Request

    engine.warmup()
    rng = np.random.default_rng(0)
    reqs = [Request(rid=-1 - i, prompt=tuple(
        int(t) for t in rng.integers(0, vocab, b)), max_new_tokens=2,
        arrival_step=0) for i, b in enumerate(engine.prompt_buckets)]
    engine.run(reqs)


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

@dataclass
class Req:
    rid: int
    prompt: tuple
    max_new: int
    due: float                          # host clock (perf_counter)
    times: List[float] = field(default_factory=list)
    admitted: Optional[float] = None    # start of the admitting tick
    tokens: Optional[List[int]] = None  # served tokens, once finished
    failure: Optional[str] = None


@dataclass
class Tick:
    t0: float
    t1: float = 0.0
    n_decode: int = 0
    contexts: List[int] = field(default_factory=list)  # per decoded token
    prefills: List[int] = field(default_factory=list)  # real prompt lengths


@dataclass
class Window:
    t0: float
    t_end: float
    t_close: float
    reqs: Dict[int, Req]
    ticks: List[Tick]
    compiles: int
    backends: tuple = ()                # engine rung at open and close
    demotions: int = 0                  # ladder + watchdog, engine lifetime
    fallbacks: int = 0                  # Pallas lowering fallbacks
    engine_failures: List[dict] = field(default_factory=list)  # no rid
    paused_s: float = 0.0               # host stalled starting the tracer
    traced: Optional[tuple] = None      # (first, last + 1) tick traced
    t_trace: Optional[float] = None     # when the tracer was started

    @property
    def t_counted(self) -> float:
        """End of the part the harness counts from: the tracer slows the
        host, so counts stop where it starts."""
        return self.t_close if self.t_trace is None else self.t_trace


class _Compiles:
    """Counts backend compiles while ``on``."""

    def __init__(self):
        from jax import monitoring

        self.on, self.n = False, 0
        monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **_):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def _harvest(engine, rep, tick: Tick, reqs: Dict[int, Req],
             orphans: List[dict]) -> None:
    seen = [(s.rid, s.generated, False) for s in engine.slots if s]
    seen += [(rid, toks, True) for rid, toks in rep.tokens.items()]
    for rid, toks, done in seen:
        r = reqs[rid]
        a, k = len(r.times), len(toks)
        if a == 0 and k:
            r.admitted = tick.t0
            tick.prefills.append(len(r.prompt))
        # token j (1-based) of a request past its first comes from a
        # decode step that attends over plen + j - 1 positions
        tick.contexts += [len(r.prompt) + j - 1
                          for j in range(max(a + 1, 2), k + 1)]
        r.times += [tick.t1] * (k - a)
        if done:
            r.tokens = list(toks)
    for f in rep.failures:
        if "rid" in f and f["rid"] in reqs:
            reqs[f["rid"]].failure = f["reason"]
        elif "rid" not in f:
            orphans.append(f)       # a watchdog demotion, say
    tick.n_decode = rep.per_step[0].n_decode if rep.per_step else 0


def serve(engine, cell: Cell, seed: int, seconds: float,
          trace_s: float = 0.0, trace_dir: Optional[Path] = None) -> Window:
    """Set the window up (a saturated cell first fills every slot), then
    measure ``seconds``; with ``trace_s`` trace that many seconds of
    whole ticks at the window's end."""
    import jax

    from repro.launch.engine import Request

    from bench import traffic

    mix, vocab = cell.mix, cell.config["model"]["vocab_size"]
    mb = mix["engine"]["max_batch"]
    if cell.saturated:
        backlog = int(mix["backlog"])
        source = traffic.stream(mix, seed, vocab)
    else:
        n = traffic.n_for_window(mix, seconds)
        items = traffic.generate(mix, seed, vocab, n)
        source = iter(items)
    reqs: Dict[int, Req] = {}
    nxt = 0
    compiles = _Compiles()
    orphans: List[dict] = []
    last = []                               # the newest tick's report

    def hand(k: int, now: float) -> list:
        nonlocal nxt
        out = []
        for it in itertools.islice(source, k):
            reqs[nxt] = Req(nxt, it.prompt, it.max_new_tokens,
                            due=now if cell.saturated else due(it))
            out.append(Request(rid=nxt, prompt=it.prompt,
                               max_new_tokens=it.max_new_tokens,
                               arrival_step=0))
            nxt += 1
        return out

    def due(it) -> float:
        # arrivals move with the tracer's start-up stall, as the window's
        # end does
        return t0 + paused + it.due_s

    ticks: List[Tick] = []

    def tick(handed: list) -> Tick:
        t = Tick(t0=time.perf_counter())
        with jax.profiler.TraceAnnotation("bench.tick"):
            rep = engine.run(handed, max_steps=1)
        t.t1 = time.perf_counter()
        _harvest(engine, rep, t, reqs, orphans)
        last[:] = [rep]
        return t

    if cell.saturated:
        tick(hand(mb + backlog, time.perf_counter()))
        if any(s is None for s in engine.slots):
            raise RuntimeError("the fill tick left a slot empty")
    backend_open = backend(engine.cfg)
    t0 = time.perf_counter()
    t_end = t0 + seconds
    trace_at = t0 + max(0.0, seconds - trace_s) if trace_s else None
    t_trace, first, paused = None, 0, 0.0
    compiles.on = True
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        if trace_at is not None and t_trace is None and now >= trace_at:
            # starting the profiler stalls the host for seconds; the
            # window and the arrivals are moved on by the stall, so that
            # it serves as long as an untraced one
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            t_trace, first = now, len(ticks)
            paused = time.perf_counter() - now
            t_end += paused
            continue
        if cell.saturated:
            handed = hand(max(0, backlog - len(engine.queue)), now)
        else:
            k = 0
            while nxt + k < n and due(items[nxt + k]) <= now:
                k += 1
            handed = hand(k, now)
        if not handed and not engine.queue and not any(engine.slots):
            wake = due(items[nxt]) if nxt < n else t_end
            with jax.profiler.TraceAnnotation("bench.wait_arrival"):
                time.sleep(max(0.0, min(wake, t_end) - now))
            continue
        ticks.append(tick(handed))
    compiles.on = False
    t_close = time.perf_counter()
    if t_trace is not None:
        jax.profiler.stop_trace()
    rep = last[0] if last else None
    return Window(
        t0=t0, t_end=t_end, t_close=t_close, reqs=reqs, ticks=ticks,
        compiles=compiles.n,
        backends=(backend_open, backend(engine.cfg)),
        demotions=max(rep.degradations if rep else 0,
                      engine.watchdog_demotions),
        fallbacks=engine.pallas_fallbacks, engine_failures=orphans,
        paused_s=paused, t_trace=t_trace,
        traced=None if t_trace is None else (first, len(ticks)))


# ---------------------------------------------------------------------------
# end-to-end metrics
# ---------------------------------------------------------------------------

def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def end_to_end(w: Window) -> Dict[str, float]:
    """Every number a user sees, over the whole window."""
    toks = sum(1 for r in w.reqs.values() for t in r.times if t > w.t0)
    gaps = [b - a for r in w.reqs.values()
            for a, b in zip(r.times, r.times[1:]) if a >= w.t0]
    ttft = [(r.times[0] if r.times else w.t_close) - r.due
            for r in w.reqs.values() if w.t0 <= r.due < w.t_end]
    out = {"tokens_per_s": toks / (w.t_close - w.t0)}
    if gaps:
        out["itl_p95_ms"] = percentile(gaps, 95) * 1e3
    if ttft:
        out["ttft_p90_ms"] = percentile(ttft, 90) * 1e3
    return out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

TRACE_S = 3.0           # seconds of whole ticks traced, at the window's end


@dataclass
class Readings:
    """What a per-layer reader (``metrics/<name>.py``) reads."""
    cell: Cell
    window: Window
    work: object
    peak: dict
    trace: object       # trace.Summary, or None

    @property
    def model(self) -> dict:
        return self.cell.config["model"]

    @property
    def max_batch(self) -> int:
        return self.cell.mix["engine"]["max_batch"]

    def traced_ticks(self) -> List[Tick]:
        if self.window.traced is None:
            return []
        a, b = self.window.traced
        return self.window.ticks[a:b]

    def counted_ticks(self) -> List[Tick]:
        """The window's ticks before the tracer started, which it did
        not slow."""
        w = self.window
        return w.ticks if w.traced is None else w.ticks[:w.traced[0]]

    @property
    def counted_s(self) -> float:
        return self.window.t_counted - self.window.t0


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

AT_LEAST = ("tokens_compared",)     # every other number is at most its limit


def checked(w: Window) -> List[Req]:
    """The requests the check may sample: finished, unfailed, and with a
    token served inside the window."""
    return [r for r in w.reqs.values() if r.tokens is not None
            and r.failure is None and r.times[-1] > w.t0]


def numbers(cell: Cell, w: Window, gap: float, compared: int) -> dict:
    """Every number the check compares, each beside its limit."""
    return {
        "widest_gap": {"value": gap,
                       "limit": float(cell.limits["widest_gap"])},
        "tokens_compared": {"value": compared,
                            "limit": check_tokens(cell)},
        "failed_requests": {"value": sum(1 for r in w.reqs.values()
                                         if r.failure), "limit": 0},
        "engine_failures": {"value": len(w.engine_failures), "limit": 0},
        "compiles_in_window": {"value": w.compiles, "limit": 0},
        "demotions": {"value": w.demotions + w.fallbacks, "limit": 0},
        "off_pallas": {"value": sum(b != "pallas" for b in w.backends),
                       "limit": 0},
    }


def check_tokens(cell: Cell) -> int:
    return int(cell.limits.get("tokens_compared", CHECK_TOKENS))


def passes(nums: dict) -> bool:
    """``correct``: every number on the right side of its limit."""
    return all(v["value"] >= v["limit"] if k in AT_LEAST
               else v["value"] <= v["limit"] for k, v in nums.items())


def peak_of(kind: str) -> dict:
    peaks = load_json(BENCH / "peaks.json")
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return peaks[kind]


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, log=sys.stderr, control: bool = False) -> dict:
    """Set up, measure, check; returns the result line's object.  With
    ``control`` the check judges, in place of the served tokens, the
    tokens that the fp8 reference puts first at the same positions (the
    control; ``check.py``), and has to find them not correct."""
    import shutil

    import jax

    from bench import check, trace as tr

    devices = jax.devices()
    dev = devices[0]
    peak = peak_of(dev.device_kind) if dev.platform == "tpu" else None
    m = cell.config["model"]
    engine = build_engine(cell.config, cell.mix, seed)
    warm(engine, m["vocab_size"])
    trace_dir = CACHE / "trace" / cell.name
    shutil.rmtree(trace_dir, ignore_errors=True)
    w = serve(engine, cell, seed, seconds,
              trace_s=TRACE_S if trace else 0.0, trace_dir=trace_dir)
    setup_s = w.t0 - t_start
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": stats.get("peak_bytes_in_use")}

    # the program's state goes before the reference runs
    params = engine.params
    engine.caches = None
    del engine

    result = {"correct": False, "attempted": len(w.reqs),
              "failed": sum(1 for r in w.reqs.values() if r.failure)}
    breakdown = None
    if trace:
        summary = tr.summarize(tr.load(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        rd = Readings(cell, w, cell.work, peak, summary)
        metrics = {}
        for spec in cell.per_layer:
            v = load_module(BENCH / "metrics" / f"{spec['name']}.py").read(rd)
            if v is not None:
                metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        breakdown = {"device_ops": summary.top_ops,
                     "idle_gaps": summary.idle_gaps}
    else:
        values = end_to_end(w)
        values["setup_s"] = setup_s
        metrics = {s["name"]: {"value": values[s["name"]], "unit": s["unit"]}
                   for s in cell.end_to_end if s["name"] in values}
    slow = sorted(w.ticks, key=lambda k: k.t0 - k.t1)[:3]
    print(f"bench: setup_s={setup_s!r} window_s={w.t_close - w.t0!r} "
          f"tracer_stall_s={w.paused_s!r} "
          f"ticks={len(w.ticks)} requests={len(w.reqs)} slowest ticks "
          + ", ".join(f"{(k.t1 - k.t0) * 1e3:.1f} ms at "
                      f"{k.t0 - w.t0:.1f} s" for k in slow),
          file=log, flush=True)

    picked = check.sample(checked(w), seed, check_tokens(cell))
    t_check = time.perf_counter()
    gap, gap_c, compared = check.widest_gaps(
        cell.reference, cell.config["model"], params, picked,
        control=control)
    nums = numbers(cell, w, gap_c if control else gap, compared)
    result["correct"] = passes(nums)
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = nums
    print(f"bench: check {time.perf_counter() - t_check:.2f} s over "
          f"{len(picked)} requests"
          + (" (the fp8 control in the program's place)" if control
             else ""), file=log)
    for k, v in nums.items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r}"
              + (", at least" if k in AT_LEAST else "") + ")", file=log)
    log.flush()
    return result
