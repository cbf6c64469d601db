"""The resilience layer: degradation-ladder compiles under injected
faults (matrix over every in-repo program, outputs pinned to the
interpreter oracle), kernel-cache integrity (checksums, quarantine,
named counters), serving-engine fault isolation (poison eviction with
co-batched oracle match, watchdog demotion, bounded admission,
deadlines), the deterministic FaultPlan machinery itself, and that no
failure leaves state behind for the next process."""

import dataclasses
import json
import pickle

import numpy as np
import pytest

from repro import configs, pipeline
from repro import resilience as RZ
from repro.pipeline import cache as C

from test_lowering_coverage import PROGRAMS, _merged_inputs


@pytest.fixture()
def fresh_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_CACHE", str(tmp_path))
    pipeline.reset_default_cache()
    yield tmp_path
    pipeline.reset_default_cache()


@pytest.fixture(autouse=True)
def _no_env_plan(monkeypatch):
    monkeypatch.delenv("REPRO_FAULT_PLAN", raising=False)
    RZ.install(None)
    yield
    RZ.install(None)


def _mem_cache():
    return pipeline.KernelCache(disk=False)


# ---------------------------------------------------------------------------
# ladder primitives
# ---------------------------------------------------------------------------

def test_ladder_order_and_rungs_from():
    assert RZ.LADDER == ("grouped", "ungrouped", "jax", "interpreter")
    assert RZ.start_rung("pallas", True) == "grouped"
    assert RZ.start_rung("pallas", False) == "ungrouped"
    assert RZ.start_rung("jax", True) == "jax"
    assert RZ.start_rung("py", True) == "interpreter"
    assert RZ.rungs_from("grouped", "interpreter") == RZ.LADDER
    assert RZ.rungs_from("ungrouped", "jax") == ("ungrouped", "jax")
    # a max_rung ABOVE the start permits no demotion at all
    assert RZ.rungs_from("jax", "grouped") == ("jax",)
    with pytest.raises(ValueError):
        RZ.rung_index("warp-speed")


def test_policy_is_frozen_hashable_and_keyed():
    assert [f.name for f in dataclasses.fields(RZ.ResiliencePolicy)] == [
        "max_rung"]
    p = RZ.ResiliencePolicy(max_rung="jax")
    assert hash(p) != hash(RZ.DEFAULT_POLICY)
    assert p.key() == ("jax",)
    assert p == RZ.ResiliencePolicy(max_rung="jax")
    with pytest.raises(dataclasses.FrozenInstanceError):
        p.max_rung = "grouped"
    with pytest.raises(ValueError):
        RZ.ResiliencePolicy(max_rung="nope")
    # non-default policies land in the cache-key opts; the default stays
    # byte-identical to pre-resilience builds
    base = pipeline.CompileOptions(backend="jax")
    keyed = pipeline.CompileOptions(backend="jax", resilience=p)
    assert base.cache_opts(stabilized=False, autotuned=False) == \
        pipeline.CompileOptions(
            backend="jax",
            resilience=RZ.ResiliencePolicy()).cache_opts(
                stabilized=False, autotuned=False)
    assert ("resilience", p.key()) in keyed.cache_opts(
        stabilized=False, autotuned=False)
    assert base != keyed


# ---------------------------------------------------------------------------
# the fault-injection matrix: every program x injected compile faults,
# output pinned to the interpreter oracle, report names the served rung
# ---------------------------------------------------------------------------

_FAULT_MATRIX = [
    # (faulted sites, expected served rung, expected demotions)
    (("compile:grouped",), "ungrouped", 1),
    (("compile:grouped", "compile:ungrouped"), "jax", 2),
    (("compile:grouped", "compile:ungrouped", "compile:jax"),
     "interpreter", 3),
]


@pytest.mark.parametrize("name", sorted(PROGRAMS))
@pytest.mark.parametrize("sites,rung,demotions", _FAULT_MATRIX)
def test_ladder_matrix_matches_interpreter_oracle(name, sites, rung,
                                                  demotions):
    build, dims, blocks = PROGRAMS[name]
    g = build()
    oracle = pipeline.compile(g, dims, backend="py", cache=_mem_cache())
    inputs = _merged_inputs(g, dims, blocks,
                            np.random.default_rng(0))
    expect = oracle(dict(inputs))

    plan = RZ.FaultPlan([RZ.FaultSpec(site=s) for s in sites])
    with RZ.faults(plan), pytest.warns(RuntimeWarning,
                                       match="compile ladder"):
        kern = pipeline.compile(g, dims, backend="pallas", blocks=blocks,
                                cache=_mem_cache())
    rr = kern.resilience_report
    assert rr is not None and rr.rung == rung == kern.rung
    assert rr.requested == "grouped"
    assert rr.demotions == demotions
    assert len(rr.errors) == len(sites)
    assert all("InjectedFault" in e for e in rr.errors)
    assert plan.fired_count() == len(sites)
    got = kern(dict(inputs))
    for nm in expect:
        np.testing.assert_allclose(np.asarray(got[nm]),
                                   np.asarray(expect[nm]),
                                   rtol=2e-4, atol=2e-4)


def test_happy_path_report_is_one_ok_attempt(fresh_cache):
    build, dims, blocks = PROGRAMS["layernorm_matmul"]
    before = RZ.METRICS.snapshot()
    kern = pipeline.compile(build(), dims, backend="pallas",
                            blocks=blocks, cache=_mem_cache())
    rr = kern.resilience_report
    assert rr.rung == rr.requested == "grouped"
    assert rr.demotions == 0 and rr.errors == []
    assert [a.ok for a in rr.attempts] == [True]
    d = RZ.METRICS.delta(before)
    assert d.demotions == 0 and d.faults_fired == 0
    # and the report is JSON-serializable provenance
    js = json.loads(json.dumps(rr.to_json()))
    assert js["demotions"] == 0 and js["rung"] == "grouped"


@pytest.mark.parametrize("name", sorted(PROGRAMS))
@pytest.mark.parametrize("max_rung", ["grouped", "ungrouped"])
def test_bounded_max_rung_exhaustion_raises_ladder_error(name, max_rung):
    """Every rung down to ``max_rung`` fails once: the compile raises
    with one attempt per rung and serves nothing.  ``grouped`` is the
    benchmark's own policy: a grouped failure is not demoted past."""
    build, dims, blocks = PROGRAMS[name]
    rungs = RZ.rungs_from("grouped", max_rung)
    plan = RZ.FaultPlan([RZ.FaultSpec(site=f"compile:{r}") for r in rungs])
    opts = pipeline.CompileOptions(
        backend="pallas", blocks=blocks,
        resilience=RZ.ResiliencePolicy(max_rung=max_rung))
    before = RZ.METRICS.snapshot()
    cache = _mem_cache()
    with RZ.faults(plan), pytest.raises(RZ.LadderError) as ei:
        pipeline.compile(build(), dims, options=opts, cache=cache)
    rep = ei.value.report
    assert [a.rung for a in rep.attempts] == list(rungs)
    assert not any(a.ok for a in rep.attempts) and rep.rung is None
    assert all("InjectedFault" in e for e in rep.errors)
    d = RZ.METRICS.delta(before)
    assert d.ladder_failures == 1
    assert d.demotions == len(rungs) - 1
    # nothing was cached for the failed compile: the next one, with the
    # faults spent, lowers at the grouped rung
    assert pipeline.compile(build(), dims, options=opts,
                            cache=cache).rung == "grouped"


def test_config_errors_raise_instead_of_demoting():
    """User mistakes (pallas without blocks) are not failures to survive:
    they raise before any rung runs."""
    build, dims, _ = PROGRAMS["layernorm_matmul"]
    with pytest.raises(ValueError, match="blocks"):
        pipeline.compile(build(), dims, backend="pallas",
                         cache=_mem_cache())


# ---------------------------------------------------------------------------
# fault plan machinery
# ---------------------------------------------------------------------------

def test_fault_plan_is_deterministic_and_roundtrips():
    spec = RZ.FaultSpec(site="compile:grouped", indices=(1, 3),
                        kind="raise", message="boom")
    plan = RZ.FaultPlan([spec], seed=7)
    fired = [plan.fire("compile:grouped") is not None for _ in range(5)]
    assert fired == [False, True, False, True, False]
    assert plan.calls("compile:grouped") == 5
    assert plan.fired_count() == 2
    assert plan.expected_count("compile:") == 2

    plan2 = RZ.FaultPlan.from_json(
        json.loads(json.dumps(plan.to_json())))
    assert plan2.seed == 7 and plan2.specs == (spec,)
    fired2 = [plan2.fire("compile:grouped") is not None for _ in range(5)]
    assert fired2 == fired  # same plan, same schedule, every run

    plan.reset()
    assert plan.calls("compile:grouped") == 0 and plan.fired_count() == 0
    with pytest.raises(ValueError, match="fault kind"):
        RZ.FaultSpec(site="x", kind="explode")


def test_env_var_activates_plan(monkeypatch):
    raw = json.dumps({"seed": 1, "faults": [
        {"site": "compile:grouped", "indices": [0]}]})
    monkeypatch.setenv("REPRO_FAULT_PLAN", raw)
    plan = RZ.active()
    assert plan is not None and plan.seed == 1
    assert RZ.active() is plan  # cached per env value: counters survive
    with pytest.raises(RZ.InjectedFault):
        RZ.check("compile:grouped")


# ---------------------------------------------------------------------------
# cache integrity: checksums, quarantine, named counters, atomic writes
# ---------------------------------------------------------------------------

def _kc(tmp_path):
    return C.KernelCache(root=tmp_path)


def _seed_entry(kc, with_graph=True):
    from repro.core import array_program as AP
    key = C.CacheKey.make("fp-test", "jax", {"M": 2}, None, True)
    plan = C.CachePlan(0, {"M": 2}, 1.0, (1.0, 2.0), 2.0)
    kc.put_plan(key, plan,
                AP.layernorm_matmul_program(32.0) if with_graph else None)
    return key, plan


def test_plan_roundtrip_and_checksum_envelope(tmp_path):
    kc = _kc(tmp_path)
    key, plan = _seed_entry(kc)
    got, graph = kc.get_plan(key)
    assert got == plan and graph is not None
    env = json.loads((tmp_path / f"{key.digest()}.json").read_text())
    assert env["schema"] == C._SCHEMA_VERSION
    assert len(env["sha256"]) == 64
    # no stray temp files after the atomic write
    assert not list(tmp_path.glob("*.tmp"))


def test_missing_entry_is_a_plain_miss_no_counters(tmp_path):
    kc = _kc(tmp_path)
    key = C.CacheKey.make("nope", "jax", {"M": 2}, None, True)
    assert kc.get_plan(key) == (None, None)
    st = kc.stats
    assert (st.corrupt_plans, st.corrupt_graphs, st.quarantined,
            st.io_errors) == (0, 0, 0, 0)


@pytest.mark.parametrize("mutate,reason", [
    (lambda b: b[: len(b) // 2], "truncated"),
    (lambda b: b"\xffgarbage" + b[8:], "garbled bytes"),
    (lambda b: b.replace(b'"snapshot_index": 0',
                         b'"snapshot_index": 9'), "checksum mismatch"),
    (lambda b: json.dumps({"schema": 3, "sha256": "0" * 64,
                           "plan": {}}).encode(), "stale schema"),
])
def test_corrupt_plan_quarantined_counted_warned(tmp_path, mutate, reason):
    import re
    kc = _kc(tmp_path)
    key, _ = _seed_entry(kc)
    pj = tmp_path / f"{key.digest()}.json"
    pj.write_bytes(mutate(pj.read_bytes()))
    # the satellite contract: the warning names the offending path
    with pytest.warns(RuntimeWarning, match=re.escape(str(pj))):
        assert kc.get_plan(key) == (None, None), reason
    assert kc.stats.corrupt_plans == 1
    # plan AND its paired graph move aside for triage (never deleted)
    assert kc.stats.quarantined == 2
    qdir = tmp_path / "quarantine"
    assert sorted(p.name for p in qdir.iterdir()) == sorted(
        [pj.name, f"{key.digest()}.graph.pkl"])
    # the entry is gone from the hot path: next read is a plain miss
    assert kc.get_plan(key) == (None, None)
    assert kc.stats.corrupt_plans == 1


def test_corrupt_graph_degrades_to_plan_only(tmp_path):
    kc = _kc(tmp_path)
    key, plan = _seed_entry(kc)
    pg = tmp_path / f"{key.digest()}.graph.pkl"
    blob = pg.read_bytes()
    pg.write_bytes(blob[:-10])  # truncate the pickle payload
    with pytest.warns(RuntimeWarning, match="corrupt graph"):
        got, graph = kc.get_plan(key)
    assert got == plan and graph is None  # plan survives, graph gone
    assert kc.stats.corrupt_graphs == 1 and kc.stats.quarantined == 1
    assert kc.stats.disk_hits == 1


def test_graph_missing_magic_header_rejected(tmp_path):
    kc = _kc(tmp_path)
    key, plan = _seed_entry(kc)
    pg = tmp_path / f"{key.digest()}.graph.pkl"
    # a legacy headerless pickle must not be trusted
    pg.write_bytes(pickle.dumps({"not": "a graph"}))
    with pytest.warns(RuntimeWarning, match="integrity header"):
        got, graph = kc.get_plan(key)
    assert got == plan and graph is None
    assert kc.stats.corrupt_graphs == 1


def test_write_failure_counts_and_warns(tmp_path):
    blocker = tmp_path / "blocked"
    blocker.write_text("a regular file where the cache dir should be")
    kc = C.KernelCache(root=blocker / "sub")
    from repro.core import array_program as AP
    key = C.CacheKey.make("fp", "jax", {"M": 2}, None, True)
    with pytest.warns(RuntimeWarning, match="failed to write plan"):
        kc.put_plan(key, C.CachePlan(0, {"M": 2}, 1.0, (1.0,), 2.0),
                    AP.layernorm_matmul_program(32.0))
    assert kc.stats.write_errors == 1
    assert kc.stats.misses == 1  # still counted as a compile-path miss


def test_unpicklable_graph_is_plan_only_with_counter(tmp_path):
    from repro.core import array_program as AP
    g = AP.layernorm_matmul_program(32.0)
    g._poison = lambda x: x  # closures don't pickle
    kc = _kc(tmp_path)
    key = C.CacheKey.make("fp-unpick", "jax", {"M": 2}, None, True)
    with pytest.warns(RuntimeWarning, match="plan-only"):
        kc.put_plan(key, C.CachePlan(0, {"M": 2}, 1.0, (1.0,), 2.0), g)
    assert kc.stats.write_errors == 1
    got, graph = kc.get_plan(key)
    assert got is not None and graph is None


def test_cache_stats_snapshot_delta_cover_all_counters(tmp_path):
    st = C.CacheStats(memory_hits=3, disk_hits=1, misses=2,
                      corrupt_plans=4, quarantined=5)
    snap = st.snapshot()
    st.quarantined += 2
    st.io_errors += 1
    d = st.delta(snap)
    assert (d.quarantined, d.io_errors, d.corrupt_plans) == (2, 1, 0)
    assert d.memory_hits == 0


def test_injected_cache_corruption_drives_real_machinery(fresh_cache):
    """The chaos-CI path: a 'corrupt' fault garbles the REAL on-disk
    entry; detection, quarantine, and recompile all run for real."""
    from repro.core import array_program as AP
    g = AP.layernorm_matmul_program(32.0)
    dims = {"M": 2, "K": 4, "N": 2}
    k1 = pipeline.compile(g, dims, backend="jax")
    assert k1.cache_hit is None
    pipeline.reset_default_cache()
    plan = RZ.FaultPlan([RZ.FaultSpec(site="cache:get_plan",
                                      kind="corrupt")])
    with RZ.faults(plan), pytest.warns(RuntimeWarning,
                                       match="corrupt plan"):
        k2 = pipeline.compile(g, dims, backend="jax")
    assert k2.cache_hit is None  # quarantined -> honest miss
    st = pipeline.default_cache().stats
    assert st.corrupt_plans == 1 and st.quarantined >= 1
    # the rewritten entry serves the next compile from disk again
    pipeline.reset_default_cache()
    assert pipeline.compile(g, dims, backend="jax").cache_hit == "disk"


# ---------------------------------------------------------------------------
# serving isolation: poison eviction, watchdog, admission bounds, deadlines
# ---------------------------------------------------------------------------

def _tiny_cfg(backend="jax", **overrides):
    """A tiny smollm; ``backend="xla"`` keeps the plain XLA kernels, the
    bottom rung of the serving ladder."""
    mc = configs.get_reduced_config(
        "smollm-135m", n_layers=2, d_model=64, n_heads=2, n_kv_heads=2,
        d_head=32, d_ff=128, vocab=128, **overrides)
    if backend == "xla":
        return mc
    return configs.with_pipeline(
        mc, options=pipeline.CompileOptions(backend=backend))


def _plant_decode(engine, index: int, fault: str) -> None:
    """Warm ``engine`` up, then wrap its ``_decode`` so the ``index``-th
    call of the run loop (0-based) raises (``fault="raise"``) or returns
    a NaN logits row for the first active slot (``fault="nan"``)."""
    import jax.numpy as jnp

    engine.warmup()
    decode, calls = engine._decode, []

    def planted(params, caches, tokens, pos):
        calls.append(1)
        if len(calls) - 1 == index and fault == "raise":
            raise RuntimeError("planted decode fault")
        logits, caches = decode(params, caches, tokens, pos)
        if len(calls) - 1 == index and fault == "nan":
            row = next(i for i, s in enumerate(engine.slots) if s)
            logits = logits.at[row, -1].set(jnp.nan)
        return logits, caches

    engine._decode = planted


def _oracle(engine, req):
    """Per-sequence sequential greedy decode — no batching, no padding."""
    import jax
    import jax.numpy as jnp
    m, params = engine.model, engine.params
    decode = jax.jit(m.decode_step)
    prompt = jnp.asarray(req.prompt)[None, :]
    lg, cache = m.prefill(params, prompt, max_len=engine.max_len)
    tok = int(jnp.argmax(lg[0, -1]))
    toks = [tok]
    pos = len(req.prompt)
    for _ in range(req.max_new_tokens - 1):
        lg, cache = decode(params, cache, jnp.asarray([[tok]]),
                           jnp.asarray(pos))
        tok = int(jnp.argmax(lg[0, -1]))
        toks.append(tok)
        pos += 1
    return toks


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_poison_request_evicted_cobatched_match_oracle(fresh_cache,
                                                       backend):
    """The isolation acceptance: one NaN-logits request is evicted with
    a structured failure record while every co-batched sequence's tokens
    exactly match the sequential-decode oracle."""
    from repro.launch.engine import Engine, synth_trace
    engine = Engine(_tiny_cfg(backend), max_batch=3, max_len=48,
                    prompt_buckets=(8, 16), sampling="greedy", seed=0)
    trace = synth_trace(6, seed=3, arrival_rate=1.5, prompt_lens=(3, 14),
                        gen_lens=(3, 6), vocab=engine.cfg.vocab)
    _plant_decode(engine, 1, "nan")
    report = engine.run(trace)
    assert report.n_poisoned == 1
    bad = [f for f in report.failures
           if f["reason"] == "nonfinite_logits"]
    assert len(bad) == 1 and "rid" in bad[0] and "step" in bad[0]
    poisoned_rid = bad[0]["rid"]
    assert report.n_completed == len(trace) - 1
    for req in trace:
        if req.rid == poisoned_rid:
            continue  # evicted with partial tokens; the rest are exact
        assert report.tokens[req.rid] == _oracle(engine, req), (
            f"co-batched request {req.rid} diverged after the poison "
            "eviction")


@pytest.mark.parametrize("start,demoted_to", [
    ("pallas", "pipeline-jax"), ("jax", "xla"), ("xla", None)])
def test_watchdog_demotes_decode_and_keeps_serving(fresh_cache, start,
                                                   demoted_to):
    """A decode-step crash mid-run demotes the kernel one rung and the
    run completes; tokens still match the oracle on the ORIGINAL impl
    (the demoted backend computes the same function).  On the bottom
    rung there is nothing to demote to: the crash re-raises."""
    from repro.launch.engine import Engine, synth_trace
    engine = Engine(_tiny_cfg(start), max_batch=2, max_len=32,
                    prompt_buckets=(8,), sampling="greedy", seed=0)
    trace = synth_trace(4, seed=1, arrival_rate=1.0, prompt_lens=(2, 7),
                        gen_lens=(3, 5), vocab=engine.cfg.vocab)
    _plant_decode(engine, 1, "raise")
    if demoted_to is None:
        with pytest.raises(RuntimeError, match="planted decode fault"):
            engine.run(trace)
        assert engine.watchdog_demotions == 0
        return
    oracle_engine = Engine(_tiny_cfg(start), max_batch=2, max_len=32,
                           prompt_buckets=(8,), sampling="greedy", seed=0)
    with pytest.warns(RuntimeWarning, match="serve watchdog"):
        report = engine.run(trace)
    assert report.n_completed == len(trace)
    assert engine.watchdog_demotions == 1
    assert report.degradations >= 1
    assert report.decode_backend == demoted_to
    demos = [f for f in report.failures
             if f["reason"] == "decode_demotion"]
    assert len(demos) == 1 and demos[0]["to"] == demoted_to
    # strict_no_recompile stayed armed: the demotion compiles were
    # explained, and nothing else compiled
    assert report.decode_recompiles == 0
    for req in trace:
        assert report.tokens[req.rid] == _oracle(oracle_engine, req)


def test_bounded_admission_rejects_with_record(fresh_cache):
    from repro.launch.engine import Engine, Request
    engine = Engine(_tiny_cfg("jax"), max_batch=1, max_len=32,
                    prompt_buckets=(8,), sampling="greedy", seed=0,
                    max_queue=1)
    trace = [Request(rid=i, prompt=(1, 2, 3), max_new_tokens=3,
                     arrival_step=0) for i in range(5)]
    report = engine.run(trace)
    overflows = [f for f in report.failures
                 if f["reason"] == "queue_full"]
    assert report.n_rejected == len(overflows) > 0
    assert report.max_queue_depth <= 1
    assert report.n_completed == len(trace) - report.n_rejected


def test_deadline_evicts_queued_and_active(fresh_cache):
    from repro.launch.engine import Engine, Request
    engine = Engine(_tiny_cfg("jax"), max_batch=1, max_len=48,
                    prompt_buckets=(8,), sampling="greedy", seed=0)
    trace = [
        # hogs the only slot for a while
        Request(rid=0, prompt=(1, 2, 3), max_new_tokens=12,
                arrival_step=0),
        # active eviction: admitted but cut off mid-generation
        Request(rid=1, prompt=(4, 5, 6), max_new_tokens=12,
                arrival_step=0, deadline_step=14),
        # queued eviction: expires while waiting behind the others
        Request(rid=2, prompt=(7, 8), max_new_tokens=4,
                arrival_step=0, deadline_step=2),
    ]
    report = engine.run(trace)
    assert report.n_deadline_evicted == 2
    reasons = sorted(f["reason"] for f in report.failures)
    assert reasons == ["deadline", "deadline_queued"]
    assert report.n_completed == 1
    assert 0 < len(report.tokens[1]) < 12  # partial output recorded


def test_clean_serve_run_has_zero_resilience_counters(fresh_cache):
    from repro.launch.engine import Engine, synth_trace
    engine = Engine(_tiny_cfg("jax"), max_batch=2, max_len=32,
                    prompt_buckets=(8,), sampling="greedy", seed=0)
    trace = synth_trace(3, seed=0, arrival_rate=1.0, prompt_lens=(2, 6),
                        gen_lens=(2, 4), vocab=engine.cfg.vocab)
    report = engine.run(trace)
    assert report.degradations == 0
    assert report.quarantined == 0
    assert report.n_poisoned == 0
    assert report.n_deadline_evicted == 0
    assert report.failures == []
    assert report.decode_backend == "pipeline-jax"
    # the counters serialize with the report, and only these
    d = json.loads(json.dumps(report.to_json()))
    assert d["degradations"] == 0 and d["failures"] == []
    assert {"n_poisoned", "n_deadline_evicted", "degradations",
            "quarantined", "decode_backend"} <= set(d)
    assert not {"repromotions", "probes", "probe_failures"} & set(d)


def test_engine_accepts_only_none_for_repromote_after(fresh_cache):
    from repro.launch.engine import Engine
    with pytest.raises(ValueError, match="repromote_after"):
        Engine(_tiny_cfg("jax"), repromote_after=8)
    assert Engine(_tiny_cfg("jax"), repromote_after=None).cfg is not None


# ---------------------------------------------------------------------------
# no failure leaves state behind for the next process
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("where", ["compile", "serve"])
def test_failure_leaves_no_state_for_the_next_process(fresh_cache, where):
    """A faulted compile, or a watchdog demotion mid-serve, on a disk
    cache writes nothing under ``<root>/health/``; a fresh cache or
    engine on the same root starts at the top rung again."""
    root = fresh_cache
    if where == "compile":
        build, dims, blocks = PROGRAMS["layernorm_matmul"]
        plan = RZ.FaultPlan([RZ.FaultSpec(site="compile:grouped")])
        with RZ.faults(plan), pytest.warns(RuntimeWarning,
                                           match="compile ladder"):
            kern = pipeline.compile(build(), dims, backend="pallas",
                                    blocks=blocks,
                                    cache=C.KernelCache(root=root))
        assert kern.rung == "ungrouped"
        assert not (root / "health").exists()
        again = pipeline.compile(build(), dims, backend="pallas",
                                 blocks=blocks,
                                 cache=C.KernelCache(root=root))
        assert again.rung == "grouped"
    else:
        from repro.launch.engine import Engine, synth_trace
        engine = Engine(_tiny_cfg("pallas"), max_batch=2, max_len=32,
                        prompt_buckets=(8,), sampling="greedy", seed=0)
        trace = synth_trace(3, seed=1, arrival_rate=1.0,
                            prompt_lens=(2, 7), gen_lens=(3, 4),
                            vocab=engine.cfg.vocab)
        _plant_decode(engine, 1, "raise")
        with pytest.warns(RuntimeWarning, match="serve watchdog"):
            report = engine.run(trace)
        assert report.decode_backend == "pipeline-jax"
        assert not (root / "health").exists()
        pipeline.reset_default_cache()
        fresh = Engine(_tiny_cfg("pallas"), max_batch=2, max_len=32,
                       prompt_buckets=(8,), sampling="greedy", seed=0)
        assert fresh.run(trace).decode_backend == "pipeline-pallas"
    assert not (root / "health").exists()
