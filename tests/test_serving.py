"""Continuous-batching serving loop: oracle differential (ragged mixed
prefill+decode tokens == per-sequence sequential decode), scheduler
invariants, and the zero-recompile cache-stats pin."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, pipeline
from repro.launch import serve as S
from repro.launch.engine import Engine, Request, synth_trace


@pytest.fixture()
def fresh_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_CACHE", str(tmp_path))
    pipeline.reset_default_cache()
    yield
    pipeline.reset_default_cache()


def _tiny_cfg(backend="jax", **overrides):
    mc = configs.get_reduced_config(
        "smollm-135m", n_layers=2, d_model=64, n_heads=2, n_kv_heads=2,
        d_head=32, d_ff=128, vocab=128, **overrides)
    return configs.with_pipeline(
        mc, options=pipeline.CompileOptions(backend=backend))


_ORACLE_DECODE = {}


def _oracle_decode(engine):
    # one jitted single-sequence decode step per engine (sharing it
    # across requests keeps the oracle loop out of retrace purgatory)
    fn = _ORACLE_DECODE.get(id(engine))
    if fn is None:
        fn = _ORACLE_DECODE[id(engine)] = jax.jit(engine.model.decode_step)
    return fn


def _oracle(engine, req):
    """Per-sequence sequential greedy decode — no batching, no padding."""
    m, params = engine.model, engine.params
    decode = _oracle_decode(engine)
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


def test_ragged_trace_matches_sequential_oracle(fresh_cache):
    """The acceptance differential: a ragged mixed prefill+decode trace
    (varying per-sequence positions and occupancy) must emit tokens
    IDENTICAL to decoding each sequence alone."""
    engine = Engine(_tiny_cfg("jax"), max_batch=3, max_len=48,
                    prompt_buckets=(8, 16), sampling="greedy", seed=0)
    trace = synth_trace(7, seed=3, arrival_rate=1.5, prompt_lens=(3, 14),
                        gen_lens=(2, 6), vocab=engine.cfg.vocab)
    report = engine.run(trace)
    assert report.n_completed == len(trace)
    assert report.n_rejected == 0 and report.n_evicted_stalled == 0
    for req in trace:
        assert report.tokens[req.rid] == _oracle(engine, req), (
            f"request {req.rid} diverged from the sequential oracle")


def test_admission_eviction_invariants(fresh_cache):
    """Occupancy never exceeds the slot count, the queue builds under
    overload and drains, every request is accounted for exactly once,
    and oversized requests are rejected, not wedged."""
    engine = Engine(_tiny_cfg("jax"), max_batch=2, max_len=32,
                    prompt_buckets=(8,), sampling="greedy", seed=0)
    trace = [Request(rid=i, prompt=tuple(range(1, 7)), max_new_tokens=4,
                     arrival_step=0) for i in range(5)]
    # prompt longer than every bucket -> must be rejected
    trace.append(Request(rid=5, prompt=tuple(range(1, 15)),
                         max_new_tokens=4, arrival_step=0))
    # prompt + generation overflowing the cache slot -> rejected
    trace.append(Request(rid=6, prompt=tuple(range(1, 7)),
                         max_new_tokens=30, arrival_step=0))
    report = engine.run(trace)
    assert report.n_rejected == 2
    assert report.n_completed == 5
    assert report.n_completed + report.n_rejected == len(trace)
    assert all(r.occupancy <= 2 for r in report.per_step)
    # 5 single-step-arrival requests over 2 slots: the queue must build
    assert report.max_queue_depth >= 3
    # and drain: the engine ran to quiescence with every slot free
    assert all(s is None for s in engine.slots)
    assert report.per_step[-1].queue_depth == 0
    # each completed request produced exactly max_new_tokens tokens
    for rid in range(5):
        assert len(report.tokens[rid]) == 4


def test_zero_recompiles_after_warmup_pallas(fresh_cache):
    """The tentpole pin: a ragged trace through the grouped pallas
    megakernels compiles everything in warmup and NOTHING after —
    cache-stats growth in the steady state is zero, and no region fell
    back off the megakernel path."""
    engine = Engine(_tiny_cfg("pallas"), max_batch=2, max_len=24,
                    prompt_buckets=(4, 8), sampling="greedy", seed=0)
    compiles = engine.warmup()
    assert compiles > 0, "warmup compiled nothing"
    assert engine.pallas_fallbacks == 0
    trace = synth_trace(4, seed=1, arrival_rate=1.0, prompt_lens=(2, 8),
                        gen_lens=(2, 4), vocab=engine.cfg.vocab)
    report = engine.run(trace)  # strict_no_recompile raises on any growth
    assert report.decode_recompiles == 0
    assert report.warmup_compiles == compiles
    assert report.n_completed == len(trace)


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_insert_updates_the_cache_in_place(fresh_cache, backend):
    """Warm-up reads from the compiled insert and decode programs how much
    of the cache each aliases.  Insert takes the cache as a donation and
    writes its slot in place (all of it aliased; the buffers it was given
    are gone after the call); decode writes a new cache and leaves the
    one it was given valid."""
    engine = Engine(_tiny_cfg(backend), max_batch=2, max_len=24,
                    prompt_buckets=(8,), sampling="greedy", seed=0)
    engine.warmup()
    assert engine.cache_alias_share == {"decode": 0.0, "insert": 1.0}
    before = jax.tree.leaves(engine.caches)
    report = engine.run([Request(rid=0, prompt=(1, 2, 3), max_new_tokens=3,
                                 arrival_step=0)])
    assert report.cache_alias_share == {"decode": 0.0, "insert": 1.0}
    assert all(a.is_deleted() for a in before)
    live = engine.caches
    b = engine.max_batch
    _, after = engine._decode(engine.params, live,
                              jnp.zeros((b, 1), jnp.int32),
                              jnp.zeros((b,), jnp.int32))
    assert not any(a.is_deleted() for a in jax.tree.leaves((live, after)))
    assert report.n_completed == 1 and report.decode_recompiles == 0
    d = report.to_json()
    assert d["cache_alias_share"] == {"decode": 0.0, "insert": 1.0}


def test_serve_config_run_api(fresh_cache):
    """ServeConfig + run(cfg) -> ServeReport, JSON-serializable."""
    import json

    cfg = S.ServeConfig(arch="smollm-135m", backend="jax", max_batch=2,
                        max_len=48, prompt_buckets=(8,), n_requests=3,
                        prompt_lens=(3, 8), gen_lens=(2, 4),
                        sampling="categorical", temperature=0.8, seed=0)
    report = S.run(cfg)
    assert report.n_completed + report.n_rejected == 3
    d = json.loads(json.dumps(report.to_json()))
    assert d["decode_recompiles"] == 0
    assert d["steps"] == len(d["per_step"])
    assert d["tokens_per_s"] > 0


def test_grouped_max_rung_raises_instead_of_demoting(fresh_cache):
    """``max_rung="grouped"`` (what the chip smoke test serves with): a
    kernel the grouped rung cannot lower fails warm-up with the lowering
    error in the message, and no rung below is tried."""
    from repro import resilience as RZ

    cfg = S.ServeConfig(arch="smollm-135m", backend="pallas", max_batch=2,
                        max_len=48, prompt_buckets=(8,), max_rung="grouped")
    engine = S.build_engine(cfg)
    assert engine.cfg.pipeline_options.resilience.max_rung == "grouped"
    plan = RZ.FaultPlan([RZ.FaultSpec(site="compile:grouped", indices=(0,),
                                      kind="raise", message="refused")])
    with RZ.faults(plan), pytest.raises(RZ.LadderError, match="refused") \
            as ei:
        engine.warmup()
    assert [a.rung for a in ei.value.report.attempts] == ["grouped"]


def test_engine_rejects_ssm_families():
    with pytest.raises(ValueError, match="attention-family"):
        Engine(configs.get_reduced_config("mamba2-2.7b"))
