"""What the serving engine records about itself: host spans on the
profiler's clock, one per phase of the run loop, and a host-clock
timeline per admitted request."""

import json
from pathlib import Path

import jax
import pytest

from repro import configs, pipeline
from repro.launch.engine import Engine, Request

# the children each admission holds, in order
ADMIT = ["engine.prefill", "engine.sync.prefill_guard", "engine.insert",
         "engine.sync.first_token"]
TICK = {"engine.admit", "engine.decode", "engine.sync.decode_guard",
        "engine.sync.sample", "engine.retire"}


@pytest.fixture()
def engine(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_CACHE", str(tmp_path / "kernels"))
    pipeline.reset_default_cache()
    mc = configs.get_reduced_config(
        "smollm-135m", n_layers=2, d_model=64, n_heads=2, n_kv_heads=2,
        d_head=32, d_ff=128, vocab=128)
    mc = configs.with_pipeline(
        mc, options=pipeline.CompileOptions(backend="jax"))
    eng = Engine(mc, max_batch=2, max_len=48, prompt_buckets=(8, 16),
                 sampling="greedy", seed=0)
    eng.warmup()
    yield eng
    pipeline.reset_default_cache()


def _requests(n):
    return [Request(rid=i, prompt=tuple(range(1, 4 + 3 * i)),
                    max_new_tokens=3 + i, arrival_step=i // 2)
            for i in range(n)]


def _engine_events(trace_dir: Path):
    """``(name, start, end, stats)`` of every ``engine.*`` host event."""
    path = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))[-1]
    pd = jax.profiler.ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_spans_nest_under_run_and_tick(engine, tmp_path):
    with jax.profiler.trace(str(tmp_path / "trace")):
        report = engine.run(_requests(4))
    events = _engine_events(tmp_path / "trace")
    runs = [e for e in events if e[0] == "engine.run"]
    ticks = [e for e in events if e[0] == "engine.tick"]
    assert len(runs) == 1 and len(ticks) == report.steps
    assert all(_inside(t, runs[0]) for t in ticks)
    assert [t[3]["step"] for t in ticks] == list(range(report.steps))
    assert ticks[0][3]["n_active"] == 0
    assert max(t[3]["n_active"] for t in ticks) == 2
    rest = [e for e in events if e[0] not in ("engine.run", "engine.tick")]
    assert {e[0] for e in rest} == TICK | set(ADMIT)
    for e in rest:
        assert sum(_inside(e, t) for t in ticks) == 1, e
    admits = [e for e in rest if e[0] == "engine.admit"]
    assert sorted(a[3]["rid"] for a in admits) == [0, 1, 2, 3]
    assert {a[3]["bucket"] for a in admits} == {8, 16}
    for a in admits:
        held = [e[0] for e in rest if e is not a and _inside(e, a)]
        assert held == ADMIT
    # a decode tick reads the device twice, each time in its own span
    syncs = [e for e in rest if e[0].startswith("engine.sync.")]
    assert len(syncs) == 2 * 4 + 2 * sum(
        1 for s in report.per_step if s.n_decode)


def test_steps_compile_to_the_programs_the_benchmark_names(engine):
    # the benchmark's trace reduction (bench/trace.py MODULES) finds the
    # engine's device programs by their module names
    import jax.numpy as jnp

    toks = jnp.zeros((1, 8), jnp.int32)
    assert "@jit__lambda" in engine._prefill.lower(
        engine.params, toks).as_text()
    pos = jnp.zeros((2,), jnp.int32)
    assert "@jit_decode_step" in engine._decode.lower(
        engine.params, engine.caches, jnp.zeros((2, 1), jnp.int32),
        pos).as_text()


def test_timeline_orders_and_latencies(engine):
    report = engine.run(_requests(4))
    tls = {t.rid: t for t in report.timeline}
    assert sorted(tls) == [0, 1, 2, 3]
    for t in tls.values():
        assert t.arrival_s <= t.prefill_s <= t.first_token_s <= t.finish_s
        assert t.n_tokens == len(report.tokens[t.rid])
        assert t.ttft_s == t.first_token_s - t.arrival_s > 0
        assert t.itl_s == pytest.approx(
            (t.finish_s - t.first_token_s) / (t.n_tokens - 1))
    # two slots: the third and fourth requests wait for a free one
    assert tls[2].prefill_s >= min(tls[0].finish_s, tls[1].finish_s)
    assert 0 < report.ttft_p50_ms <= report.ttft_p99_ms
    assert 0 < report.itl_p50_ms <= report.itl_p99_ms
    assert json.loads(json.dumps(report.to_json()))["timeline"][0]["rid"] \
        in tls


def test_given_arrival_and_one_call_per_tick(engine):
    # the caller's arrival stamp stands; with one tick per call each
    # report lists the requests admitted in that call, and the engine
    # keeps filling their timelines in later calls
    import time

    t_arr = time.perf_counter() - 0.5
    reqs = [Request(rid=i, prompt=(1, 2, 3), max_new_tokens=4,
                    arrival_step=0, arrival_s=t_arr) for i in range(3)]
    reps = [engine.run(reqs, max_steps=1)]
    while engine.queue or any(engine.slots):
        reps.append(engine.run([], max_steps=1))
    admitted = [[t.rid for t in r.timeline] for r in reps]
    assert admitted[0] == [0, 1] and [2] in admitted[1:]
    tls = [t for r in reps for t in r.timeline]
    assert all(t.arrival_s == t_arr for t in tls)
    assert all(t.finish_s is not None and t.n_tokens == 4 for t in tls)
    assert min(t.ttft_s for t in tls) >= 0.5
