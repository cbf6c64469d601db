"""``phases.py``: the engine's spans, named kernels and request timelines
read from a traced window, on a hand-made trace with known answers, on
the traces recorded from a program without them (which read nothing),
on a trace recorded on the chip with them, and through the harness at a
tiny width on the CPU."""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

CHECKOUT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]

from bench import harness, phases, trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
PEAK = "TPU v5 lite"
NAMED = sorted(DATA.glob("spans_*.json"))      # recorded with the spans
UNNAMED = sorted(DATA.glob("trace_*.json"))    # recorded without them


def _op(text, s, d):
    return [trace._op_name(text), s, d, trace.MOSAIC in text]


def _call(name):
    return f"%{name}.7 = f32[1] custom-call(%a), " + trace.MOSAIC


def hand_made():
    # host: one engine tick inside the harness's span: a decode
    # dispatch, the two syncs (the sample holding a readback event) and
    # the slot bookkeeping; device: a decode program over [10, 40) with
    # a named attention kernel, two groups of the named SwiGLU and an
    # unnamed kernel; an eager op over [50, 60) inside the first sync
    return {
        "devices": [{
            "modules": [["jit_decode_step", 10, 30], ["jit_argmax", 50, 10]],
            "ops": [_op("%while.1 = (s32[]) while(%t)", 10, 30),
                    _op(_call("attention"), 15, 5),
                    _op(_call("rmsnorm_swiglu_0"), 22, 4),
                    _op(_call("rmsnorm_swiglu_1"), 26, 4),
                    _op(_call("vmap_vmap_jit_run___"), 31, 2),
                    ["reduce", 50, 10, False]]}],
        "host": [["bench.tick", 0, 100], ["engine.run", 1, 98],
                 ["engine.tick", 2, 96], ["engine.decode", 5, 7],
                 ["engine.sync.decode_guard", 40, 15],
                 ["engine.sync.sample", 60, 30],
                 ["np.asarray(jax.Array)", 61, 28],
                 ["engine.retire", 90, 5]]}


def test_hand_made_trace():
    red = phases.reduce(hand_made())
    # idle [0,10), [40,50) and [60,100), split exactly over the phases
    assert dict(red["idle_by_phase"]) == pytest.approx({
        phases.OUTSIDE: 2e-9, "engine.run": 2e-9, "engine.tick": 6e-9,
        "engine.decode": 5e-9, "engine.sync.decode_guard": 10e-9,
        "engine.sync.sample": 30e-9, "engine.retire": 5e-9})
    assert red["sync_idle_s"] == pytest.approx(40e-9)
    # the named kernels by kind; the unnamed one is in no kind
    assert red["named"] == pytest.approx({"decode:attention": [1, 5e-9],
                                          "decode:rmsnorm_swiglu": [2, 8e-9]})
    s = trace.summarize(hand_made())
    assert sum(v for _, v in red["idle_by_phase"]) == pytest.approx(
        s.window_s - s.busy_s)
    assert s.kernel_seconds("decode") == pytest.approx(15e-9)


def test_kernel_kinds():
    assert phases.kernel_kind("attention") == "attention"
    assert phases.kernel_kind("rmsnorm_swiglu_12") == "rmsnorm_swiglu"
    assert phases.kernel_kind("vmap_vmap_jit_run___") is None
    assert phases.kernel_kind("run") is None


def test_pieces_cover_the_interval():
    host = [["engine.run", 10, 80], ["engine.tick", 20, 50],
            ["engine.admit", 25, 10], ["engine.prefill", 26, 4],
            ["np.asarray", 27, 1]]
    assert phases.pieces(host, 0, 100) == [
        (0, 10, phases.OUTSIDE), (10, 20, "engine.run"),
        (20, 25, "engine.tick"), (25, 26, "engine.admit"),
        (26, 30, "engine.prefill"), (30, 35, "engine.admit"),
        (35, 70, "engine.tick"), (70, 90, "engine.run"),
        (90, 100, phases.OUTSIDE)]
    assert phases.pieces([], 0, 100) == [(0, 100, phases.OUTSIDE)]


def _window(ex, reqs=None):
    return harness.Window(
        t0=0.0, t_end=1.0, t_close=1.0, reqs=reqs or {}, compiles=0,
        ticks=[harness.Tick(t0=0.0, t1=0.1, contexts=t["contexts"],
                            prefills=t["prefills"]) for t in ex["ticks"]],
        traced=(0, len(ex["ticks"])))


def _cell(path, prefix):
    return harness.load_cell(path.stem[len(prefix):])


@pytest.mark.parametrize("path", UNNAMED, ids=[p.stem for p in UNNAMED])
def test_program_without_them_reads_nothing(path):
    # traces of a program with no engine spans, unnamed kernels and no
    # request timelines
    ex = json.loads(path.read_text())
    reqs = {0: harness.Req(0, (1,) * 8, 8, due=0.1, admitted=0.2)}
    ph = phases.readings(ex, _window(ex, reqs), _cell(path, "trace_"),
                         harness.peak_of(PEAK), {})
    assert ph["idle_by_phase"] == [] and ph["sync_idle_share"] is None
    assert ph["kernels"] == {}
    for k in ("engine_queue_wait_p90_ms", "decode_attention_roofline",
              "decode_swiglu_roofline"):
        assert ph[k] is None, k
    assert ph["idle_share"] > 0


@pytest.mark.parametrize("path", NAMED, ids=[p.stem for p in NAMED])
def test_program_with_them_on_the_chip(path):
    ex = json.loads(path.read_text())
    cell = _cell(path, "spans_")
    ph = phases.readings(ex, _window(ex), cell, harness.peak_of(PEAK), {})
    # every idle moment lies in some engine phase or outside the engine,
    # and the syncs hold part of it
    assert sum(v for _, v in ph["idle_by_phase"]) == pytest.approx(
        ph["idle_share"] / 100 * trace.summarize(ex).window_s)
    assert 0 < ph["sync_idle_share"] <= ph["idle_share"]
    assert {k for k, _ in ph["idle_by_phase"]} <= {
        phases.OUTSIDE, "engine.run", "engine.tick", "engine.admit",
        "engine.prefill", "engine.insert", "engine.decode",
        "engine.retire", "engine.sync.prefill_guard",
        "engine.sync.first_token", "engine.sync.decode_guard",
        "engine.sync.sample"}
    # the engine's run covers the harness's tick: no idle gap is put to
    # the bare harness span
    assert "bench.tick" not in dict(trace.summarize(ex).idle_gaps)
    # the names cover the decode program's Mosaic kernels
    assert ph["named_share"]["decode"] >= 99.0
    assert ph["kernels"]["decode:attention"][0] >= 1
    assert ph["kernels"]["decode:rmsnorm_swiglu"][0] >= 1
    for k in ("decode_attention_roofline", "decode_swiglu_roofline"):
        assert 0 < ph[k] <= 100, k


def test_engine_queue_wait_reads_the_engines_stamps():
    # the engine's stamps run later than the harness's: one request
    # waited behind another prompt's prefill inside its admitting tick
    from repro.launch.engine import RequestTimeline

    cell = harness.load_cell("smollm-135m.chat-short")
    tls = {0: RequestTimeline(0, 0.0, 0.5), 1: RequestTimeline(1, 0.5, 1.3),
           2: RequestTimeline(2, 1.5, 2.5)}
    reqs = {i: harness.Req(i, (1,) * 64, 8, due=d, admitted=a) for i, (d, a)
            in enumerate([(0.0, 0.5), (0.5, 1.0), (1.5, None), (2.5, 9.0)])}
    w = harness.Window(t0=0.0, t_end=10.0, t_close=10.0, reqs=reqs,
                       ticks=[], compiles=0, traced=(0, 0), t_trace=2.0)
    # waits 0.5, 0.8 and 0.5 (its prefill began after the tracer did);
    # the fourth came later
    wait = phases.engine_queue_wait_p90_ms(w, tls)
    assert wait == pytest.approx(
        1e3 * float(np.percentile([0.5, 0.8, 0.5], 90)))
    rd = harness.Readings(cell, w, cell.work, None, None)
    assert wait >= harness.load_module(
        harness.BENCH / "metrics" / "queue_wait_p90_ms.py").read(rd)
    assert phases.engine_queue_wait_p90_ms(w, {}) is None


def test_capture_through_the_harness():
    # a served window at a tiny width on the CPU: the engine's stamps of
    # every admitted request reach the reader, later than the harness's
    cpu = harness.load_module(Path(__file__).parent / "test_bench_cpu.py")
    harness.configure_caches()
    cell = cpu.tiny_cell("float32", tied=True, mix=cpu.POISSON)
    engine = harness.build_engine(cell.config, cell.mix, cpu.SEED)
    harness.warm(engine, cell.config["model"]["vocab_size"])
    with phases.capture() as seen:
        w = harness.serve(engine, cell, cpu.SEED, 1.5)
    assert harness.serve.__name__ == "serve"
    assert seen["window"] is w and seen["trace"] is None
    admitted = [r for r in w.reqs.values() if r.admitted is not None]
    assert admitted and set(seen["timelines"]) == {r.rid for r in admitted}
    for r in admitted:
        tl = seen["timelines"][r.rid]
        assert r.due <= r.admitted <= tl.prefill_s <= tl.first_token_s
        assert tl.first_token_s <= r.times[0]
        if r.tokens is not None:
            assert tl.n_tokens == len(r.tokens)
            assert tl.finish_s <= r.times[-1]
    rd = harness.Readings(cell, w, cell.work, None, None)
    ph = phases.readings(None, w, cell, None, seen["timelines"])
    assert ph["engine_queue_wait_p90_ms"] >= harness.load_module(
        harness.BENCH / "metrics" / "queue_wait_p90_ms.py").read(rd) >= 0


def test_command_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/phases.py", "--workload",
         "smollm-135m.chat-short", "--seed", "1", "--seconds", "1"],
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert "{" not in p.stdout
