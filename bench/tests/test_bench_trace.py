"""The trace reduction: on a hand-made trace with known answers, and on
a small trace recorded on the chip (two ticks of each saturated cell,
extracted by ``control.py --record-trace``)."""

import json
import sys
from pathlib import Path

import pytest

CHECKOUT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(CHECKOUT))

from bench import harness, trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
MOSAIC_TEXT = '%run.3 = f32[8,128] custom-call(%a), ' + trace.MOSAIC


def hand_made():
    # host: one tick over [0, 100) ns holding a sync from 60 to 90;
    # device: a decode module over [10, 40) whose loop holds a Mosaic
    # kernel over [15, 25); an eager op over [50, 60)
    return {
        "devices": [{
            "modules": [["jit_decode_step", 10, 30], ["jit_argmax", 50, 10]],
            "ops": [[trace._op_name("%while.13 = (s32[]) while(%t)"), 10,
                     30, False],
                    [trace._op_name(MOSAIC_TEXT), 15, 10,
                     trace.MOSAIC in MOSAIC_TEXT],
                    ["reduce", 50, 10, False]]}],
        "host": [["bench.tick", 0, 100], ["np.asarray(jax.Array)", 60, 30]]}


def test_hand_made_trace():
    s = trace.summarize(hand_made())
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(40e-9)
    assert s.module_count("decode") == 1
    assert s.module_seconds("decode") == pytest.approx(30e-9)
    assert s.kernel_count("decode") == 1
    assert s.kernel_seconds("decode") == pytest.approx(10e-9)
    assert s.kernel_count("other") == 0
    # self time: the loop holds the kernel, so 20 of its 30 ns are its own
    assert dict(s.top_ops) == pytest.approx(
        {"decode:while": 20e-9, "decode:run": 10e-9, "other:reduce": 10e-9})
    # idle [0,10) and [40,50) inside the tick, [60,100) in the sync
    assert dict(s.idle_gaps) == pytest.approx(
        {"bench.tick": 20e-9, "np.asarray(jax.Array)": 40e-9})


def test_op_names():
    assert trace._op_name("%fusion.12 = f32[2] fusion(%x)") == "fusion"
    assert trace._op_name("%vmap_vmap_jit_run___.5 = f32[1] custom-call()") \
        == "vmap_vmap_jit_run___"


RECORDED = sorted(DATA.glob("trace_*.json"))
# Mosaic launches per decode step in the recorded traces: smollm-135m
# launches two kernels per layer (attention, SwiGLU), qwen2-7b four (its
# SwiGLU compiles to three launches)
LAUNCHES = {"smollm-135m.decode-long": 60, "qwen2-7b.decode": 32}


@pytest.mark.parametrize("path", RECORDED, ids=[p.stem for p in RECORDED])
def test_recorded_trace(path):
    ex = json.loads(path.read_text())
    s = trace.summarize(ex)
    assert 0 < s.busy_s <= s.window_s
    assert s.module_count("decode") >= 1
    cell = harness.load_cell(path.stem[len("trace_"):])
    layers = cell.config["model"]["num_hidden_layers"]
    # every layer launches its attention and SwiGLU kernels
    per_step = s.kernel_count("decode") / s.module_count("decode")
    assert per_step == LAUNCHES[cell.name] >= 2 * layers
    # the kernels run inside the decode program, and take part of it
    assert 0 < s.kernel_seconds("decode") < s.module_seconds("decode")
    w = harness.Window(t0=0.0, t_end=1.0, t_close=1.0, reqs={}, ticks=[
        harness.Tick(t0=0.0, contexts=t["contexts"], prefills=t["prefills"])
        for t in ex["ticks"]], compiles=0, traced=(0, len(ex["ticks"])))
    peak = harness.peak_of("TPU v5 lite")
    rd = harness.Readings(cell, w, cell.work, peak, s)
    roof = harness.load_module(harness.BENCH / "metrics"
                               / "decode_kernel_roofline.py").read(rd)
    assert 0 < roof <= 100
    idle = harness.load_module(harness.BENCH / "metrics"
                               / "idle_share.saturated.py").read(rd)
    assert 0 <= idle < 100


def test_harness_counts_stop_where_the_tracer_starts():
    # two ticks before the tracer started at t=2 s, one after it: the
    # counts read the first two only, and a request still queued when the
    # tracer started counts its wait so far
    cell = harness.load_cell("smollm-135m.chat-short")
    ticks = [harness.Tick(t0=0.0, t1=1.0, n_decode=32, contexts=[100] * 32),
             harness.Tick(t0=1.0, t1=2.0, n_decode=16, contexts=[100] * 16),
             harness.Tick(t0=2.0, t1=9.0, n_decode=64, contexts=[100] * 64)]
    reqs = {i: harness.Req(i, (1,) * 64, 8, due=d, admitted=a) for i, (d, a)
            in enumerate([(0.0, 0.5), (0.5, 1.0), (1.5, None), (2.5, 9.0)])}
    w = harness.Window(t0=0.0, t_end=10.0, t_close=10.0, reqs=reqs,
                       ticks=ticks, compiles=0, traced=(2, 3), t_trace=2.0)
    rd = harness.Readings(cell, w, cell.work,
                          harness.peak_of("TPU v5 lite"), None)

    def read(name):
        return harness.load_module(harness.BENCH / "metrics"
                                   / f"{name}.py").read(rd)

    assert rd.counted_s == 2.0
    assert read("occupancy") == pytest.approx(100 * 48 / (2 * 64))
    # waits 0.5, 0.5 and 0.5 (still queued at 2.0); the fourth came later
    assert read("queue_wait_p90_ms") == pytest.approx(500.0)
    flops = 48 * cell.work.decode_flops(cell.config["model"], 100)
    assert read("mfu") == pytest.approx(
        100 * flops / (2.0 * rd.peak["bf16_flops_per_s"]))
