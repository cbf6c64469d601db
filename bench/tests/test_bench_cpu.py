"""The harness end to end at a tiny width on the CPU (Pallas interpret
mode): the plain reference against the served engine in float32 and
bfloat16, the fp8 control and faults planted under the timed path, each
judged by the benchmark's own decision, the counts that stop where the
tracer starts, and the command's refusal to run without a TPU."""

import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

CHECKOUT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]

from bench import check, harness  # noqa: E402


def tiny_config(dtype: str, tied: bool) -> dict:
    return {"name": "tiny", "arch": "qwen2-7b", "work": "dense", "model": {
        "hidden_size": 128, "intermediate_size": 256,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 2, "vocab_size": 512, "rms_norm_eps": 1e-6,
        "rope_theta": 1e6, "tie_word_embeddings": tied,
        "attention_bias": not tied, "max_position_embeddings": 256,
        "torch_dtype": dtype}}


MIX = {"kind": "saturated",
       "engine": {"max_batch": 4, "max_len": 128, "prompt_buckets": [32, 64]},
       "prompt": {"median": 24, "sigma": 0.6, "min": 8, "max": 64},
       "answer": {"median": 12, "sigma": 0.5, "min": 4, "max": 40},
       "backlog": 4, "block": 16}
POISSON = dict(MIX, kind="poisson", rate_per_s=10.0)
SEED = 2 ** 31 + 4321
# widest gap allowed at this size in bfloat16 (see
# test_bf16_engine_within_limit_and_control_fails), and the served tokens
# a 1.5 s window at this size finishes enough of to compare
TINY_LIMIT = 0.05
TINY_TOKENS = 100


def tiny_cell(dtype="bfloat16", tied=False, mix=MIX) -> harness.Cell:
    return harness.Cell(
        name="tiny", config=tiny_config(dtype, tied), mix=mix, chips=1,
        limits={"widest_gap": TINY_LIMIT, "tokens_compared": TINY_TOKENS},
        end_to_end=[{"name": n, "unit": "x"} for n in
                    ("tokens_per_s", "itl_p95_ms", "ttft_p90_ms", "setup_s")],
        per_layer=[])


@pytest.fixture(scope="module", autouse=True)
def caches():
    harness.configure_caches()


def served(cell, seconds=1.5):
    engine = harness.build_engine(cell.config, cell.mix, SEED)
    harness.warm(engine, cell.config["model"]["vocab_size"])
    w = harness.serve(engine, cell, SEED, seconds)
    done = [r for r in w.reqs.values() if r.tokens is not None]
    return engine, check.sample(done, SEED, harness.check_tokens(cell))


def test_float32_engine_agrees_with_reference():
    cell = tiny_cell("float32", tied=True)
    engine, picked = served(cell)
    gap, _, n = check.widest_gaps(cell.reference, cell.config["model"],
                                  engine.params, picked)
    assert n >= 100
    assert gap < 1e-4


def _run(cell, monkeypatch, fault=None, control=False):
    """A whole run of the harness; ``fault`` is planted in the engine
    once it is warm, under the timed path."""
    serve = harness.serve

    def broken(engine, *a, **k):
        if fault:
            fault(engine)
        return serve(engine, *a, **k)

    monkeypatch.setattr(harness, "serve", broken)
    return harness.run(cell, SEED, 1.5, False, time.perf_counter(),
                       control=control)


def test_bf16_engine_within_limit_and_control_fails(monkeypatch):
    sound = _run(tiny_cell(), monkeypatch)
    assert sound["correct"] is True
    gap = sound["check"]["widest_gap"]["value"]
    assert gap < TINY_LIMIT
    assert sound["check"]["tokens_compared"]["value"] >= TINY_TOKENS
    # the control, judged in the program's place by the same decision
    ctl = _run(tiny_cell(), monkeypatch, control=True)
    assert ctl["correct"] is False
    assert ctl["check"]["widest_gap"]["value"] > max(3 * gap, TINY_LIMIT)


def test_sound_run_is_correct(monkeypatch):
    res = _run(tiny_cell(mix=POISSON), monkeypatch)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"tokens_per_s", "itl_p95_ms",
                                   "ttft_p90_ms", "setup_s"}
    assert list(res)[-1] == "check"
    assert all(v["value"] == 0 for k, v in res["check"].items()
               if k not in ("widest_gap", "tokens_compared"))


def _alter_token(engine):
    sample = engine._sample

    def altered(logits):
        toks = sample(logits).copy()
        toks[0] = (toks[0] + 1) % engine.cfg.vocab
        return toks

    engine._sample = altered


def _state_unchanged(engine):
    decode = engine._decode

    def unchanged(params, caches, tokens, pos):
        logits, _ = decode(params, caches, tokens, pos)
        return logits, caches

    engine._decode = unchanged


def _decode_raises(engine):
    # the fifth decode step raises, inside the window: the watchdog
    # demotes the decode step off the Pallas kernels and the engine
    # serves on, with nothing but a warning
    decode, calls = engine._decode, []

    def raises(*args):
        calls.append(1)
        if len(calls) < 5:
            return decode(*args)
        engine._decode = decode
        raise RuntimeError("planted decode fault")

    engine._decode = raises


@pytest.mark.parametrize("fault,caught", [
    (_alter_token, "widest_gap"), (_state_unchanged, "widest_gap"),
    (_decode_raises, "demotions")],
    ids=["token_altered", "decode_state_unchanged", "decode_demoted"])
def test_fault_under_timed_path_is_not_correct(monkeypatch, fault, caught):
    res = _run(tiny_cell(), monkeypatch, fault)
    assert res["correct"] is False
    assert res["check"][caught]["value"] > res["check"][caught]["limit"]
    if fault is _decode_raises:
        assert res["check"]["off_pallas"]["value"] == 1
        assert res["check"]["engine_failures"]["value"] == 1


def test_command_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "smollm-135m.decode-long", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=CHECKOUT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode == 2
    assert "{" not in p.stdout


def test_command_fails_with_only_benchmark_files(tmp_path):
    shutil.copy(CHECKOUT / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHECKOUT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2-7b.decode",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())

