"""Work counts of the dense family against numbers worked by hand."""

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench.harness import load_module  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]
work = load_module(BENCH / "work" / "dense.py")
PEAK = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]


def model(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())["model"]


def test_smollm_decode_step_by_hand():
    m = model("smollm-135m")
    # two sequences over 100 and 200 cached positions: 9 heads x 64 wide
    # read K and V of 3 kv heads at 2 bytes
    attn, mlp = work.decode_kernel_calls(m, [100, 200])[:2]
    assert attn == (4 * 9 * 64 * 300, 235_008)
    assert mlp == (10_616_832, 5_314_176)
    calls = work.decode_kernel_calls(m, [100, 200])
    assert len(calls) == 60
    assert work.least_time(calls, PEAK) == pytest.approx(2.0326681e-4)
    assert work.decode_flops(m, 100) == 275_871_744


def test_smollm_prefill_by_hand():
    m = model("smollm-135m")
    attn, mlp = work.prefill_kernel_calls(m, 100)[:2]
    assert attn == (11_635_200, 307_200)
    assert mlp == (530_841_600, 5_539_968)
    assert work.prefill_flops(m, 100) == 21_639_343_104


def test_qwen2_decode_step_by_hand():
    m = model("qwen2-7b")
    attn, mlp = work.decode_kernel_calls(m, [300])[:2]
    assert attn == (4_300_800, 628_736)
    assert mlp == (407_371_776, 407_393_280)
    assert len(work.decode_kernel_calls(m, [300])) == 16
    assert work.decode_flops(m, 300) == 4_853_137_408


def test_qwen2_prefill_by_hand():
    m = model("qwen2-7b")
    attn, _ = work.prefill_kernel_calls(m, 512)[:2]
    assert attn == (1_882_718_208, 8_388_608)
    assert work.prefill_flops(m, 512) == 1_925_264_703_488


def test_least_time_takes_the_binding_bound():
    # compute-bound and memory-bound calls each take their own bound
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_time([(1000.0, 1.0), (1.0, 50.0)], peak) == 15.0
