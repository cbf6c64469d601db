"""Seeded traffic: same seed, same requests; lengths as the mix states."""

import statistics
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import traffic  # noqa: E402

MIXES = ("decode-long", "decode", "chat-short", "rag")


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = traffic.load_mix(name)
    a = traffic.generate(mix, 2 ** 31 + 12345, 1000, 70)
    b = traffic.generate(mix, 2 ** 31 + 12345, 1000, 70)
    c = traffic.generate(mix, 7, 1000, 70)
    assert a == b
    assert a != c


@pytest.mark.parametrize("name", MIXES)
def test_lengths_in_range_with_stated_median(name):
    mix = traffic.load_mix(name)
    n = 64 if mix["kind"] == "saturated" else 101
    items = traffic.generate(mix, 3, 1000, n)
    plens = [len(i.prompt) for i in items]
    alens = [i.max_new_tokens for i in items]
    for lens, spec in ((plens, mix["prompt"]), (alens, mix["answer"])):
        assert spec["min"] <= min(lens) and max(lens) <= spec["max"]
        assert abs(statistics.median(lens) - spec["median"]) <= 1
    bucket = max(mix["engine"]["prompt_buckets"])
    assert max(plens) <= bucket
    assert max(plens) + max(alens) <= mix["engine"]["max_len"]


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_carries_the_same_work(name):
    mix = traffic.load_mix(name)
    n = 128 if mix["kind"] == "saturated" else traffic.n_for_window(mix, 30)
    sizes = [sorted((len(i.prompt), i.max_new_tokens) for i in
                    traffic.generate(mix, s, 1000, n)) for s in (1, 2)]
    assert sorted(p for p, _ in sizes[0]) == sorted(p for p, _ in sizes[1])
    assert sorted(a for _, a in sizes[0]) == sorted(a for _, a in sizes[1])


@pytest.mark.parametrize("name", ("chat-short", "rag"))
def test_open_loop_arrivals_fill_the_window(name):
    mix = traffic.load_mix(name)
    n = traffic.n_for_window(mix, 30)
    dues = [i.due_s for i in traffic.generate(mix, 9, 1000, n)]
    assert dues == sorted(dues) and dues[0] == 0.0
    assert 0.85 * 30 < dues[-1] < 30
