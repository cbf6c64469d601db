"""Ahead-of-time compiles of the serving megakernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached, and it
compiles for a described topology.  These tests compile the kernels of
the serving main path at smollm-135m's published widths (d_model 576,
d_ff 1536, GQA group 3, d_head 64) with ``interpret=False`` and a
ladder capped at the grouped rung, so what Mosaic refuses fails here
with Mosaic's own message instead of demoting.  Nothing runs: a compile
that passes is not a chip run.

This is the only test file that describes the topology, and it does so
inside a fixture: only one process may load the TPU library, so the
call must not happen while modules are imported (see the fixture).
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import pipeline
from repro import resilience as RZ
from repro.models import layers as LY

D_MODEL, D_FF, D_HEAD, GROUP = 576, 1536, 64, 3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # else the TPU compiler writes its logs under the temp directory
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler can describe it here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described-topology compile written to JAX's persistent cache
    cannot be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture()
def chip_options(tmp_path, monkeypatch, no_persistent_cache):
    monkeypatch.setenv("REPRO_KERNEL_CACHE", str(tmp_path))
    pipeline.reset_default_cache()
    yield pipeline.CompileOptions(
        backend="pallas", interpret=False,
        resilience=RZ.ResiliencePolicy(max_rung="grouped"))
    pipeline.reset_default_cache()


def _compile_for_chip(kern, shapes, one_chip) -> str:
    names = list(shapes)

    def step(*arrays):
        return kern(dict(zip(names, arrays)))

    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes.values()]
    return jax.jit(step).lower(*args).compile().as_text()


def _assert_chip_kernel(kern, hlo, name):
    assert kern.rung == "grouped"
    assert dict(kern.key.opts)["interpret"] is False
    assert kern.lowering_report.fallbacks == 0
    # every Mosaic call carries the kernel's name, which is what the
    # profiler's "XLA Ops" line shows for it
    calls = [line.split(" = ")[0].strip()
             for line in hlo.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == kern.launches >= 1
    for call in calls:
        assert re.fullmatch(rf"(ROOT )?%{name}(_\d+)?\.\d+", call), call


@pytest.mark.parametrize("tokens", [8, 256])
def test_swiglu_compiles_for_v5e(tokens, one_chip, chip_options):
    """RMSNorm+FFN-SwiGLU at the decode batch and the largest prompt
    bucket: d_model 576 is no multiple of 128, so its blocks span it."""
    kern = LY._swiglu_kernel(tokens, D_MODEL, D_FF, 1e-6, chip_options)
    hlo = _compile_for_chip(kern, {
        "X": (tokens, D_MODEL), "WT": (D_FF, D_MODEL),
        "VT": (D_FF, D_MODEL), "UT": (D_MODEL, D_FF)}, one_chip)
    _assert_chip_kernel(kern, hlo, "rmsnorm_swiglu")


@pytest.mark.parametrize("sq", [1, 256])
def test_causal_gqa_attention_compiles_for_v5e(sq, one_chip, chip_options):
    """The head-group causal attention program (one query head per grid
    step) at one and 256 query rows over a 1024-long cache: eight
    128-wide KV blocks, so the key-position vector is split across the
    grid.  Prefill buckets run it; decode folds the group (below)."""
    sk = 1024
    kern = LY._attention_kernel(sq, D_HEAD, sk, D_HEAD, GROUP, True,
                                D_HEAD ** -0.5, chip_options)
    assert kern.dims["N"] == sk // 128
    hlo = _compile_for_chip(kern, {
        "Q": (GROUP, sq, D_HEAD), "KT": (sk, D_HEAD), "VT": (D_HEAD, sk),
        "QP": (sq,), "KP": (sk,)}, one_chip)
    _assert_chip_kernel(kern, hlo, "attention")


@pytest.mark.parametrize("group,sk,dh", [(3, 2048, 64), (7, 1024, 128),
                                         (7, 32768, 128)])
def test_folded_decode_attention_compiles_for_v5e(group, sk, dh, one_chip,
                                                  chip_options):
    """Decode attention as the layers run it: the GQA group folded into
    the query rows (M 3 for smollm-135m, 7 for qwen2-7b) and the cache
    as one N tile where it fits the VMEM budget (2048 x 64, 1024 x 128),
    else split (32768 x 128): Mosaic accepts the whole-cache tiles."""
    kern, rows, kgroup = LY._attention_call(
        (1, group, 1, dh), (1, 1, sk, dh), dh, True, dh ** -0.5,
        chip_options)
    assert (rows, kgroup) == (group, 1)
    assert kern.blocks["N"] == LY._kv_tile(sk, dh, dh)
    assert (kern.dims["N"] == 1) == (sk <= 2048)
    hlo = _compile_for_chip(kern, {
        "Q": (group, dh), "KT": (sk, dh), "VT": (dh, sk),
        "QP": (group,), "KP": (sk,)}, one_chip)
    _assert_chip_kernel(kern, hlo, "attention")


def test_wide_swiglu_names_each_group_for_v5e(one_chip, chip_options):
    """At qwen2-7b's widths (d_model 3584, d_ff 18944) the SwiGLU program
    emits three kernels under the VMEM budget; each carries the kind's
    name and its group index."""
    d, f, t = 3584, 18944, 32
    kern = LY._swiglu_kernel(t, d, f, 1e-6, chip_options)
    hlo = _compile_for_chip(kern, {
        "X": (t, d), "WT": (f, d), "VT": (f, d), "UT": (d, f)}, one_chip)
    _assert_chip_kernel(kern, hlo, "rmsnorm_swiglu")
    assert {f"rmsnorm_swiglu_{i}" for i in range(kern.launches)} == {
        re.sub(r"\.\d+ = .*", "", line.strip().removeprefix("ROOT ")[1:])
        for line in hlo.splitlines() if "tpu_custom_call" in line}
