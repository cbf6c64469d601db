"""Auto-emitted Pallas kernels from fusion-derived block programs:
array program -> Table-2 expansion -> the 9 rules -> emit() -> pallas_call.

This closes the loop the paper opens: the fusion algorithm's output is not
just analyzed but *executed as a TPU kernel* (interpret mode on CPU)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import array_program as AP
from repro.core.codegen_pallas import emit
from repro.core.fusion import fuse


def test_attention_kernel_autogen(rng):
    dims = {"M": 2, "D": 2, "N": 4, "L": 2}
    blocks = {"M": 8, "D": 16, "N": 8, "L": 16}
    fused = fuse(AP.attention_program(scale=0.125))[-1]
    f = emit(fused, dims, blocks, interpret=True)
    Q = rng.normal(size=(16, 32)).astype(np.float32) * 0.5
    K = rng.normal(size=(32, 32)).astype(np.float32) * 0.5
    V = rng.normal(size=(32, 32)).astype(np.float32)
    out = f(jnp.asarray(Q), jnp.asarray(K), jnp.asarray(V.T))
    S = (Q @ K.T) * 0.125
    P = np.exp(S)
    ref = (P / P.sum(1, keepdims=True)) @ V
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)


def test_layernorm_matmul_kernel_autogen(rng):
    dims = {"M": 2, "K": 4, "N": 2}
    blocks = {"M": 8, "K": 8, "N": 16}
    KK = dims["K"] * blocks["K"]
    fused = fuse(AP.layernorm_matmul_program(float(KK)))[-1]
    f = emit(fused, dims, blocks, interpret=True)
    X = rng.normal(size=(16, KK)).astype(np.float32)
    Y = rng.normal(size=(KK, 32)).astype(np.float32)
    out = f(jnp.asarray(X), jnp.asarray(Y.T))
    mu = X.mean(1, keepdims=True)
    sd = np.sqrt((X ** 2).mean(1, keepdims=True) - mu ** 2)
    ref = ((X - mu) / sd) @ Y
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-4, rtol=1e-4)


def test_rmsnorm_swiglu_kernel_autogen(rng):
    dims = {"M": 2, "D": 2, "K": 4, "N": 2}
    blocks = {"M": 8, "D": 16, "K": 8, "N": 8}
    DD = dims["D"] * blocks["D"]
    fused = fuse(AP.rmsnorm_ffn_swiglu_program(float(DD)))[-1]
    f = emit(fused, dims, blocks, interpret=True)
    X = rng.normal(size=(16, DD)).astype(np.float32)
    W = (rng.normal(size=(DD, 32)) / np.sqrt(DD)).astype(np.float32)
    V = (rng.normal(size=(DD, 32)) / np.sqrt(DD)).astype(np.float32)
    U = (rng.normal(size=(32, 16)) / np.sqrt(32)).astype(np.float32)
    out = f(jnp.asarray(X), jnp.asarray(W.T), jnp.asarray(V.T),
            jnp.asarray(U.T))
    xn = X / np.sqrt((X ** 2).mean(1, keepdims=True))
    gsw = xn @ W
    ref = ((gsw / (1 + np.exp(-gsw))) * (xn @ V)) @ U
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-4, rtol=1e-4)


def test_kernel_name_changes_neither_key_nor_numerics(rng, tmp_path):
    """A kernel's name labels it in a profiler trace and nothing more:
    the named and the unnamed compile share one cache key and give the
    same numbers."""
    from repro import pipeline

    dims = {"M": 2, "D": 2, "N": 4, "L": 2}
    opts = pipeline.CompileOptions(
        backend="pallas", interpret=True,
        blocks={"M": 8, "D": 16, "N": 8, "L": 16})
    g = AP.attention_program(scale=0.125)
    feed = {"Q": rng.normal(size=(16, 32)).astype(np.float32),
            "KT": rng.normal(size=(32, 32)).astype(np.float32),
            "VT": rng.normal(size=(32, 32)).astype(np.float32)}
    named, unnamed = (
        pipeline.compile(g, dims, options=opts, name=name,
                         cache=pipeline.KernelCache(tmp_path / str(i)))
        for i, name in enumerate(["attention", None]))
    assert named.key == unnamed.key
    np.testing.assert_array_equal(np.asarray(named(feed)["O"]),
                                  np.asarray(unnamed(feed)["O"]))
