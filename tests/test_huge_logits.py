"""Huge-logit differential matrix for the compiled backends.

The graph-level safety pass (``numerics.stabilize``, applied by default
in ``pipeline.compile``) must make every backend agree with the
stabilized interpreter oracle at |logit| ~ 1e4 — far past float32
``exp`` overflow (~88) — across {plain, causal, GQA} attention, with the
fused Pallas snapshot lowering fallback-free as a single launch.  On top
of the matrix: prefill/decode parity through the model layer at large
logits, where the unstabilized kernel would produce NaNs.
"""

import dataclasses

import numpy as np
import pytest

from repro import pipeline
from repro.core import array_program as AP
from repro.core import numerics as NU
from repro.pipeline import packing as P

BACKENDS = ["py", "jax", "pallas"]

H = 4                       # GQA group size
DIMS = {"M": 3, "D": 2, "N": 3, "L": 2}
BLOCKS = {"M": 8, "D": 8, "N": 8, "L": 8, "H": 1}
SCALE = 0.125
# Q entries ~N(0, 2000^2): logits Q@K^T * SCALE land around |1e4|,
# where raw exp overflows by thousands of orders of magnitude
QSCALE = 2000.0


@pytest.fixture()
def cache(tmp_path):
    return pipeline.KernelCache(tmp_path)


def _case(rng, grouped: bool, causal: bool):
    """(program, dims, merged inputs, float64 dense reference)."""
    s_q = DIMS["M"] * BLOCKS["M"]
    s_kv = DIMS["N"] * BLOCKS["N"]
    d = DIMS["D"] * BLOCKS["D"]
    dv = DIMS["L"] * BLOCKS["L"]
    lead = (H,) if grouped else ()
    Q = (rng.normal(size=lead + (s_q, d)) * QSCALE).astype(np.float32)
    K = rng.normal(size=(s_kv, d)).astype(np.float32)
    V = rng.normal(size=(s_kv, dv)).astype(np.float32)
    qp = np.arange(s_q, dtype=np.float32)
    kp = np.arange(s_kv, dtype=np.float32)

    s = Q.astype(np.float64) @ K.T.astype(np.float64)
    if causal:
        s = np.where(qp[:, None] >= kp[None, :], s, -1e30)
    s = s * SCALE
    p = np.exp(s - s.max(-1, keepdims=True))
    ref = (p / p.sum(-1, keepdims=True)) @ V.astype(np.float64)

    if grouped:
        g = AP.gqa_attention_program(SCALE, causal=causal)
    elif causal:
        g = AP.causal_attention_program(SCALE)
    else:
        g = AP.attention_program(SCALE)
    dims = dict(DIMS, **({"H": H} if grouped else {}))
    inputs = {"Q": Q, "KT": K, "VT": V.T}
    if causal:
        inputs.update(QP=qp, KP=kp)
    return g, dims, inputs, ref


def _oracle(g, dims, inputs):
    """Stabilized-interpreter run of the unfused program."""
    nested = {}
    for nid in g.input_ids:
        node = g.nodes[nid]
        nested[node.name] = P.to_nested(inputs[node.name], node.vtype,
                                        dims)
    out = NU.run_stabilized(g, nested, dims)["O"]
    return P.from_nested(out, P.output_types(g)[0], dims)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("variant", ["plain", "causal", "gqa"])
def test_huge_logit_matrix_differential(variant, backend, cache, rng):
    grouped = variant == "gqa"
    causal = variant != "plain"
    g, dims, inputs, ref = _case(rng, grouped, causal)
    kern = pipeline.compile(g, dims, backend=backend, blocks=BLOCKS,
                            cache=cache)
    assert kern.stabilized  # auto-detected, no explicit opt-in
    got = np.asarray(kern(inputs)[kern.out_names[0]])
    assert np.isfinite(got).all(), "stabilized kernel overflowed"
    oracle = _oracle(g, dims, inputs)
    np.testing.assert_allclose(got, oracle, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(got, ref, rtol=5e-4, atol=5e-4)
    if backend == "pallas":
        rep = kern.lowering_report
        assert rep.fallbacks == 0, rep.summary()
        assert rep.launches == 1  # fused attention stays one kernel


@pytest.mark.parametrize("group", [True, False],
                         ids=["grouped", "ungrouped"])
def test_huge_logit_pallas_group_modes(group, cache, rng):
    """Both Pallas lowering modes (megakernel groups on/off) stay finite
    and agree with the oracle on the stabilized snapshot."""
    g, dims, inputs, _ = _case(rng, grouped=False, causal=False)
    kern = pipeline.compile(g, dims, backend="pallas", blocks=BLOCKS,
                            cache=cache, group=group)
    assert kern.stabilized
    assert kern.lowering_report.fallbacks == 0
    got = np.asarray(kern(inputs)[kern.out_names[0]])
    assert np.isfinite(got).all()
    oracle = _oracle(g, dims, inputs)
    np.testing.assert_allclose(got, oracle, rtol=2e-4, atol=2e-4)


def test_stabilize_off_overflows_stabilize_on_does_not(cache, rng):
    """The rewrite is what buys the safety: the same program compiled
    with ``stabilize=False`` produces non-finite output where the
    default stays finite."""
    import warnings
    g, dims, inputs, _ = _case(rng, grouped=False, causal=False)
    raw = pipeline.compile(g, dims, backend="jax", cache=cache,
                           stabilize=False)
    assert not raw.stabilized
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        out_raw = np.asarray(raw(inputs)[raw.out_names[0]])
    assert not np.isfinite(out_raw).all()
    stab = pipeline.compile(g, dims, backend="jax", cache=cache)
    assert stab.key != raw.key  # stabilization is part of the cache key
    out = np.asarray(stab(inputs)[stab.out_names[0]])
    assert np.isfinite(out).all()


def test_prefill_decode_parity_at_huge_logits(tmp_path, monkeypatch):
    """Causal prefill and token-by-token decode through the model layer
    agree position by position with inputs scaled so logits reach ~1e4
    (the pre-stabilization kernel NaN'd here)."""
    import jax
    import jax.numpy as jnp

    monkeypatch.setenv("REPRO_KERNEL_CACHE", str(tmp_path))
    pipeline.reset_default_cache()
    from repro.models import layers as L
    from repro.models.common import ModelConfig, ParamBuilder

    n_heads = 4
    cfg = ModelConfig(d_model=64, n_heads=n_heads, n_kv_heads=1,
                      d_head=16, d_ff=128, dtype=jnp.float32,
                      norm_eps=1e-6)
    cfg = dataclasses.replace(cfg, attn_impl="pipeline",
                              pipeline_backend="jax", rope_theta=0.0)
    pb = ParamBuilder(jax.random.PRNGKey(0), jnp.float32)
    L.init_attention(pb, cfg)
    p = pb.params
    batch, seq = 2, 8
    # x ~ N(0, 100^2) drives q/k to ~1e2 each: logits ~ 1e4
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, seq, 64),
                          jnp.float32) * 100.0

    prefill = L.attention_apply(p, x, cfg, causal=True)
    assert np.isfinite(np.asarray(prefill)).all()
    # a one-layer stack: decode reads and writes layer 0 of it
    cache_kv = jax.tree.map(lambda a: a[None],
                            L.attention_init_cache(cfg, batch, seq,
                                                   jnp.float32))
    for pos in range(seq):
        step, cache_kv = L.attention_decode(p, x[:, pos:pos + 1],
                                            cache_kv, 0, pos, cfg)
        np.testing.assert_allclose(np.asarray(step[:, 0]),
                                   np.asarray(prefill[:, pos]),
                                   rtol=2e-3, atol=2e-3)
