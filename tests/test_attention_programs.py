"""Differential test matrix for the decoder attention path.

{causal, non-causal} x {MHA, GQA 4:1} x {py, jax, pallas}: every compiled
kernel must agree with (a) the dense numpy reference and (b) the
block-program interpreter oracle on the ORIGINAL (unfused) program.  On
top of the matrix: prefill-vs-decode parity through the model layer —
decoding token-by-token through ``pipeline.compile`` must reproduce the
causal prefill output position by position.
"""

import dataclasses

import numpy as np
import pytest

from repro import pipeline
from repro.core import array_program as AP
from repro.core.interpreter import run as interp_run
from repro.pipeline import packing as P

BACKENDS = ["py", "jax", "pallas"]

H = 4                       # GQA group size (4 query heads : 1 kv head)
DIMS = {"M": 3, "D": 2, "N": 3, "L": 2}
BLOCKS = {"M": 8, "D": 8, "N": 8, "L": 8, "H": 1}
SCALE = 0.125


@pytest.fixture()
def cache(tmp_path):
    return pipeline.KernelCache(tmp_path)


def _case(rng, grouped: bool, causal: bool):
    """(program, dims, blocks, merged inputs, dense numpy reference)."""
    s_q = DIMS["M"] * BLOCKS["M"]
    s_kv = DIMS["N"] * BLOCKS["N"]
    d = DIMS["D"] * BLOCKS["D"]
    dv = DIMS["L"] * BLOCKS["L"]
    lead = (H,) if grouped else ()
    Q = rng.normal(size=lead + (s_q, d)).astype(np.float32)
    K = rng.normal(size=(s_kv, d)).astype(np.float32)
    V = rng.normal(size=(s_kv, dv)).astype(np.float32)
    qp = np.arange(s_q, dtype=np.float32)
    kp = np.arange(s_kv, dtype=np.float32)

    s = Q @ K.T                                  # (*lead, s_q, s_kv)
    if causal:
        s = np.where(qp[:, None] >= kp[None, :], s, -1e30)
    s = s * SCALE
    p = np.exp(s - s.max(-1, keepdims=True))
    ref = (p / p.sum(-1, keepdims=True)) @ V

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
    """Interpreter run of the unfused program on nested-block inputs."""
    nested = {}
    for nid in g.input_ids:
        node = g.nodes[nid]
        nested[node.name] = P.to_nested(inputs[node.name], node.vtype,
                                        dims)
    out = interp_run(g, nested, dims)["O"]
    out_vt = P.output_types(g)[0]
    return P.from_nested(out, out_vt, dims)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("grouped", [False, True], ids=["mha", "gqa"])
@pytest.mark.parametrize("causal", [False, True],
                         ids=["noncausal", "causal"])
def test_attention_matrix_differential(causal, grouped, backend, cache,
                                       rng):
    g, dims, inputs, ref = _case(rng, grouped, causal)
    kern = pipeline.compile(g, dims, backend=backend, blocks=BLOCKS,
                            cache=cache)
    assert kern.cache_hit is None
    got = np.asarray(kern(inputs)[kern.out_names[0]])
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)
    oracle = _oracle(g, dims, inputs)
    np.testing.assert_allclose(got, oracle, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("grouped", [False, True], ids=["mha", "gqa"])
def test_prefill_decode_parity_through_pipeline(grouped, tmp_path,
                                                monkeypatch):
    """Causal prefill and token-by-token decode, both through
    ``pipeline.compile``, agree position by position."""
    import jax
    import jax.numpy as jnp

    monkeypatch.setenv("REPRO_KERNEL_CACHE", str(tmp_path))
    pipeline.reset_default_cache()
    from repro.models import layers as L
    from repro.models.common import ModelConfig, ParamBuilder

    n_heads = 4
    cfg = ModelConfig(d_model=64, n_heads=n_heads,
                      n_kv_heads=1 if grouped else n_heads, d_head=16,
                      d_ff=128, dtype=jnp.float32, norm_eps=1e-6)
    cfg = dataclasses.replace(cfg, attn_impl="pipeline",
                              pipeline_backend="jax", rope_theta=0.0)
    pb = ParamBuilder(jax.random.PRNGKey(0), jnp.float32)
    L.init_attention(pb, cfg)
    p = pb.params
    batch, seq = 2, 8
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, seq, 64),
                          jnp.float32)

    prefill = L.attention_apply(p, x, cfg, causal=True)
    # a one-layer stack: decode reads and writes layer 0 of it
    cache = jax.tree.map(lambda a: a[None],
                         L.attention_init_cache(cfg, batch, seq,
                                                jnp.float32))
    for pos in range(seq):
        step, cache = L.attention_decode(p, x[:, pos:pos + 1], cache, 0,
                                         pos, cfg)
        np.testing.assert_allclose(np.asarray(step[:, 0]),
                                   np.asarray(prefill[:, pos]),
                                   rtol=2e-5, atol=2e-5)
    pipeline.reset_default_cache()


def test_gqa_shares_kv_blocks_across_group():
    """The head-group broadcast is structural: K/V enter the H map as
    broadcast (non-mapped) ports, so one kv-head block set serves every
    query head in the group."""
    from repro.core.graph import MapNode

    g = AP.gqa_attention_program(SCALE, causal=True)
    (hid,) = [n for n in g.op_nodes()
              if isinstance(g.nodes[n], MapNode)]
    h = g.nodes[hid]
    assert h.dim == "H"
    by_port = {g.nodes[g.in_edge(hid, p).src].name: h.mapped[p]
               for p in range(h.n_in())}
    assert by_port == {"Q": True, "KT": False, "VT": False,
                       "QP": False, "KP": False}
