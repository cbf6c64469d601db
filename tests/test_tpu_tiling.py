"""The TPU tiling rule in the layers' block choice and the Pallas
operand layout, checked on the CPU.

Mosaic accepts a block only when its last two axes are multiples of
(8, 128) or span the whole array, and it tiles a rank-1 operand by 128
lanes where XLA may tile it by its whole length.  The layers therefore
cut every dim into 128-wide blocks or keep it whole, and rank-1 merged
arrays cross the ``pallas_call`` boundary as ``(1, n)`` rows.
``tests/test_tpu_compile.py`` proves both against the TPU compiler;
these tests pin the rule itself and the interpret-mode numerics.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, pipeline
from repro.core import codegen_pallas as CP
from repro.models import layers as LY

SERVE_TOKENS = (1, 8, 64, 128, 256, 1024)  # decode batch, buckets, max_len


def _tiles(block: int, whole: int, quantum: int) -> bool:
    return block == whole or block % quantum == 0


@pytest.mark.parametrize("arch", ["smollm-135m", "llama3.2-1b"])
def test_block_choice_meets_tiling_rule(arch):
    """Every operand of the SwiGLU and attention programs gets blocks
    whose last axis is a multiple of 128 (or whole) and whose
    second-to-last is a multiple of 8 (or whole), at the published
    widths and every served token count."""
    cfg = configs.get_config(arch)
    for t in SERVE_TOKENS:
        # SwiGLU: X (M, D), WT/VT (K, D), UT (N, K), O (M, N)
        sizes = {"M": t, "D": cfg.d_model, "K": cfg.d_ff, "N": cfg.d_model}
        _, b = LY._pipeline_dims_blocks(sizes)
        for rows, cols in (("M", "D"), ("K", "D"), ("N", "K"), ("M", "N")):
            assert _tiles(b[rows], sizes[rows], 8), (arch, t, rows, b)
            assert _tiles(b[cols], sizes[cols], 128), (arch, t, cols, b)
        # attention: Q (H, M, D), KT (N, D), VT (L, N), O (H, M, L), and
        # the QP (M,) / KP (N,) position vectors as (1, n) rows
        for sk in SERVE_TOKENS:
            sizes = {"M": t, "D": cfg.d_head, "N": sk, "L": cfg.d_head}
            _, b = LY._pipeline_dims_blocks(sizes)
            for rows, cols in (("M", "D"), ("N", "D"), ("L", "N"),
                               ("M", "L")):
                assert _tiles(b[rows], sizes[rows], 8), (arch, t, sk, b)
                assert _tiles(b[cols], sizes[cols], 128), (arch, t, sk, b)
            for vec in ("M", "N"):
                assert _tiles(b[vec], sizes[vec], 128), (arch, t, sk, b)


@pytest.mark.parametrize("size,n", [(576, 1), (1536, 12), (64, 1),
                                    (2048, 16), (1024, 8), (96, 1)])
def test_n_blocks_cuts_by_lanes_or_keeps_whole(size, n):
    assert LY._n_blocks(size) == n


def test_rank1_operands_cross_as_rows():
    """A rank-1 block spec becomes a (1, n) row indexed on its lane
    axis; higher ranks are untouched."""
    from repro.core.graph import VType
    from repro.core import ops as O
    spec = CP._block_spec(VType(("N",), O.VECTOR), (128,), {"N": 8},
                          ["N"])
    assert tuple(spec.block_shape) == (1, 128)
    assert spec.index_map(5) == (0, 5)
    assert CP._row((1024,)) == (1, 1024)
    assert CP._row((8, 576)) == (8, 576)


@pytest.fixture()
def fresh_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_CACHE", str(tmp_path))
    pipeline.reset_default_cache()
    yield
    pipeline.reset_default_cache()


def _attention_ref(q, k, v, pos):
    """Plain float64 causal GQA decode attention: query ``i`` of
    sequence ``b`` sits at position ``pos[b] + i``."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    b, hq, sq, dh = q.shape
    group = hq // k.shape[1]
    k = np.repeat(k, group, axis=1)
    v = np.repeat(v, group, axis=1)
    s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(dh)
    qpos = np.asarray(pos)[:, None] + np.arange(sq)[None, :]
    mask = np.arange(k.shape[2])[None, None, :] <= qpos[:, :, None]
    s = np.where(mask[:, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("bhqk,bhkd->bhqd", p / p.sum(-1, keepdims=True), v)


def test_split_position_vectors_match_reference(fresh_cache):
    """Ragged decode over a 256-long cache: two 128-wide KV blocks, so
    the key-position row is split across the grid.  The interpret-mode
    megakernel agrees with a float64 reference at every position."""
    rng = np.random.default_rng(0)
    b, hkv, group, sk, dh = 3, 1, 2, 256, 32
    q = rng.normal(size=(b, hkv * group, 1, dh)).astype(np.float32)
    k = rng.normal(size=(b, hkv, sk, dh)).astype(np.float32)
    v = rng.normal(size=(b, hkv, sk, dh)).astype(np.float32)
    pos = np.array([0, 130, 255], np.int32)
    opts = pipeline.CompileOptions(backend="pallas")
    out = LY._attention_pipeline(jnp.asarray(q), jnp.asarray(k),
                                 jnp.asarray(v), dh ** -0.5, opts,
                                 causal=True, q_offset=jnp.asarray(pos))
    kern = LY._attention_kernel(1, dh, sk, dh, group, True, dh ** -0.5,
                                opts)
    assert kern.dims["N"] == 2 and kern.lowering_report.fallbacks == 0
    np.testing.assert_allclose(np.asarray(out),
                               _attention_ref(q, k, v, pos),
                               rtol=2e-5, atol=2e-5)
