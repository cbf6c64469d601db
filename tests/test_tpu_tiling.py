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


def test_split_position_vectors_match_reference(fresh_cache, monkeypatch):
    """Ragged decode over a 256-long cache whose K/V tiles do not fit the
    VMEM budget whole: two 128-wide KV blocks, so the key-position row
    is split across the grid.  The interpret-mode megakernel agrees with
    a float64 reference at every position."""
    rng = np.random.default_rng(0)
    b, hkv, group, sk, dh = 3, 1, 2, 256, 32
    # double-buffered f32 K and V^T tiles: 512 bytes a key at dh 32
    monkeypatch.setenv("REPRO_VMEM_BUDGET_BYTES", str(512 * 200))
    q = rng.normal(size=(b, hkv * group, 1, dh)).astype(np.float32)
    k = rng.normal(size=(b, hkv, sk, dh)).astype(np.float32)
    v = rng.normal(size=(b, hkv, sk, dh)).astype(np.float32)
    pos = np.array([0, 130, 255], np.int32)
    opts = pipeline.CompileOptions(backend="pallas")
    out = LY._attention_pipeline(jnp.asarray(q), jnp.asarray(k),
                                 jnp.asarray(v), dh ** -0.5, opts,
                                 causal=True, q_offset=jnp.asarray(pos))
    kern, rows, _ = LY._attention_call(q.shape, k.shape, dh, True,
                                       dh ** -0.5, opts)
    assert rows == group and kern.blocks["N"] == 128
    assert kern.dims["N"] == 2 and kern.lowering_report.fallbacks == 0
    np.testing.assert_allclose(np.asarray(out),
                               _attention_ref(q, k, v, pos),
                               rtol=2e-5, atol=2e-5)


def _attention_ref_f32(q, k, v, pos):
    """Plain float32 ``jax.numpy`` causal GQA attention at "highest"
    precision: query ``i`` of sequence ``b`` sits at ``pos[b] + i``."""
    hp = jax.lax.Precision.HIGHEST
    b, hq, sq, dh = q.shape
    group = hq // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=hp) / np.sqrt(dh)
    qpos = jnp.asarray(pos)[:, None] + jnp.arange(sq)[None, :]
    mask = jnp.arange(k.shape[2])[None, None, :] <= qpos[:, :, None]
    s = jnp.where(mask[:, None], s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v,
                      precision=hp)


@pytest.mark.parametrize("sk", [512, 2048])
@pytest.mark.parametrize("hkv,group,dh,sq", [(2, 3, 32, 1), (1, 7, 64, 1),
                                             (3, 1, 32, 1), (2, 3, 32, 4)])
def test_folded_decode_attention_matches_reference(fresh_cache, hkv, group,
                                                   dh, sq, sk):
    """Ragged decode with the GQA group folded into the query rows and
    the whole cache as one N tile (GQA groups 3 and 7 at reduced widths,
    MHA, and four tokens a step): one grid step per (sequence, KV head),
    and the interpret-mode kernel agrees with a float32 reference with
    the first token at position 0, a middle one and the last rows of the
    cache."""
    rng = np.random.default_rng(sk + group + sq)
    pos = np.array([0, sk // 2 + 3, sk - sq], np.int32)
    b = len(pos)
    q = rng.normal(size=(b, hkv * group, sq, dh)).astype(np.float32)
    k = rng.normal(size=(b, hkv, sk, dh)).astype(np.float32)
    v = rng.normal(size=(b, hkv, sk, dh)).astype(np.float32)
    opts = pipeline.CompileOptions(backend="pallas")
    kern, rows, kgroup = LY._attention_call(q.shape, k.shape, dh, True,
                                            dh ** -0.5, opts)
    assert (rows, kgroup) == (group * sq, 1)
    assert kern.blocks["M"] == group * sq and kern.blocks["N"] == sk
    assert kern.launches == 1 and kern.lowering_report.fallbacks == 0
    assert LY.attention_grid_steps(q.shape, k.shape, dh, opts) == b * hkv
    out = LY._attention_pipeline(jnp.asarray(q), jnp.asarray(k),
                                 jnp.asarray(v), dh ** -0.5, opts,
                                 causal=True, q_offset=jnp.asarray(pos))
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_attention_ref_f32(q, k, v, pos)),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("sq,group", [(64, 3), (128, 7), (256, 3)])
def test_prefill_attention_keeps_head_group_blocks(fresh_cache, sq, group):
    """A prefill-shaped call (a prompt bucket) keeps the head-group
    program with one head per step, its rows cut as before and 128-key
    blocks."""
    dh, sk = 64, 512
    opts = pipeline.CompileOptions(backend="pallas")
    kern, rows, kgroup = LY._attention_call((1, 2 * group, sq, dh),
                                            (1, 2, sk, dh), dh, True,
                                            dh ** -0.5, opts)
    dims, blocks = LY._pipeline_dims_blocks(
        {"M": sq, "D": dh, "N": sk, "L": dh})
    assert (rows, kgroup) == (sq, group)
    assert kern.dims == {**dims, "H": group}
    assert kern.blocks == {**blocks, "H": 1}
    assert blocks["N"] == 128


# (b, hq, hkv, max_len, d_head) of the benchmark's cells, and the grid
# steps of one decode attention call before and after the group fold
CELLS = {"smollm-135m.decode-long": ((64, 9, 3, 2048, 64), 9216, 192),
         "qwen2-7b.decode": ((32, 28, 4, 1024, 128), 7168, 128),
         "smollm-135m.chat-short": ((64, 9, 3, 512, 64), 2304, 192)}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_decode_grid_steps_per_call(fresh_cache, cell):
    """At the benchmark cells' shapes a decode attention call runs one
    grid step per (sequence, KV head), down from one per (sequence,
    query head, 128-key block) in the head-group program."""
    (b, hq, hkv, sk, dh), before, after = CELLS[cell]
    opts = pipeline.CompileOptions(backend="pallas")
    assert LY.attention_grid_steps((b, hq, 1, dh), (b, hkv, sk, dh), dh,
                                   opts) == after
    old = LY._attention_kernel(1, dh, sk, dh, hq // hkv, True, dh ** -0.5,
                               opts)
    assert b * hkv * old.lowering_report.grid_steps == before


def test_long_cache_splits_to_fit_vmem_budget(fresh_cache):
    """A 32768-row cache at d_head 128 does not fit as one tile: it takes
    the largest 128-multiple divisor whose double-buffered float32 K and
    V^T tiles fit ``vmem_budget()``."""
    from repro.core import regions as REG
    sk, dh = 32768, 128
    budget = REG.vmem_budget()
    tile = LY._kv_tile(sk, dh, dh)

    def tile_bytes(t):
        return 2 * 4 * t * (dh + dh)

    assert sk % tile == 0 and tile % 128 == 0 and tile < sk
    assert tile_bytes(tile) <= budget
    assert all(tile_bytes(t) > budget
               for t in range(tile + 128, sk + 1, 128) if sk % t == 0)
    opts = pipeline.CompileOptions(backend="pallas")
    assert LY.attention_grid_steps((2, 28, 1, dh), (2, 4, sk, dh), dh,
                                   opts) == 2 * 4 * (sk // tile)
