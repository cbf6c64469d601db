"""Layer implementations shared across the 10-architecture zoo.

Every layer is a pair of functions:
  * ``init_<layer>(pb, cfg)``          — adds params+specs to a ParamBuilder
  * ``<layer>_apply(p, x, cfg, ...)``  — forward (full sequence)
  * ``<layer>_decode(p, x, cache, ...)`` — one-token step with cache

The paper's fused kernels are wired in here: attention uses the fused
flash kernel (Example 1), gated MLPs use Flash-RMSNorm+FFN-SwiGLU
(Example 3), whisper's LayerNorm+fc1 uses Flash-LayerNorm+Matmul
(Example 2).  ``cfg.attn_impl`` / ``cfg.mlp_impl`` select Pallas vs the
XLA-level fused lowering (dry-run / CPU).
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as K
from repro.kernels import ref as R
from repro.models.common import (ModelConfig, ParamBuilder, apply_rope,
                                 layer_norm, rms_norm)
from repro.runtime.sharding import constrain


# ---------------------------------------------------------------------------
# Fusion-pipeline execution path (repro.pipeline): layers compile their
# block program through fuse -> select -> codegen and run the resulting
# cached kernel.  Selected by ``cfg.attn_impl``/``cfg.mlp_impl`` ==
# "pipeline"; ``cfg.pipeline_backend`` picks the codegen backend.
# ---------------------------------------------------------------------------

LANES = 128  # TPU vector lane width: the block quantum of every layer dim


def _n_blocks(size: int) -> int:
    """Block count for one layer dim under the TPU tiling rule: the last
    two axes of every block are multiples of (8, 128) or span the whole
    array.  A layer dim can be the last axis of one operand (``D`` of
    ``X``) and the second-to-last of another (``D`` of ``UT``), so each
    dim is cut into 128-wide blocks when 128 divides it and otherwise
    kept whole (smollm's d_model 576 = 4.5 x 128, a 64-wide head)."""
    return size // LANES if size % LANES == 0 else 1


def _pipeline_dims_blocks(sizes):
    dims = {d: _n_blocks(s) for d, s in sizes.items()}
    blocks = {d: sizes[d] // n for d, n in dims.items()}
    return dims, blocks


def _pipeline_options(src):
    """Resolve a :class:`pipeline.CompileOptions` from a ModelConfig, a
    bare backend string (back-compat), or an options instance."""
    from repro import pipeline as PL
    if isinstance(src, PL.CompileOptions):
        return src
    if isinstance(src, str):
        return PL.CompileOptions(backend=src)
    if src.pipeline_options is not None:
        return src.pipeline_options
    return PL.CompileOptions(backend=src.pipeline_backend)


@functools.lru_cache(maxsize=256)
def _attention_kernel(s: int, dh: int, sk: int, dv: int, group: int,
                      causal: bool, scale: float, options,
                      kv_tile: Optional[int] = None):
    """One compiled kernel per (shape, group, causal, scale, options,
    kv_tile); the lru_cache skips graph reconstruction + fingerprinting
    on every forward call (CompileOptions is hashable, so it keys
    directly).  Query positions are kernel *data* (QP/KP inputs), so a
    decode step at any cache position — scalar or a ragged per-sequence
    position vector — reuses the same compiled kernel.  ``kv_tile`` sets
    the keys per N block (default: :func:`_n_blocks`)."""
    from repro import pipeline as PL
    from repro.core import array_program as AP
    dims, blocks = _pipeline_dims_blocks(
        {"M": s, "D": dh, "N": sk, "L": dv})
    if kv_tile is not None:
        dims["N"], blocks["N"] = sk // kv_tile, kv_tile
    if group > 1:
        g = AP.gqa_attention_program(scale, causal=causal)
        dims["H"] = group
        blocks["H"] = 1  # the head-group dim is a stack axis
    elif causal:
        g = AP.causal_attention_program(scale)
    else:
        g = AP.attention_program(scale)
    return PL.compile(g, dims, options=options.replace(blocks=blocks),
                      name="attention")


# Query rows per KV head up to which an attention call is decode-shaped:
# a one-token step at any GQA group up to 32, or a few tokens each.
# Prefill buckets (64 tokens and up) stay above it.
DECODE_ROWS = 32


def _kv_tile(sk: int, dh: int, dv: int) -> int:
    """Keys per N tile of a decode-shaped call.  The whole cache when
    one grid step's float32 K (sk, dh) and V^T (dv, sk) tiles, double-
    buffered, fit the grouping VMEM budget (``regions.vmem_budget()``);
    else the largest 128-multiple divisor of ``sk`` that fits, 128 at
    least.  A cache that 128 does not divide stays whole, as
    :func:`_n_blocks` keeps it."""
    from repro.core import regions as REG
    budget = REG.vmem_budget()

    def fits(t: int) -> bool:
        return 2 * 4 * t * (dh + dv) <= budget

    if fits(sk) or sk % LANES:
        return sk
    return next((t for t in range(sk - LANES, 0, -LANES)
                 if sk % t == 0 and fits(t)), LANES)


def _attention_call(q_shape, k_shape, dv: int, causal: bool,
                    scale: float, options):
    """The kernel one attention call runs: ``(kernel, rows, group)``,
    where ``rows`` are the query rows of one (sequence, KV head) and
    ``group`` the head-group stack the kernel keeps.

    A decode-shaped call (``group * sq <= DECODE_ROWS``) folds the GQA
    group into the query rows (every head of a group reads the same
    cache, at the same positions) and takes the cache as one N tile
    where it fits (:func:`_kv_tile`): one grid step per (sequence, KV
    head), each K/V block read once.  Larger calls (prefill) keep the
    head-group program with one head per step and 128-key blocks."""
    b, hq, sq, dh = q_shape
    _, hkv, sk, _ = k_shape
    group = hq // hkv
    opts = _pipeline_options(options)
    if group * sq <= DECODE_ROWS:
        rows, group, tile = group * sq, 1, _kv_tile(sk, dh, dv)
    else:
        rows, tile = sq, None
    kern = _attention_kernel(rows, dh, sk, dv, group, causal, scale, opts,
                             tile)
    return kern, rows, group


def attention_grid_steps(q_shape, k_shape, dv: int, options, *,
                         causal: bool = True) -> int:
    """Grid steps of one attention call (q ``(b, hq, sq, dh)`` against a
    ``(b, hkv, sk, dh)`` cache, at the layers' ``1/sqrt(dh)`` scale): the
    kernel's own grid times the batch and KV-head axes the call maps it
    over.  Static per shape."""
    kern, _, _ = _attention_call(q_shape, k_shape, dv, causal,
                                 1.0 / q_shape[3] ** 0.5, options)
    return q_shape[0] * k_shape[1] * kern.lowering_report.grid_steps


@functools.lru_cache(maxsize=256)
def _swiglu_kernel(t: int, d: int, d_ff: int, eps: float, options):
    from repro import pipeline as PL
    from repro.core import array_program as AP
    dims, blocks = _pipeline_dims_blocks(
        {"M": t, "D": d, "K": d_ff, "N": d})
    return PL.compile(
        AP.rmsnorm_ffn_swiglu_program(float(d), eps=eps), dims,
        options=options.replace(blocks=blocks), name="rmsnorm_swiglu")


def _attention_pipeline(q, k, v, scale: float, options, *,
                        causal: bool = False, q_offset=0) -> jax.Array:
    """Attention through the fused pipeline — causal or not, MHA or GQA.

    One compiled kernel per shape (:func:`_attention_call`), vmapped over
    batch and kv heads.  A decode-shaped call runs the group's heads as
    the query rows of one plain attention program; a prefill runs the
    head-group block program (Q blocked (H, M, D); K/V broadcast across
    the group).  Causal masking takes the global query/key positions as
    kernel inputs, so decode (``q`` is one token at cache position
    ``q_offset``) needs no recompile as the position advances.
    ``q_offset`` may be a scalar (every sequence at the same position)
    or a ``(b,)`` vector (ragged continuous-batching decode: each
    sequence at its own cache position) — the ragged case maps the
    per-sequence position vector into the kernel's QP input, same
    compiled kernel either way."""
    b, hq, sq, dh = q.shape
    _, hkv, skv, _ = k.shape
    dv = v.shape[3]
    kern, rows, group = _attention_call(q.shape, k.shape, dv, causal,
                                        scale, options)
    kp = jnp.arange(skv, dtype=jnp.float32)

    def one(qh, kh, vh, qp):
        feed = {"Q": qh.astype(jnp.float32),
                "KT": kh.astype(jnp.float32),
                "VT": vh.astype(jnp.float32).T}
        if causal:
            feed["QP"], feed["KP"] = qp, kp
        return kern(feed)["O"]

    off = jnp.asarray(q_offset, dtype=jnp.float32)
    # row r of a folded group is token r % sq of head r // sq
    qp = off[..., None] + jnp.tile(jnp.arange(sq, dtype=jnp.float32),
                                   rows // sq)
    # heads share the position vector; the batch axis maps it only when
    # q_offset is ragged (per-sequence)
    inner = jax.vmap(one, in_axes=(0, 0, 0, None))
    outer = jax.vmap(inner, in_axes=(0, 0, 0, 0 if off.ndim == 1 else None))
    if group > 1:
        qg = q.reshape(b, hkv, group, sq, dh)      # o: (b, hkv, group, sq, dv)
    else:
        qg = q.reshape(b, hkv, rows, dh)           # o: (b, hkv, rows, dv)
    o = outer(qg, k, v, qp).reshape(b, hq, sq, dv)
    return o.astype(q.dtype)


def _swiglu_pipeline(x2, wg, wu, wd, gamma, cfg: ModelConfig) -> jax.Array:
    """RMSNorm+FFN-SwiGLU through the fused pipeline.  The norm gain is
    folded into W/V columns (RMS(x)*g @ W == RMS(x) @ diag(g)W), so the
    paper's gain-free Example-3 program applies unchanged."""
    t, d = x2.shape
    d_ff = wg.shape[1]
    kern = _swiglu_kernel(t, d, d_ff, float(cfg.norm_eps),
                          _pipeline_options(cfg))
    gf = gamma.astype(jnp.float32)[:, None]
    out = kern({"X": x2.astype(jnp.float32),
                "WT": (gf * wg.astype(jnp.float32)).T,
                "VT": (gf * wu.astype(jnp.float32)).T,
                "UT": wd.astype(jnp.float32).T})["O"]
    return out.astype(x2.dtype)


# ---------------------------------------------------------------------------
# GQA attention (qwen2/llama3/qwen3/internvl/jamba/whisper-self)
# ---------------------------------------------------------------------------

def init_attention(pb: ParamBuilder, cfg: ModelConfig) -> None:
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    pb.dense("wq", (d, h * dh), ("fsdp", "tensor"))
    pb.dense("wk", (d, hkv * dh), ("fsdp", "tensor"))
    pb.dense("wv", (d, hkv * dh), ("fsdp", "tensor"))
    pb.dense("wo", (h * dh, d), ("tensor", "fsdp"))
    if cfg.qkv_bias:
        pb.zeros("bq", (h * dh,), ("tensor",))
        pb.zeros("bk", (hkv * dh,), ("tensor",))
        pb.zeros("bv", (hkv * dh,), ("tensor",))
    if cfg.qk_norm:
        pb.ones("q_norm", (dh,), (None,))
        pb.ones("k_norm", (dh,), (None,))


def _qkv(p, x, cfg: ModelConfig, positions):
    b, s, _ = x.shape
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, h, dh).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, hkv, dh).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, hkv, dh).transpose(0, 2, 1, 3)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if positions is not None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = constrain(q, "batch", "tensor", None, None)
    k = constrain(k, "batch", "tensor", None, None)
    v = constrain(v, "batch", "tensor", None, None)
    return q, k, v


def attention_apply(p, x, cfg: ModelConfig, *, causal=True,
                    positions=None) -> jax.Array:
    b, s, _ = x.shape
    if positions is None and cfg.rope_theta > 0:
        positions = jnp.arange(s)
    q, k, v = _qkv(p, x, cfg, positions)
    if cfg.attn_impl == "pipeline":
        # fusion-derived flash kernel via the pipeline driver — causal
        # (decoder prefill) and GQA included; no XLA fallback.  Two
        # hand-kernel knobs do not apply here: attn_p_half/unroll_scans
        # belong to kernels/flash_attention.py.  The driver stabilizes
        # softmax-bearing programs by default (numerics.stabilize: the
        # online-softmax rewrite, compiled on every backend), so the
        # generated kernel is finite at any logit magnitude.
        o = _attention_pipeline(q, k, v, 1.0 / cfg.d_head ** 0.5,
                                cfg, causal=causal)
    else:
        o = K.flash_attention(q, k, v, causal=causal, impl=cfg.attn_impl,
                              unroll=cfg.unroll_scans,
                              p_half=cfg.attn_p_half)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.d_head)
    return constrain(o @ p["wo"], "batch", None, None)


def attention_init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    hkv, dh = cfg.n_kv_heads, cfg.d_head
    return {
        "k": jnp.zeros((batch, hkv, max_len, dh), dtype),
        "v": jnp.zeros((batch, hkv, max_len, dh), dtype),
    }


def attention_cache_specs(cfg: ModelConfig):
    return {"k": ("batch", "tensor", "kv_seq", None),
            "v": ("batch", "tensor", "kv_seq", None)}


def attention_decode(p, x, cache, pos, cfg: ModelConfig):
    """One-token decode: insert k/v at ``pos``, attend over the cache.

    ``pos`` is either a scalar (every sequence at the same position — the
    classic lockstep batch) or a ``(b,)`` int vector (ragged
    continuous-batching step: each sequence writes its k/v at its own
    cache position and masks its own causal frontier).  Both run the same
    compiled kernels — positions are data, not shape."""
    b = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    ragged = pos.ndim == 1
    if cfg.rope_theta > 0:
        # (b,1,1) broadcasts per-sequence angles through apply_rope's
        # (..., S, Dh) convention; scalar keeps the shared (1,) vector
        positions = pos[:, None, None] if ragged else pos.reshape(1)
    else:
        positions = None
    q, k, v = _qkv(p, x, cfg, positions)
    if ragged:
        def put(buf, val, pv):  # per sequence: (hkv, max_len, dh) at pv
            return jax.lax.dynamic_update_slice(buf, val, (0, pv, 0))
        ck = jax.vmap(put)(cache["k"], k.astype(cache["k"].dtype), pos)
        cv = jax.vmap(put)(cache["v"], v.astype(cache["v"].dtype), pos)
    else:
        ck = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, 0, pos, 0))
        cv = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, 0, pos, 0))
    # mask positions beyond pos via the causal path with explicit offset
    if cfg.attn_impl == "pipeline":
        o = _attention_pipeline(q, ck, cv, 1.0 / cfg.d_head ** 0.5,
                                cfg, causal=True, q_offset=pos)
    else:
        o = K.flash_attention(q, ck, cv, causal=True, q_offset=pos,
                              impl=cfg.attn_impl,
                              unroll=cfg.unroll_scans)
    o = o.transpose(0, 2, 1, 3).reshape(b, 1, cfg.n_heads * cfg.d_head)
    return constrain(o @ p["wo"], "batch", None, None), {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# MLA attention (deepseek-v3): low-rank q/kv compression, decoupled RoPE,
# compressed-KV cache with the absorbed decode form.
# ---------------------------------------------------------------------------

def init_mla(pb: ParamBuilder, cfg: ModelConfig) -> None:
    d, h = cfg.d_model, cfg.n_heads
    qd = cfg.qk_nope_dim + cfg.qk_rope_dim
    if cfg.q_lora_rank:
        pb.dense("wq_a", (d, cfg.q_lora_rank), ("fsdp", None))
        pb.ones("q_norm", (cfg.q_lora_rank,), (None,))
        pb.dense("wq_b", (cfg.q_lora_rank, h * qd), (None, "tensor"))
    else:
        pb.dense("wq", (d, h * qd), ("fsdp", "tensor"))
    pb.dense("wkv_a", (d, cfg.kv_lora_rank + cfg.qk_rope_dim), ("fsdp", None))
    pb.ones("kv_norm", (cfg.kv_lora_rank,), (None,))
    pb.dense("wkv_b",
             (cfg.kv_lora_rank, h * (cfg.qk_nope_dim + cfg.v_head_dim)),
             (None, "tensor"))
    pb.dense("wo", (h * cfg.v_head_dim, d), ("tensor", "fsdp"))


def _mla_q(p, x, cfg: ModelConfig, positions):
    b, s, _ = x.shape
    h = cfg.n_heads
    qd = cfg.qk_nope_dim + cfg.qk_rope_dim
    if cfg.q_lora_rank:
        ql = rms_norm(x @ p["wq_a"], p["q_norm"], cfg.norm_eps)
        q = ql @ p["wq_b"]
    else:
        q = x @ p["wq"]
    q = q.reshape(b, s, h, qd).transpose(0, 2, 1, 3)
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_kv_compressed(p, x, cfg: ModelConfig, positions):
    ckv, k_rope = jnp.split(x @ p["wkv_a"], [cfg.kv_lora_rank], axis=-1)
    ckv = rms_norm(ckv, p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, None], positions, cfg.rope_theta)[:, 0]
    return ckv, k_rope  # (B,S,r), (B,S,rope)


def mla_apply(p, x, cfg: ModelConfig, *, causal=True,
              positions=None) -> jax.Array:
    b, s, _ = x.shape
    h = cfg.n_heads
    if positions is None:
        positions = jnp.arange(s)
    q_nope, q_rope = _mla_q(p, x, cfg, positions)
    ckv, k_rope = _mla_kv_compressed(p, x, cfg, positions)
    kv = (ckv @ p["wkv_b"]).reshape(
        b, s, h, cfg.qk_nope_dim + cfg.v_head_dim).transpose(0, 2, 1, 3)
    k_nope, v = jnp.split(kv, [cfg.qk_nope_dim], axis=-1)
    k_rope_h = jnp.broadcast_to(k_rope[:, None],
                                (b, h, s, cfg.qk_rope_dim))
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate([k_nope, k_rope_h], -1)
    q = constrain(q, "batch", "tensor", None, None)
    k = constrain(k, "batch", "tensor", None, None)
    scale = 1.0 / (cfg.qk_nope_dim + cfg.qk_rope_dim) ** 0.5
    impl = cfg.attn_impl if cfg.attn_impl in ("xla", "ref") else "xla"
    o = K.flash_attention(q, k, v, scale=scale, causal=causal, impl=impl,
                          unroll=cfg.unroll_scans)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, h * cfg.v_head_dim)
    return constrain(o @ p["wo"], "batch", None, None)


def mla_init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    return {
        "ckv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
        "krope": jnp.zeros((batch, max_len, cfg.qk_rope_dim), dtype),
    }


def mla_cache_specs(cfg: ModelConfig):
    return {"ckv": ("batch", "kv_seq", None),
            "krope": ("batch", "kv_seq", None)}


def mla_decode(p, x, cache, pos, cfg: ModelConfig):
    """Absorbed decode: attention runs against the *compressed* cache
    (this is MLA's serving trick; the per-token cache is r+rope wide).

    Like ``attention_decode``, ``pos`` is a scalar or a ``(b,)`` vector
    (ragged continuous-batching step)."""
    b = x.shape[0]
    h = cfg.n_heads
    pos = jnp.asarray(pos, jnp.int32)
    ragged = pos.ndim == 1
    positions = pos[:, None, None] if ragged else pos.reshape(1)
    q_nope, q_rope = _mla_q(p, x, cfg, positions)       # (b,h,1,*)
    ckv_t, krope_t = _mla_kv_compressed(p, x, cfg, positions)
    if ragged:
        def put(buf, val, pv):  # per sequence: (max_len, width) at pv
            return jax.lax.dynamic_update_slice(buf, val, (pv, 0))
        ckv = jax.vmap(put)(cache["ckv"],
                            ckv_t.astype(cache["ckv"].dtype), pos)
        krope = jax.vmap(put)(cache["krope"],
                              krope_t.astype(cache["krope"].dtype), pos)
    else:
        ckv = jax.lax.dynamic_update_slice(
            cache["ckv"], ckv_t.astype(cache["ckv"].dtype), (0, pos, 0))
        krope = jax.lax.dynamic_update_slice(
            cache["krope"], krope_t.astype(cache["krope"].dtype),
            (0, pos, 0))

    wkv_b = p["wkv_b"].reshape(cfg.kv_lora_rank, h,
                               cfg.qk_nope_dim + cfg.v_head_dim)
    w_uk = wkv_b[:, :, :cfg.qk_nope_dim]                 # (r,h,nope)
    w_uv = wkv_b[:, :, cfg.qk_nope_dim:]                 # (r,h,v)
    q_abs = jnp.einsum("bhqn,rhn->bhqr", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))         # (b,h,1,r)
    scale = 1.0 / (cfg.qk_nope_dim + cfg.qk_rope_dim) ** 0.5
    s = (jnp.einsum("bhqr,bsr->bhqs", q_abs, ckv.astype(jnp.float32))
         + jnp.einsum("bhqe,bse->bhqs", q_rope.astype(jnp.float32),
                      krope.astype(jnp.float32))) * scale
    cols = jnp.arange(ckv.shape[1])[None, None, None, :]
    frontier = pos[:, None, None, None] if ragged else pos
    s = jnp.where(cols <= frontier, s, -1e30)
    m = s.max(-1, keepdims=True)
    pr = jnp.exp(s - m)
    pr = pr / pr.sum(-1, keepdims=True)
    ctx = jnp.einsum("bhqs,bsr->bhqr", pr, ckv.astype(jnp.float32))
    o = jnp.einsum("bhqr,rhv->bhqv", ctx, w_uv.astype(jnp.float32))
    o = o.transpose(0, 2, 1, 3).reshape(b, 1, h * cfg.v_head_dim)
    o = o.astype(x.dtype)
    return (constrain(o @ p["wo"], "batch", None, None),
            {"ckv": ckv, "krope": krope})


# ---------------------------------------------------------------------------
# Gated MLP (SwiGLU) — fused with the preceding RMSNorm (paper Example 3)
# ---------------------------------------------------------------------------

def init_swiglu(pb: ParamBuilder, cfg: ModelConfig, d_ff: int,
                prefix: str = "") -> None:
    d = cfg.d_model
    pb.dense(prefix + "w_gate", (d, d_ff), ("fsdp", "tensor"))
    pb.dense(prefix + "w_up", (d, d_ff), ("fsdp", "tensor"))
    pb.dense(prefix + "w_down", (d_ff, d), ("tensor", "fsdp"))


def rmsnorm_swiglu_apply(p, x, gamma, cfg: ModelConfig,
                         prefix: str = "") -> jax.Array:
    """O = (swish(RMS_g(x) @ Wg) * (RMS_g(x) @ Wu)) @ Wd, fused."""
    b, s, d = x.shape
    x2 = x.reshape(b * s, d)
    if cfg.mlp_impl == "unfused":
        xn = rms_norm(x2, gamma, cfg.norm_eps)
        h = R.swish(xn @ p[prefix + "w_gate"]) * (xn @ p[prefix + "w_up"])
        out = h @ p[prefix + "w_down"]
    elif cfg.mlp_impl == "pipeline":
        out = _swiglu_pipeline(x2, p[prefix + "w_gate"],
                               p[prefix + "w_up"], p[prefix + "w_down"],
                               gamma, cfg)
    else:
        impl = {"fused_ref": "ref", "pallas": "pallas",
                "interpret": "interpret"}[cfg.mlp_impl]
        out = K.rmsnorm_swiglu(x2, p[prefix + "w_gate"], p[prefix + "w_up"],
                               p[prefix + "w_down"], gamma,
                               eps=cfg.norm_eps, impl=impl)
    return constrain(out.reshape(b, s, d), "batch", None, None)


# ---------------------------------------------------------------------------
# MoE (qwen3-moe / deepseek-v3 / jamba): top-k routing with capacity,
# scatter dispatch into per-expert buffers, EP over the 'expert' axis.
# ---------------------------------------------------------------------------

def init_moe(pb: ParamBuilder, cfg: ModelConfig) -> None:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    pb.dense("router", (d, e), (None, None), scale=0.02)
    pb.dense("we_gate", (e, d, f), ("expert", "fsdp", None))
    pb.dense("we_up", (e, d, f), ("expert", "fsdp", None))
    pb.dense("we_down", (e, f, d), ("expert", None, "fsdp"))
    if cfg.n_shared_experts:
        init_swiglu(pb, cfg, cfg.moe_d_ff * cfg.n_shared_experts, "shared_")


def moe_apply(p, x, gamma, cfg: ModelConfig) -> jax.Array:
    """x: (B, S, d).  RMSNorm -> router -> top-k experts (+ shared).

    With an active mesh the dispatch/combine run through the shard_map
    path (shard-local scatter, deterministic shardings): GSPMD's generic
    scatter partitioning replicates the (E, C, d) buffer and all-reduces
    it — measured 13TB/chip/step on deepseek-v3 train_4k (§Perf)."""
    from repro.runtime.sharding import active_mesh
    mesh = active_mesh()
    if (cfg.moe_impl == "shard_map" and mesh is not None
            and "data" in mesh.axis_names and "model" in mesh.axis_names):
        return _moe_apply_sharded(p, x, gamma, cfg, mesh)
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    xn = rms_norm(x, gamma, cfg.norm_eps).reshape(b * s, d)
    t = b * s

    logits = (xn.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_ids = jax.lax.top_k(probs, k)             # (T,k)
    top_w = top_w / top_w.sum(-1, keepdims=True)

    import math
    capacity = int(min(t, max(1, math.ceil(t * k * cfg.capacity_factor / e))))
    onehot = jax.nn.one_hot(top_ids, e, dtype=jnp.int32)  # (T,k,E)
    flat = onehot.reshape(t * k, e)
    # position of each assignment within its expert.  NOTE: jnp.cumsum
    # lowers to reduce-window (cost = elements x window -> quadratic in
    # tokens; measured 1.1e15 flops/chip on the 256-chip mesh);
    # associative_scan is the log-depth prefix sum.
    pos_in_expert = jax.lax.associative_scan(jnp.add, flat, axis=0) - flat
    pos = (pos_in_expert * flat).sum(-1).reshape(t, k)    # (T,k)
    keep = pos < capacity

    # dropped assignments scatter a zero contribution into slot 0 of their
    # expert (keeps the buffer evenly shardable over the expert axis)
    slot = top_ids * capacity + jnp.minimum(pos, capacity - 1)  # (T,k)
    updates = jnp.repeat(xn, k, axis=0) * keep.reshape(-1, 1).astype(xn.dtype)
    buf = jnp.zeros((e * capacity, d), xn.dtype)
    buf = buf.at[slot.reshape(-1)].add(updates)
    eb = buf.reshape(e, capacity, d)
    # shard experts over the model axis (EP) AND capacity over the data
    # axes — otherwise every data-parallel replica runs the full global
    # expert batch (measured 16x flop replication on the 256-chip mesh)
    eb = constrain(eb, "expert", "capacity", None)

    h = constrain(jnp.einsum("ecd,edf->ecf", eb, p["we_gate"]),
                  "expert", "capacity", None)
    u = constrain(jnp.einsum("ecd,edf->ecf", eb, p["we_up"]),
                  "expert", "capacity", None)
    h = R.swish(h) * u
    eo = jnp.einsum("ecf,efd->ecd", h, p["we_down"])
    eo = constrain(eo, "expert", "capacity", None)

    flat_out = eo.reshape(e * capacity, d)
    routed = flat_out[slot]                                # (T,k,d)
    routed = constrain(routed, "batch", None, None)
    w = (top_w * keep).astype(routed.dtype)
    out = jnp.einsum("tkd,tk->td", routed, w)

    if cfg.n_shared_experts:
        xs = xn
        hsh = R.swish(xs @ p["shared_w_gate"]) * (xs @ p["shared_w_up"])
        out = out + hsh @ p["shared_w_down"]
    return constrain(out.reshape(b, s, d).astype(x.dtype),
                     "batch", None, None)


def _moe_apply_sharded(p, x, gamma, cfg: ModelConfig, mesh) -> jax.Array:
    """EP MoE with shard_map dispatch/combine (capacity enforced per data
    shard — standard local-capacity semantics).

      1. routing: token-sharded top-k (plain SPMD ops);
      2. dispatch: per-data-shard local scatter into (E, C_local, d) —
         zero collectives, deterministic sharding;
      3. experts: the (E, C, d) buffer resharded to (expert->model,
         capacity->data) with one cheap all-to-all; einsums fully sharded;
      4. combine: per-(model,data) shard masked local gather of its own
         experts' rows + psum over model (bf16 partials).
    """
    import math
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    t = b * s
    xn = rms_norm(x, gamma, cfg.norm_eps).reshape(t, d)
    xn = constrain(xn, "batch", None)

    logits = (xn.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_ids = jax.lax.top_k(probs, k)
    top_w = (top_w / top_w.sum(-1, keepdims=True)).astype(xn.dtype)

    dax = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_shards = math.prod(mesh.shape[a] for a in dax)
    t_local = t // n_shards
    cap_local = int(min(t_local,
                        max(1, math.ceil(t_local * k
                                         * cfg.capacity_factor / e))))
    e_local = e // mesh.shape["model"]

    def local_dispatch(xn_l, ids_l):
        onehot = jax.nn.one_hot(ids_l, e, dtype=jnp.int32)
        flat = onehot.reshape(-1, e)
        pos = jax.lax.associative_scan(jnp.add, flat, axis=0) - flat
        pos_tk = (pos * flat).sum(-1).reshape(-1, k)
        keep = pos_tk < cap_local
        slot = ids_l * cap_local + jnp.minimum(pos_tk, cap_local - 1)
        upd = jnp.repeat(xn_l, k, axis=0) * keep.reshape(-1, 1).astype(
            xn_l.dtype)
        buf = jnp.zeros((e * cap_local, d), xn_l.dtype)
        buf = buf.at[slot.reshape(-1)].add(upd)
        return buf.reshape(e, cap_local, d), slot, keep

    eb, slot, keep = jax.shard_map(
        local_dispatch, mesh=mesh,
        in_specs=(P(dax), P(dax)),
        out_specs=(P(None, dax, None), P(dax), P(dax)),
        check_vma=False,
    )(xn, top_ids)
    eb = constrain(eb, "expert", "capacity", None)

    h = constrain(jnp.einsum("ecd,edf->ecf", eb, p["we_gate"]),
                  "expert", "capacity", None)
    u = constrain(jnp.einsum("ecd,edf->ecf", eb, p["we_up"]),
                  "expert", "capacity", None)
    eo = jnp.einsum("ecf,efd->ecd", R.swish(h) * u, p["we_down"])
    eo = constrain(eo, "expert", "capacity", None)

    def local_combine(eo_l, slot_l, keep_l, w_l):
        # eo_l: (e_local, cap_local, d) — this (model,data) shard's slice;
        # gather only rows of the LOCAL experts, psum partials over model
        midx = jax.lax.axis_index("model")
        e_base = midx * e_local
        flat = eo_l.reshape(e_local * cap_local, d)
        exp_id = slot_l // cap_local
        local = (exp_id >= e_base) & (exp_id < e_base + e_local) & keep_l
        local_slot = jnp.where(local, slot_l - e_base * cap_local, 0)
        routed = flat[local_slot] * local[..., None].astype(flat.dtype)
        out = jnp.einsum("tkd,tk->td", routed, w_l.astype(routed.dtype))
        return jax.lax.psum(out.astype(jnp.bfloat16), "model")

    out = jax.shard_map(
        local_combine, mesh=mesh,
        in_specs=(P("model", dax, None), P(dax), P(dax), P(dax)),
        out_specs=P(dax),
        check_vma=False,
    )(eo, slot, keep, top_w)

    if cfg.n_shared_experts:
        out = out.astype(xn.dtype) + (
            R.swish(xn @ p["shared_w_gate"])
            * (xn @ p["shared_w_up"])) @ p["shared_w_down"]
    return constrain(out.reshape(b, s, d).astype(x.dtype),
                     "batch", None, None)


def moe_ref(p, x, gamma, cfg: ModelConfig) -> jax.Array:
    """Dense per-expert loop oracle (tests only; no capacity drops)."""
    b, s, d = x.shape
    xn = rms_norm(x, gamma, cfg.norm_eps).reshape(b * s, d)
    logits = xn.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, -1)
    top_w, top_ids = jax.lax.top_k(probs, cfg.top_k)
    top_w = top_w / top_w.sum(-1, keepdims=True)
    out = jnp.zeros_like(xn)
    for e_i in range(cfg.n_experts):
        he = R.swish(xn @ p["we_gate"][e_i]) * (xn @ p["we_up"][e_i])
        oe = he @ p["we_down"][e_i]
        wsel = jnp.where(top_ids == e_i, top_w, 0.0).sum(-1)
        out = out + oe * wsel[:, None].astype(oe.dtype)
    if cfg.n_shared_experts:
        out = out + (R.swish(xn @ p["shared_w_gate"])
                     * (xn @ p["shared_w_up"])) @ p["shared_w_down"]
    return out.reshape(b, s, d).astype(x.dtype)


# ---------------------------------------------------------------------------
# Mamba2 (SSD, chunked) — attention-free; matmul-dominant form for the MXU
# ---------------------------------------------------------------------------

def init_mamba(pb: ParamBuilder, cfg: ModelConfig) -> None:
    d, di, n, hd = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_head_dim
    nh = cfg.n_ssm_heads
    conv_ch = di + 2 * n
    pb.dense("w_in", (d, 2 * di + 2 * n + nh), ("fsdp", "tensor"))
    pb.dense("conv_w", (cfg.ssm_conv, conv_ch), (None, "tensor"), scale=0.5)
    pb.zeros("conv_b", (conv_ch,), ("tensor",))
    pb.zeros("A_log", (nh,), ("tensor",))
    pb.zeros("dt_bias", (nh,), ("tensor",))
    pb.zeros("D", (nh,), ("tensor",))
    pb.ones("ssm_norm", (di,), ("tensor",))
    pb.dense("w_out", (di, d), ("tensor", "fsdp"))


def _mamba_proj(p, x, cfg: ModelConfig):
    di, n, nh = cfg.d_inner, cfg.ssm_state, cfg.n_ssm_heads
    zxbcdt = x @ p["w_in"]
    z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * n], axis=-1)
    return z, xbc, dt  # xbc holds conv channels (x_in, B, C)


def _causal_conv(xbc, p, cfg: ModelConfig):
    """Depthwise causal conv, width cfg.ssm_conv (silu activation)."""
    w = p["conv_w"]                                     # (W, C)
    width = w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + xbc.shape[1]] * w[i] for i in range(width))
    return jax.nn.silu(out + p["conv_b"])


def _ssd_chunked(xh, dt, A, B, C, cfg: ModelConfig, h0=None):
    """SSD forward (Mamba-2).  xh: (b,s,nh,hd); dt: (b,s,nh);
    B, C: (b,s,n).  Returns y (b,s,nh,hd) and final state (b,nh,hd,n)."""
    b, s, nh, hd = xh.shape
    n = B.shape[-1]
    q = min(cfg.ssm_chunk, s)
    pad = (-s) % q
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    L = (s + pad) // q
    xh = xh.reshape(b, L, q, nh, hd).astype(jnp.float32)
    dt = dt.reshape(b, L, q, nh).astype(jnp.float32)
    Bc = B.reshape(b, L, q, n).astype(jnp.float32)
    Cc = C.reshape(b, L, q, n).astype(jnp.float32)

    dA = dt * A[None, None, None, :]                     # (b,L,q,nh) <= 0
    cs = jnp.cumsum(dA, axis=2)
    seg = cs[:, :, :, None, :] - jnp.swapaxes(cs[:, :, :, None, :], 2, 3)
    iota = jnp.arange(q)
    causal = (iota[:, None] >= iota[None, :])[None, None, :, :, None]
    decay = jnp.where(causal, jnp.exp(seg), 0.0)         # (b,L,q,q,nh)

    # intra-chunk (the diagonal blocks): y = (C B^T . decay . dt) x
    cb = jnp.einsum("blqn,blkn->blqk", Cc, Bc)           # (b,L,q,q)
    att = cb[..., None] * decay * dt[:, :, None, :, :]   # (b,L,q,k,nh)
    y_diag = jnp.einsum("blqkh,blkhd->blqhd", att, xh)

    # chunk states: h_c = sum_j exp(cs_end - cs_j) dt_j B_j x_j
    last = cs[:, :, -1:, :]                              # (b,L,1,nh)
    w_end = jnp.exp(last - cs) * dt                      # (b,L,q,nh)
    states = jnp.einsum("blqn,blqh,blqhd->blhdn", Bc, w_end, xh)

    # inter-chunk recurrence over L
    chunk_decay = jnp.exp(last[:, :, 0, :])              # (b,L,nh)

    def scan_fn(h, inp):
        st, dec = inp
        h_new = h * dec[:, :, None, None] + st
        return h_new, h

    if h0 is None:
        h0 = jnp.zeros((b, nh, hd, n), jnp.float32)
    h_final, h_prevs = jax.lax.scan(
        scan_fn, h0, (jnp.moveaxis(states, 1, 0),
                      jnp.moveaxis(chunk_decay, 1, 0)),
                      unroll=L if cfg.unroll_scans else 1)
    h_prevs = jnp.moveaxis(h_prevs, 0, 1)                # (b,L,nh,hd,n)

    y_inter = jnp.einsum("blqn,blqh,blhdn->blqhd", Cc, jnp.exp(cs), h_prevs)
    y = (y_diag + y_inter).reshape(b, L * q, nh, hd)[:, :s]
    return y, h_final


def mamba_apply(p, x, gamma, cfg: ModelConfig):
    """Pre-norm Mamba2 block (returns residual delta)."""
    b, s, d = x.shape
    nh, hd, n, di = (cfg.n_ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                     cfg.d_inner)
    xn = rms_norm(x, gamma, cfg.norm_eps)
    z, xbc, dt = _mamba_proj(p, xn, cfg)
    xbc = _causal_conv(xbc, p, cfg)
    xin, B, C = jnp.split(xbc, [di, di + n], axis=-1)
    xh = xin.reshape(b, s, nh, hd)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    y, _ = _ssd_chunked(xh, dt, A, B, C, cfg)
    y = y + xh.astype(jnp.float32) * p["D"].astype(jnp.float32)[None, None,
                                                                :, None]
    y = y.reshape(b, s, di).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["ssm_norm"], cfg.norm_eps)
    return constrain(y @ p["w_out"], "batch", None, None)


def mamba_init_cache(cfg: ModelConfig, batch: int, dtype):
    di, n = cfg.d_inner, cfg.ssm_state
    return {
        "h": jnp.zeros((batch, cfg.n_ssm_heads, cfg.ssm_head_dim, n),
                       jnp.float32),
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, di + 2 * n), dtype),
    }


def mamba_cache_specs(cfg: ModelConfig):
    return {"h": ("batch", "tensor", None, None),
            "conv": ("batch", None, "tensor")}


def mamba_prefill(p, x, gamma, cfg: ModelConfig):
    """Full-sequence SSD that also returns the decode cache (final SSM state
    + the raw conv window)."""
    b, s, d = x.shape
    nh, hd, n, di = (cfg.n_ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                     cfg.d_inner)
    xn = rms_norm(x, gamma, cfg.norm_eps)
    z, xbc_raw, dt = _mamba_proj(p, xn, cfg)
    xbc = _causal_conv(xbc_raw, p, cfg)
    xin, B, C = jnp.split(xbc, [di, di + n], axis=-1)
    xh = xin.reshape(b, s, nh, hd)
    dtv = jax.nn.softplus(dt.astype(jnp.float32)
                          + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    y, h_final = _ssd_chunked(xh, dtv, A, B, C, cfg)
    y = y + xh.astype(jnp.float32) * p["D"].astype(jnp.float32)[None, None,
                                                                :, None]
    y = y.reshape(b, s, di).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["ssm_norm"], cfg.norm_eps)
    w = cfg.ssm_conv - 1
    window = jnp.pad(xbc_raw, ((0, 0), (max(w - s, 0), 0), (0, 0)))[:, -w:]
    cache = {"h": h_final, "conv": window.astype(cfg.dtype)}
    return constrain(y @ p["w_out"], "batch", None, None), cache


def mamba_decode(p, x, gamma, cache, cfg: ModelConfig):
    """One-token SSM step: O(1) state update (no KV cache)."""
    b = x.shape[0]
    nh, hd, n, di = (cfg.n_ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                     cfg.d_inner)
    xn = rms_norm(x, gamma, cfg.norm_eps)
    z, xbc, dt = _mamba_proj(p, xn, cfg)                  # x: (b,1,d)
    window = jnp.concatenate([cache["conv"], xbc], axis=1)
    w = p["conv_w"]
    conv = jax.nn.silu((window * w[None]).sum(axis=1, keepdims=True)
                       + p["conv_b"])
    xin, B, C = jnp.split(conv, [di, di + n], axis=-1)
    xh = xin.reshape(b, nh, hd).astype(jnp.float32)
    dtv = jax.nn.softplus(dt[:, 0].astype(jnp.float32)
                          + p["dt_bias"].astype(jnp.float32))  # (b,nh)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    decay = jnp.exp(dtv * A[None])                        # (b,nh)
    Bv = B[:, 0].astype(jnp.float32)                      # (b,n)
    Cv = C[:, 0].astype(jnp.float32)
    h = cache["h"] * decay[:, :, None, None] + jnp.einsum(
        "bh,bhd,bn->bhdn", dtv, xh, Bv)
    y = jnp.einsum("bhdn,bn->bhd", h, Cv)
    y = y + xh * p["D"].astype(jnp.float32)[None, :, None]
    y = y.reshape(b, 1, di).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["ssm_norm"], cfg.norm_eps)
    new_cache = {"h": h, "conv": window[:, 1:]}
    return constrain(y @ p["w_out"], "batch", None, None), new_cache
