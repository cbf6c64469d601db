"""Distributed decode attention: sequence-parallel KV with the appendix's
significand-exponent combine.

The paper's appendix defines the safe combination of exponentiated partial
sums:   (S1,t1) + (S2,t2) = (S1 e^{t1-z} + S2 e^{t2-z}, z),  z = max(t1,t2)

That identity IS the flash-decoding partial-softmax merge: each device
holds a slice of the KV cache along the sequence axis, computes its local
(numerator, denominator, max) triple with the on-chip fused kernel, and
the cross-chip reduction applies the pair algebra with psum/pmax over the
ICI — turning long-context decode from one chip's memory-bound scan into
a parallel scan over ``data``-axis shards.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def _local_partial(q, k, v, scale, kv_valid):
    """Per-shard attention partials: (numerator, denominator, rowmax).

    q: (B,H,1,Dh); k,v: (B,Hkv,S_shard,Dh); kv_valid: how many of this
    shard's positions are filled (mask beyond)."""
    b, h, _, dh = q.shape
    hkv = k.shape[1]
    g = h // hkv
    qf = q.astype(jnp.float32).reshape(b, hkv, g, dh)
    s = jnp.einsum("bhgd,bhkd->bhgk", qf, k.astype(jnp.float32)) * scale
    cols = jnp.arange(k.shape[2])[None, None, None, :]
    s = jnp.where(cols < kv_valid, s, NEG_INF)
    m = s.max(axis=-1, keepdims=True)                     # (b,hkv,g,1)
    p = jnp.exp(s - m)
    num = jnp.einsum("bhgk,bhkd->bhgd", p, v.astype(jnp.float32))
    den = p.sum(axis=-1, keepdims=True)
    return num, den, m


def distributed_decode_attention(q, k_cache, v_cache, pos, mesh, *,
                                 scale: Optional[float] = None,
                                 seq_axis: str = "data"):
    """One-token attention against a KV cache sharded along its sequence
    dim over ``seq_axis``.  q: (B,H,1,Dh); caches: (B,Hkv,S,Dh) with S
    sharded.  ``pos``: number of valid cache entries (global)."""
    b, h, _, dh = q.shape
    hkv, s_total = k_cache.shape[1], k_cache.shape[2]
    if scale is None:
        scale = 1.0 / (dh ** 0.5)
    n_shards = mesh.shape[seq_axis]
    s_shard = s_total // n_shards

    def body(q, k, v, pos):
        idx = jax.lax.axis_index(seq_axis)
        start = idx * s_shard
        kv_valid = jnp.clip(pos + 1 - start, 0, s_shard)
        num, den, m = _local_partial(q, k, v, scale, kv_valid)
        # appendix pair algebra across shards: z = max(t_i)
        z = jax.lax.pmax(m, seq_axis)
        alpha = jnp.exp(m - z)                     # e^{t_i - z}
        num = jax.lax.psum(num * alpha, seq_axis)  # sum of S_i e^{t_i - z}
        den = jax.lax.psum(den * alpha, seq_axis)
        out = num / den
        g = h // hkv
        return out.reshape(b, h, 1, dh)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(None, None, seq_axis, None),
                  P(None, None, seq_axis, None), P()),
        out_specs=P(),
    )
    return fn(q, k_cache, v_cache, jnp.asarray(pos, jnp.int32)).astype(
        q.dtype)
