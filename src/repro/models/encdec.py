"""Whisper-style encoder-decoder backbone (audio frontend is a stub: the
assignment's ``input_specs()`` provides precomputed conv-frontend frame
embeddings).

Whisper uses LayerNorm + GELU MLPs: the LN -> fc1 matmul pair is exactly
the paper's Example 2, so the MLP here runs through the
Flash-LayerNorm+Matmul kernel (``layernorm_matmul``).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as K
from repro.models import layers as L
from repro.models.common import (ModelConfig, ParamBuilder, layer_norm,
                                 softmax_xent, stack_layers, stack_specs)
from repro.runtime.sharding import constrain


def _remat(fn, cfg):
    if not cfg.remat:
        return fn
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def _init_ln(pb: ParamBuilder, name: str, d: int):
    pb.ones(name + "_g", (d,), (None,))
    pb.zeros(name + "_b", (d,), (None,))


def _init_enc_layer(pb: ParamBuilder, cfg: ModelConfig):
    _init_ln(pb, "ln1", cfg.d_model)
    L.init_attention(pb.sub("attn"), cfg)
    _init_ln(pb, "ln2", cfg.d_model)
    pb.dense("fc1", (cfg.d_model, cfg.d_ff), ("fsdp", "tensor"))
    pb.zeros("fc1_b", (cfg.d_ff,), ("tensor",))
    pb.dense("fc2", (cfg.d_ff, cfg.d_model), ("tensor", "fsdp"))
    pb.zeros("fc2_b", (cfg.d_model,), (None,))


def _init_dec_layer(pb: ParamBuilder, cfg: ModelConfig):
    _init_enc_layer(pb, cfg)  # ln1+self-attn, ln2+mlp
    _init_ln(pb, "ln_x", cfg.d_model)
    L.init_attention(pb.sub("xattn"), cfg)


def _mlp(p, x, cfg: ModelConfig):
    """LN -> fc1 via the fused Example-2 kernel, then GELU -> fc2."""
    b, s, d = x.shape
    x2 = x.reshape(b * s, d)
    impl = {"fused_ref": "ref", "pallas": "pallas", "interpret": "interpret",
            "unfused": None}[cfg.mlp_impl]
    if impl is None:
        h = layer_norm(x2, p["ln2_g"], p["ln2_b"], cfg.norm_eps) @ p["fc1"]
    else:
        h = K.layernorm_matmul(x2, p["fc1"], p["ln2_g"], p["ln2_b"],
                               eps=cfg.norm_eps, impl=impl)
    h = jax.nn.gelu(h + p["fc1_b"])
    out = h @ p["fc2"] + p["fc2_b"]
    return constrain(out.reshape(b, s, d), "batch", None, None)


def _attn_block(p, x, cfg, ln, causal, kv=None):
    xn = layer_norm(x, p[ln + "_g"], p[ln + "_b"], cfg.norm_eps)
    name = "attn" if ln == "ln1" else "xattn"
    if kv is None:
        return L.attention_apply(p[name], xn, cfg, causal=causal,
                                 positions=None)
    # cross attention: q from x, k/v provided (encoder memory)
    b, s, _ = xn.shape
    h, dh = cfg.n_heads, cfg.d_head
    q = (xn @ p[name]["wq"]).reshape(b, s, h, dh).transpose(0, 2, 1, 3)
    o = K.flash_attention(q, kv["k"], kv["v"], causal=False,
                          impl=cfg.attn_impl, unroll=cfg.unroll_scans)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, h * dh)
    return constrain(o @ p[name]["wo"], "batch", None, None)


def _cross_kv(p, mem, cfg):
    b, s, _ = mem.shape
    hkv, dh = cfg.n_kv_heads, cfg.d_head
    k = (mem @ p["xattn"]["wk"]).reshape(b, s, hkv, dh).transpose(0, 2, 1, 3)
    v = (mem @ p["xattn"]["wv"]).reshape(b, s, hkv, dh).transpose(0, 2, 1, 3)
    return {"k": k, "v": v}


def _sinusoid(s: int, d: int) -> jax.Array:
    pos = jnp.arange(s)[:, None].astype(jnp.float32)
    i = jnp.arange(d // 2)[None, :].astype(jnp.float32)
    ang = pos / (10000.0 ** (2 * i / d))
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _sinusoid_at(pos, d: int) -> jax.Array:
    i = jnp.arange(d // 2).astype(jnp.float32)
    ang = pos.astype(jnp.float32) if hasattr(pos, "astype") else float(pos)
    ang = ang / (10000.0 ** (2 * i / d))
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)[None, None]


class EncDec:
    """Whisper backbone: bidirectional encoder over frame embeddings +
    causal decoder with cross attention."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    def init_params(self, key):
        cfg = self.cfg
        pb = ParamBuilder(key, cfg.dtype)
        pb.dense("embed", (cfg.vocab, cfg.d_model), ("tensor", "fsdp"),
                 scale=0.02)
        for name, n, init in (("enc", cfg.n_enc_layers, _init_enc_layer),
                              ("dec", cfg.n_layers, _init_dec_layer)):
            reps, spec = [], None
            for _ in range(n):
                b = ParamBuilder(pb._split(), cfg.dtype)
                init(b, cfg)
                reps.append(b.params)
                spec = b.specs
            pb.params[name] = stack_layers(reps)
            pb.specs[name] = stack_specs(spec)
        _init_ln(pb, "ln_enc_f", cfg.d_model)
        _init_ln(pb, "ln_f", cfg.d_model)
        return pb.build()

    def encode(self, params, frames):
        cfg = self.cfg
        x = frames.astype(cfg.dtype)
        x = x + _sinusoid(x.shape[1], cfg.d_model).astype(cfg.dtype)
        x = constrain(x, "batch", None, None)

        def body(x, lp):
            x = x + _attn_block(lp, x, cfg, "ln1", causal=False)
            x = x + _mlp(lp, x, cfg)
            return x, None

        fn = _remat(body, cfg)
        x, _ = jax.lax.scan(fn, x, params["enc"],
                            unroll=cfg.n_enc_layers if cfg.unroll_scans
                            else 1)
        return layer_norm(x, params["ln_enc_f_g"], params["ln_enc_f_b"],
                          cfg.norm_eps)

    def decode(self, params, mem, tokens):
        cfg = self.cfg
        s = tokens.shape[1]
        x = params["embed"][tokens].astype(cfg.dtype)
        x = x + _sinusoid(s, cfg.d_model).astype(cfg.dtype)
        x = constrain(x, "batch", None, None)

        def body(x, lp):
            x = x + _attn_block(lp, x, cfg, "ln1", causal=True)
            kv = _cross_kv(lp, mem, cfg)
            x = x + _attn_block(lp, x, cfg, "ln_x", causal=False, kv=kv)
            x = x + _mlp(lp, x, cfg)
            return x, None

        fn = _remat(body, cfg)
        x, _ = jax.lax.scan(fn, x, params["dec"],
                            unroll=cfg.n_layers if cfg.unroll_scans else 1)
        x = layer_norm(x, params["ln_f_g"], params["ln_f_b"], cfg.norm_eps)
        logits = x @ params["embed"].T
        return constrain(logits, "batch", None, "tensor")

    def forward(self, params, tokens, frames=None):
        mem = self.encode(params, frames)
        return self.decode(params, mem, tokens)

    def loss(self, params, tokens, labels, frames=None):
        return softmax_xent(self.forward(params, tokens, frames), labels)

    # -- serving ----------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int):
        cfg = self.cfg
        one = lambda: {
            "self": L.attention_init_cache(cfg, batch, max_len, cfg.dtype),
            "cross": {
                "k": jnp.zeros((batch, cfg.n_kv_heads, cfg.enc_seq,
                                cfg.d_head), cfg.dtype),
                "v": jnp.zeros((batch, cfg.n_kv_heads, cfg.enc_seq,
                                cfg.d_head), cfg.dtype)},
        }
        return stack_layers([one() for _ in range(cfg.n_layers)])

    def cache_specs(self):
        spec = {"self": L.attention_cache_specs(self.cfg),
                "cross": {"k": ("batch", "tensor", None, None),
                          "v": ("batch", "tensor", None, None)}}
        return stack_specs(spec)

    def prefill(self, params, tokens, frames=None, max_len=None):
        """Encode audio + run the decoder prompt; build self+cross caches."""
        cfg = self.cfg
        mem = self.encode(params, frames)
        s = tokens.shape[1]
        max_len = max_len or s
        x = params["embed"][tokens].astype(cfg.dtype)
        x = x + _sinusoid(s, cfg.d_model).astype(cfg.dtype)

        def body(x, lp):
            xn = layer_norm(x, lp["ln1_g"], lp["ln1_b"], cfg.norm_eps)
            q, k, v = L._qkv(lp["attn"], xn, cfg, None)
            y = K.flash_attention(q, k, v, causal=True, impl=cfg.attn_impl,
                                  unroll=cfg.unroll_scans)
            b = x.shape[0]
            y = y.transpose(0, 2, 1, 3).reshape(b, s,
                                                cfg.n_heads * cfg.d_head)
            x = x + constrain(y @ lp["attn"]["wo"], "batch", None, None)
            kv_cross = _cross_kv(lp, mem, cfg)
            x = x + _attn_block(lp, x, cfg, "ln_x", causal=False,
                                kv=kv_cross)
            x = x + _mlp(lp, x, cfg)
            pad = max_len - s
            cache = {
                "self": {"k": jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0))
                                      ).astype(cfg.dtype),
                         "v": jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0))
                                      ).astype(cfg.dtype)},
                "cross": jax.tree.map(lambda a: a.astype(cfg.dtype),
                                      kv_cross),
            }
            return x, cache

        x, caches = jax.lax.scan(body, x, params["dec"],
                                 unroll=cfg.n_layers if cfg.unroll_scans
                                 else 1)
        x = layer_norm(x, params["ln_f_g"], params["ln_f_b"], cfg.norm_eps)
        logits = x @ params["embed"].T
        return constrain(logits, "batch", None, "tensor"), caches

    def decode_step(self, params, caches, tokens, pos):
        """One decoder token.  The stacked self- and cross-attention
        caches ride through the layer scan in the carry (as in
        ``LM.decode_step``): a layer writes its new self-attention rows
        and reads its cross keys/values, and the step returns the same
        buffers, updated in place when the caller donates them."""
        cfg = self.cfg
        x = params["embed"][tokens].astype(cfg.dtype)
        x = x + _sinusoid_at(pos, cfg.d_model).astype(cfg.dtype)

        def body(carry, inp):
            x, cache = carry
            lp, layer = inp
            xn = layer_norm(x, lp["ln1_g"], lp["ln1_b"], cfg.norm_eps)
            y, new_self = L.attention_decode(lp["attn"], xn, cache["self"],
                                             layer, pos, cfg)
            x = x + y
            cross = jax.tree.map(lambda a: a[layer], cache["cross"])
            x = x + _attn_block(lp, x, cfg, "ln_x", causal=False, kv=cross)
            x = x + _mlp(lp, x, cfg)
            return (x, {"self": new_self, "cross": cache["cross"]}), None

        (x, new_caches), _ = jax.lax.scan(
            body, (x, caches), (params["dec"], jnp.arange(cfg.n_layers)),
            unroll=cfg.n_layers if cfg.unroll_scans else 1)
        x = layer_norm(x, params["ln_f_g"], params["ln_f_b"], cfg.norm_eps)
        logits = x @ params["embed"].T
        return constrain(logits, "batch", None, "tensor"), new_caches
