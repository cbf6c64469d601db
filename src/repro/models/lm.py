"""Decoder-only LM assembly for the architecture zoo.

A model is a sequence of *stages*; each stage is a stack of identical
*super-layers* consumed with ``jax.lax.scan`` (so deepseek's 61 layers or
jamba's 72 don't blow up the HLO).  A super-layer is a list of sub-layers
(jamba: 7 mamba + 1 attention per period, alternating MoE).

Sub-layer kinds:  mixer in {attn, mla, mamba, none},
                  mlp   in {swiglu, moe, none}.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.common import (ModelConfig, ParamBuilder, rms_norm,
                                 softmax_xent, stack_layers, stack_specs)


def _remat(fn, cfg):
    if not cfg.remat:
        return fn
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)
from repro.runtime.sharding import constrain


@dataclass(frozen=True)
class SubLayer:
    mixer: str          # attn | mla | mamba | none
    mlp: str            # swiglu | moe | none
    d_ff: int = 0


@dataclass(frozen=True)
class Stage:
    n: int              # number of stacked super-layers
    subs: Tuple[SubLayer, ...]


def plan_stages(cfg: ModelConfig) -> List[Stage]:
    mixer = "mla" if cfg.use_mla else "attn"
    if cfg.family in ("dense", "vlm"):
        return [Stage(cfg.n_layers, (SubLayer(mixer, "swiglu", cfg.d_ff),))]
    if cfg.family == "moe":
        stages = []
        if cfg.n_dense_layers:
            stages.append(Stage(cfg.n_dense_layers,
                                (SubLayer(mixer, "swiglu", cfg.d_ff),)))
        stages.append(Stage(cfg.n_layers - cfg.n_dense_layers,
                            (SubLayer(mixer, "moe"),)))
        return stages
    if cfg.family == "ssm":
        return [Stage(cfg.n_layers, (SubLayer("mamba", "none"),))]
    if cfg.family == "hybrid":
        period = cfg.attn_period
        assert cfg.n_layers % period == 0
        subs = []
        for j in range(period):
            mix = "attn" if j == period // 2 else "mamba"
            mlp = "moe" if (j % cfg.moe_period == cfg.moe_period - 1) \
                else "swiglu"
            subs.append(SubLayer(mix, mlp, cfg.d_ff))
        return [Stage(cfg.n_layers // period, tuple(subs))]
    raise ValueError(cfg.family)


# ---------------------------------------------------------------------------
# sub-layer init / apply / prefill / decode
# ---------------------------------------------------------------------------

def _init_sublayer(pb: ParamBuilder, cfg: ModelConfig, spec: SubLayer):
    d = cfg.d_model
    if spec.mixer != "none":
        pb.ones("ln1", (d,), (None,))
        sub = pb.sub("mixer")
        if spec.mixer == "attn":
            L.init_attention(sub, cfg)
        elif spec.mixer == "mla":
            L.init_mla(sub, cfg)
        elif spec.mixer == "mamba":
            L.init_mamba(sub, cfg)
    if spec.mlp != "none":
        pb.ones("ln2", (d,), (None,))
        sub = pb.sub("mlp")
        if spec.mlp == "swiglu":
            L.init_swiglu(sub, cfg, spec.d_ff)
        elif spec.mlp == "moe":
            L.init_moe(sub, cfg)


def _apply_sublayer(p, x, cfg: ModelConfig, spec: SubLayer, causal=True):
    if spec.mixer == "attn":
        x = x + L.attention_apply(p["mixer"], rms_norm(x, p["ln1"],
                                                       cfg.norm_eps),
                                  cfg, causal=causal)
    elif spec.mixer == "mla":
        x = x + L.mla_apply(p["mixer"], rms_norm(x, p["ln1"], cfg.norm_eps),
                            cfg, causal=causal)
    elif spec.mixer == "mamba":
        x = x + L.mamba_apply(p["mixer"], x, p["ln1"], cfg)
    if spec.mlp == "swiglu":
        x = x + L.rmsnorm_swiglu_apply(p["mlp"], x, p["ln2"], cfg)
    elif spec.mlp == "moe":
        x = x + L.moe_apply(p["mlp"], x, p["ln2"], cfg)
    return x


def _sub_cache_init(cfg, spec: SubLayer, batch, max_len, dtype):
    if spec.mixer == "attn":
        return L.attention_init_cache(cfg, batch, max_len, dtype)
    if spec.mixer == "mla":
        return L.mla_init_cache(cfg, batch, max_len, dtype)
    if spec.mixer == "mamba":
        return L.mamba_init_cache(cfg, batch, dtype)
    return {}


def _sub_cache_specs(cfg, spec: SubLayer):
    if spec.mixer == "attn":
        return L.attention_cache_specs(cfg)
    if spec.mixer == "mla":
        return L.mla_cache_specs(cfg)
    if spec.mixer == "mamba":
        return L.mamba_cache_specs(cfg)
    return {}


def _decode_sublayer(p, x, cache, layer, pos, cfg, spec: SubLayer):
    """One sub-layer's decode step against its stacked cache: the mixer
    reads and writes layer ``layer`` of it and returns the whole stack."""
    if spec.mixer == "attn":
        y, cache = L.attention_decode(
            p["mixer"], rms_norm(x, p["ln1"], cfg.norm_eps), cache, layer,
            pos, cfg)
        x = x + y
    elif spec.mixer == "mla":
        y, cache = L.mla_decode(
            p["mixer"], rms_norm(x, p["ln1"], cfg.norm_eps), cache, layer,
            pos, cfg)
        x = x + y
    elif spec.mixer == "mamba":
        y, cache = L.mamba_decode(p["mixer"], x, p["ln1"], cache, layer,
                                  cfg)
        x = x + y
    if spec.mlp == "swiglu":
        x = x + L.rmsnorm_swiglu_apply(p["mlp"], x, p["ln2"], cfg)
    elif spec.mlp == "moe":
        x = x + L.moe_apply(p["mlp"], x, p["ln2"], cfg)
    return x, cache


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

class LM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.stages = plan_stages(cfg)

    # -- params ---------------------------------------------------------------
    def init_params(self, key: jax.Array):
        cfg = self.cfg
        pb = ParamBuilder(key, cfg.dtype)
        pb.dense("embed", (cfg.vocab, cfg.d_model), ("tensor", "fsdp"),
                 scale=0.02)
        for si, stage in enumerate(self.stages):
            reps_p, reps_s = [], None
            for _ in range(stage.n):
                spb = ParamBuilder(pb._split(), cfg.dtype)
                for j, spec in enumerate(stage.subs):
                    b = spb.sub(f"sub{j}")
                    _init_sublayer(b, cfg, spec)
                reps_p.append(spb.params)
                reps_s = spb.specs
            pb.params[f"stage{si}"] = stack_layers(reps_p)
            pb.specs[f"stage{si}"] = stack_specs(reps_s)
        pb.ones("ln_f", (cfg.d_model,), (None,))
        if not cfg.tie_embeddings:
            pb.dense("head", (cfg.d_model, cfg.vocab), ("fsdp", "tensor"),
                     scale=0.02)
        return pb.build()

    # -- forward ----------------------------------------------------------------
    def _embed(self, params, tokens, vision_embeds=None):
        x = params["embed"][tokens].astype(self.cfg.dtype)
        if self.cfg.family == "vlm" and vision_embeds is not None:
            x = jnp.concatenate([vision_embeds.astype(x.dtype), x], axis=1)
        return constrain(x, "batch", None, None)

    def _logits(self, params, x):
        x = rms_norm(x, params["ln_f"], self.cfg.norm_eps)
        head = (params["embed"].T if self.cfg.tie_embeddings
                else params["head"])
        logits = x @ head
        return constrain(logits, "batch", None, "tensor")

    def forward(self, params, tokens, vision_embeds=None):
        cfg = self.cfg
        x = self._embed(params, tokens, vision_embeds)

        for si, stage in enumerate(self.stages):
            def body(x, lp, stage=stage):
                for j, spec in enumerate(stage.subs):
                    x = _apply_sublayer(lp[f"sub{j}"], x, cfg, spec)
                return x, None

            fn = _remat(body, cfg)
            x, _ = jax.lax.scan(fn, x, params[f"stage{si}"],
                                unroll=stage.n if cfg.unroll_scans else 1)
        return self._logits(params, x)

    def loss(self, params, tokens, labels, vision_embeds=None):
        logits = self.forward(params, tokens, vision_embeds)
        if self.cfg.family == "vlm" and vision_embeds is not None:
            logits = logits[:, vision_embeds.shape[1]:]
        return softmax_xent(logits, labels)

    # -- caches -------------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int):
        cfg = self.cfg
        caches = {}
        for si, stage in enumerate(self.stages):
            def one(_):
                return {f"sub{j}": _sub_cache_init(cfg, spec, batch, max_len,
                                                   cfg.dtype)
                        for j, spec in enumerate(stage.subs)}
            caches[f"stage{si}"] = stack_layers(
                [one(i) for i in range(stage.n)])
        return caches

    def cache_specs(self):
        caches = {}
        for si, stage in enumerate(self.stages):
            spec = {f"sub{j}": _sub_cache_specs(self.cfg, s)
                    for j, s in enumerate(stage.subs)}
            caches[f"stage{si}"] = stack_specs(spec)
        return caches

    # -- decode ---------------------------------------------------------------------
    def decode_step(self, params, caches, tokens, pos):
        """tokens: (B, 1) next input token; pos: filled cache length —
        a scalar (lockstep batch) or a (B,) int vector (ragged
        continuous-batching step, each sequence at its own position).

        Each stage's stacked cache rides through the layer scan in the
        carry, with the layer index as the scanned input: a layer writes
        only its new rows (a Mamba layer its small state) and the step
        returns the same buffers, so a caller that donates ``caches``
        (``jax.jit(..., donate_argnums=(1,))``) updates them in place."""
        cfg = self.cfg
        x = self._embed(params, tokens)

        new_caches = {}
        for si, stage in enumerate(self.stages):
            def body(carry, inp, stage=stage):
                x, cache = carry
                lp, layer = inp
                cache = dict(cache)
                for j, spec in enumerate(stage.subs):
                    x, cache[f"sub{j}"] = _decode_sublayer(
                        lp[f"sub{j}"], x, cache[f"sub{j}"], layer, pos, cfg,
                        spec)
                return (x, cache), None

            (x, new_caches[f"stage{si}"]), _ = jax.lax.scan(
                body, (x, caches[f"stage{si}"]),
                (params[f"stage{si}"], jnp.arange(stage.n)),
                unroll=stage.n if cfg.unroll_scans else 1)
        return self._logits(params, x), new_caches

    def prefill(self, params, tokens, max_len: Optional[int] = None,
                vision_embeds=None):
        """Run the prompt, returning logits and a cache filled to len(prompt)
        (padded to ``max_len`` for subsequent decode steps)."""
        cfg = self.cfg
        x = self._embed(params, tokens, vision_embeds)
        s = x.shape[1]
        max_len = max_len or s

        caches = {}
        for si, stage in enumerate(self.stages):
            def body(x, lp, stage=stage):
                cache = {}
                for j, spec in enumerate(stage.subs):
                    p = lp[f"sub{j}"]
                    if spec.mixer == "attn":
                        xn = rms_norm(x, p["ln1"], cfg.norm_eps)
                        pos = jnp.arange(s) if cfg.rope_theta > 0 else None
                        q, k, v = L._qkv(p["mixer"], xn, cfg, pos)
                        if cfg.attn_impl == "pipeline":
                            y = L._attention_pipeline(
                                q, k, v, 1.0 / cfg.d_head ** 0.5, cfg,
                                causal=True)
                        else:
                            from repro.kernels import ops as K
                            y = K.flash_attention(q, k, v, causal=True,
                                                  impl=cfg.attn_impl,
                                                  unroll=cfg.unroll_scans)
                        b = x.shape[0]
                        y = y.transpose(0, 2, 1, 3).reshape(
                            b, s, cfg.n_heads * cfg.d_head)
                        x = x + constrain(y @ p["mixer"]["wo"],
                                          "batch", None, None)
                        pad = max_len - s
                        cache[f"sub{j}"] = {
                            "k": jnp.pad(k, ((0, 0), (0, 0), (0, pad),
                                             (0, 0))).astype(cfg.dtype),
                            "v": jnp.pad(v, ((0, 0), (0, 0), (0, pad),
                                             (0, 0))).astype(cfg.dtype),
                        }
                    elif spec.mixer == "mla":
                        xn = rms_norm(x, p["ln1"], cfg.norm_eps)
                        x = x + L.mla_apply(p["mixer"], xn, cfg)
                        ckv, krope = L._mla_kv_compressed(
                            p["mixer"], xn, cfg, jnp.arange(s))
                        pad = max_len - s
                        cache[f"sub{j}"] = {
                            "ckv": jnp.pad(ckv, ((0, 0), (0, pad),
                                                 (0, 0))).astype(cfg.dtype),
                            "krope": jnp.pad(krope,
                                             ((0, 0), (0, pad),
                                              (0, 0))).astype(cfg.dtype),
                        }
                    elif spec.mixer == "mamba":
                        y, st = L.mamba_prefill(p["mixer"], x, p["ln1"], cfg)
                        x = x + y
                        cache[f"sub{j}"] = st
                    if spec.mlp == "swiglu":
                        x = x + L.rmsnorm_swiglu_apply(p["mlp"], x, p["ln2"],
                                                       cfg)
                    elif spec.mlp == "moe":
                        x = x + L.moe_apply(p["mlp"], x, p["ln2"], cfg)
                return x, cache

            x, caches[f"stage{si}"] = jax.lax.scan(
                body, x, params[f"stage{si}"],
                unroll=stage.n if cfg.unroll_scans else 1)
        return self._logits(params, x), caches


def build_model(cfg: ModelConfig) -> LM:
    if cfg.family == "encdec":
        from repro.models.encdec import EncDec
        return EncDec(cfg)
    return LM(cfg)
