"""Plain float32 reference of the dense family, and its fp8 control.

A straightforward ``jax.numpy`` forward pass of a Llama/Qwen2-style
decoder as published: token embedding; per layer RMSNorm, Q/K/V
projections (with bias where ``attention_bias`` says so, and for
``Qwen2ForCausalLM``, which projects them with bias and publishes no
such key), rotary
position embedding (rotate-half form, base ``rope_theta``), causal
grouped-query softmax attention, output projection and residual; then
RMSNorm, gated SwiGLU MLP and residual; a final RMSNorm and the head
(the embedding transposed when tied).  No kernel, cache, batching or
bucketing of the program is used, and nothing of the program is
imported: it reads the weight tree by its leaf names only.  Heads are
``head_dim`` wide where the configuration gives one, else
``hidden_size / num_attention_heads`` (``head_dim``).

It runs in float32 at matmul precision "highest", one layer per jitted
call (the stacked layer weights are indexed inside the call, so one
compile serves every layer), so it fits beside the served model's
weights.  ``quant=True`` gives the control: the same pass with both
operands of every weight matmul rounded to fp8 (e4m3, one scale per
tensor), the step below the served bf16 that would tempt a change.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
PAD = 256   # sequences are padded at the end to a multiple of this, so
            # that one compile serves many lengths (causal: exact)


def _q8(a):
    s = jnp.max(jnp.abs(a)) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, quant):
    a, w = a.astype(jnp.float32), w.astype(jnp.float32)
    if quant:
        a, w = _q8(a), _q8(w)
    return jnp.dot(a, w, precision=HI)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _rope(x, theta):
    s, d = x.shape[-2], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def _layer(x, stack, i, dims, quant):
    """Decoder layer ``i`` of the stacked weights on x (S, d), float32."""
    m = dict(dims)
    p = jax.tree.map(lambda a: a[i], stack)
    a, f = p["mixer"], p["mlp"]
    s = x.shape[0]
    h, hkv, dh = m["n_heads"], m["n_kv_heads"], m["d_head"]
    xn = _rms(x, p["ln1"], m["eps"])
    q, k, v = (_mm(xn, a[w], quant) for w in ("wq", "wk", "wv"))
    if m["qkv_bias"]:
        q, k, v = (t + a[b].astype(jnp.float32)
                   for t, b in ((q, "bq"), (k, "bk"), (v, "bv")))
    q = _rope(q.reshape(s, h, dh).transpose(1, 0, 2), m["theta"])
    k = _rope(k.reshape(s, hkv, dh).transpose(1, 0, 2), m["theta"])
    v = v.reshape(s, hkv, dh).transpose(1, 0, 2)
    k = jnp.repeat(k, h // hkv, axis=0)        # query head j reads kv j//g
    v = jnp.repeat(v, h // hkv, axis=0)
    sc = jnp.einsum("hqd,hkd->hqk", q, k, precision=HI) / jnp.sqrt(
        jnp.float32(dh))
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    sc = jnp.where(causal, sc, -jnp.inf)
    pr = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("hqk,hkd->hqd", pr, v, precision=HI)
    x = x + _mm(o.transpose(1, 0, 2).reshape(s, h * dh), a["wo"], quant)
    xn = _rms(x, p["ln2"], m["eps"])
    g = _mm(xn, f["w_gate"], quant)
    u = _mm(xn, f["w_up"], quant)
    return x + _mm(jax.nn.silu(g) * u, f["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def _logits(x, ln_f, head, dims, quant):
    return _mm(_rms(x, ln_f, dict(dims)["eps"]), head, quant)


def head_dim(model: dict) -> int:
    return model.get("head_dim", model["hidden_size"]
                     // model["num_attention_heads"])


def model_dims(model: dict) -> tuple:
    """The hashable dims the jitted layer needs, from a config file's
    ``model`` block."""
    qwen2 = "Qwen2ForCausalLM" in model.get("architectures", ())
    return tuple(sorted({
        "n_heads": model["num_attention_heads"],
        "n_kv_heads": model["num_key_value_heads"],
        "d_head": head_dim(model),
        "eps": float(model["rms_norm_eps"]),
        "theta": float(model["rope_theta"]),
        "qkv_bias": bool(model.get("attention_bias", qwen2)),
    }.items()))


def logits(model: dict, params, tokens, quant: bool = False) -> jax.Array:
    """Float32 logits of one token sequence, on the device: (S', vocab)
    with S' the sequence's length padded up to a multiple of ``PAD``;
    rows past the sequence are not to be read."""
    dims = model_dims(model)
    s = len(tokens)
    sp = -(-s // PAD) * PAD
    toks = np.zeros(sp, np.int32)
    toks[:s] = tokens
    x = params["embed"][jnp.asarray(toks)].astype(jnp.float32)
    stack = params["stage0"]["sub0"]
    for i in range(model["num_hidden_layers"]):
        x = _layer(x, stack, i, dims, quant)
    head = (params["embed"].T if model["tie_word_embeddings"]
            else params["head"])
    return _logits(x, params["ln_f"], head, dims, quant)
