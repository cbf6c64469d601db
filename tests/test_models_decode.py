"""Serving correctness: prefill + one-token decode steps must reproduce the
teacher-forced forward logits for every cache type (GQA KV, MLA compressed
KV with absorbed decode, Mamba SSM state + conv window, jamba's mix,
whisper's self+cross caches), and the donated step (the cache updated in
place) must give the same logits and cache as the undonated one."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.models import build_model

pytestmark = pytest.mark.slow  # per-arch prefill/decode loops: not tier-1

CASES = ["smollm-135m", "deepseek-v3-671b", "mamba2-2.7b",
         "jamba-1.5-large-398b"]


def _decode(model, params, cache, feed, donate):
    """Run ``feed`` (a list of (tokens, pos)) through the jitted decode
    step; returns the logits of each step and the final cache."""
    step = jax.jit(model.decode_step, donate_argnums=(1,) if donate else ())
    logits = []
    for toks, pos in feed:
        lg, cache = step(params, cache, toks, pos)
        logits.append(np.asarray(lg[:, 0], np.float32))
    return logits, cache


def _same_as_undonated(model, params, cache, feed):
    """The undonated run, then the donated one from the same cache
    (which it consumes): bit-identical logits and caches."""
    want, want_cache = _decode(model, params, cache, feed, donate=False)
    got, got_cache = _decode(model, params, cache, feed, donate=True)
    assert all(c.is_deleted() for c in jax.tree.leaves(cache))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    jax.tree.map(lambda g, w: np.testing.assert_array_equal(
        np.asarray(g, np.float32), np.asarray(w, np.float32)),
        got_cache, want_cache)
    return got, got_cache


@pytest.mark.parametrize("arch", CASES)
def test_prefill_decode_matches_forward(arch, rng):
    """Two sequences prefilled to different lengths decode in one batch,
    each at its own position (ragged), until the longer writes row
    ``max_len - 1``."""
    cfg = get_reduced_config(arch)
    model = build_model(cfg)
    params, _ = model.init_params(jax.random.key(1))
    B, S = 2, 12
    starts = np.array([8, 5], np.int32)
    steps = S - starts.max()
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32)
    full = model.forward(params, toks)
    parts = []
    for i, p in enumerate(starts):
        lp, c = model.prefill(params, toks[i:i + 1, :p], max_len=S)
        np.testing.assert_allclose(np.asarray(lp[0, -1], np.float32),
                                   np.asarray(full[i, p - 1], np.float32),
                                   atol=1e-4, rtol=1e-4)
        parts.append(c)
    # stacked cache leaves are (n_layers, batch, ...)
    cache = jax.tree.map(lambda *a: jnp.concatenate(a, axis=1), *parts)
    rows = np.arange(B)
    feed = [(toks[rows, starts + t][:, None], jnp.asarray(starts + t))
            for t in range(steps)]
    logits, cache = _same_as_undonated(model, params, cache, feed)
    for t, lg in enumerate(logits):
        np.testing.assert_allclose(
            lg, np.asarray(full[rows, starts + t], np.float32),
            atol=1e-4, rtol=1e-4)
    # every row a step wrote sits where the prefill of the whole
    # sequence puts it (KV caches, by the position axis of one
    # sequence's stacked leaf; an SSM state has no rows)
    _, whole = model.prefill(params, toks, max_len=S)
    leaves = jax.tree_util.tree_leaves_with_path
    for (path, g), w in zip(leaves(cache), jax.tree.leaves(whole)):
        ax = {"k": 2, "v": 2, "ckv": 1, "krope": 1}.get(path[-1].key)
        if ax is None:
            continue
        for i, p in enumerate(starts):
            n = np.arange(p + steps)
            np.testing.assert_allclose(
                np.asarray(jnp.take(g[:, i], n, ax), np.float32),
                np.asarray(jnp.take(w[:, i], n, ax), np.float32),
                atol=1e-4, rtol=1e-4)


def test_whisper_prefill_decode(rng):
    cfg = get_reduced_config("whisper-tiny")
    model = build_model(cfg)
    params, _ = model.init_params(jax.random.key(1))
    B, S, P = 2, 12, 8
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32)
    frames = jnp.asarray(rng.normal(size=(B, cfg.enc_seq, cfg.d_model)),
                         cfg.dtype) * 0.02
    full = model.forward(params, toks, frames=frames)
    lp, cache = model.prefill(params, toks[:, :P], frames=frames, max_len=S)
    np.testing.assert_allclose(np.asarray(lp[:, -1], np.float32),
                               np.asarray(full[:, P - 1], np.float32),
                               atol=1e-4, rtol=1e-4)
    # the last step writes row max_len - 1
    feed = [(toks[:, t:t + 1], jnp.asarray(t, jnp.int32))
            for t in range(P, S)]
    logits, _ = _same_as_undonated(model, params, cache, feed)
    for t, lg in zip(range(P, S), logits):
        np.testing.assert_allclose(lg, np.asarray(full[:, t], np.float32),
                                   atol=1e-4, rtol=1e-4)


def test_moe_scatter_dispatch_matches_loop_oracle(rng):
    """The capacity/scatter MoE equals a dense per-expert loop when no
    tokens are dropped."""
    from repro.models import layers as L
    from repro.models.common import ParamBuilder
    cfg = get_reduced_config("qwen3-moe-30b-a3b")
    pb = ParamBuilder(jax.random.key(2), cfg.dtype)
    L.init_moe(pb, cfg)
    p, _ = pb.build()
    gamma = jnp.ones((cfg.d_model,), cfg.dtype)
    x = jnp.asarray(rng.normal(size=(2, 16, cfg.d_model)), cfg.dtype) * 0.3
    got = L.moe_apply(p, x, gamma, cfg)
    want = L.moe_ref(p, x, gamma, cfg)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-4, rtol=2e-3)


def test_ssd_chunk_size_invariance(rng):
    """The SSD chunked scan is exact: results do not depend on chunk size
    (the chunking is the paper's block decomposition applied to the SSM)."""
    import dataclasses
    from repro.models import layers as L
    from repro.models.common import ParamBuilder
    cfg = get_reduced_config("mamba2-2.7b")
    pb = ParamBuilder(jax.random.key(3), cfg.dtype)
    L.init_mamba(pb, cfg)
    p, _ = pb.build()
    gamma = jnp.ones((cfg.d_model,), cfg.dtype)
    x = jnp.asarray(rng.normal(size=(2, 24, cfg.d_model)), cfg.dtype) * 0.3
    outs = []
    for q in (4, 8, 24):
        c = dataclasses.replace(cfg, ssm_chunk=q)
        outs.append(np.asarray(L.mamba_apply(p, x, gamma, c), np.float32))
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], atol=2e-5, rtol=2e-5)
