"""Chip smoke test: serve smollm-135m at its published widths on one TPU.

    python chip_smoke.py

One process drives the normal serving entry point
(``repro.launch.serve.build_engine`` / ``run``): the continuous-batching
engine on the generated Pallas megakernels, with smollm-135m at its
published widths (30 layers, d_model 576, 9 query / 3 KV heads, d_ff
1536, vocab 49152, tied embeddings) and weights drawn from a seed.  It
replays a seeded trace of 16 requests (prompts of 32-256 tokens, 16-64
new tokens each) at max_batch 8 and max_len 1024.

The script exits non-zero, and prints no result line, when JAX finds no
TPU; when a kernel resolved to interpret mode, demoted a ladder rung or
fell back to jax; when the run poisoned a request, recompiled after
warm-up or ended decode on another rung than ``pipeline-pallas``; when
the lowered prefill or decode step holds no Pallas kernel
(``tpu_custom_call``); or when the served prefill logits of two prompts
differ from the plain XLA model (``attn_impl="xla"``,
``mlp_impl="unfused"``) run at matmul precision "highest" by more than
``LOGIT_ATOL_HIGHEST`` (served at "highest" too) or
``LOGIT_ATOL_DEFAULT`` (served at the default precision).  The compile
ladder is capped at the grouped rung, so a kernel the TPU compiler
refuses raises with the compiler's own message.

The kernel plan cache starts empty (a fresh temporary directory), so no
plan or calibration profile of an earlier run can steer this one.  On
success the last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Served prefill logits vs the float32 XLA reference at matmul precision
# "highest", run twice.  Under "highest" both paths compute in float32
# and may differ only by summation order (online softmax, blocked dots).
# As served, the model's XLA matmuls run at the TPU's default precision
# (one bfloat16 pass), which moves these logits by about 0.04 against a
# range of about 2.4 on a TPU v5e; a masking, blocking or layout bug
# moves them by their whole scale.
LOGIT_ATOL_HIGHEST = 1e-3
LOGIT_ATOL_DEFAULT = 1e-1

SERVE = dict(arch="smollm-135m", reduced=False, backend="pallas",
             dtype="float32", max_batch=8, max_len=1024,
             prompt_buckets=(64, 128, 256), n_requests=16,
             prompt_lens=(32, 256), gen_lens=(16, 64), seed=0,
             max_rung="grouped")


class SmokeFailure(RuntimeError):
    """A condition the smoke test requires did not hold."""


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def compile_steps(engine, cfg):
    """Compile the engine's prefill (largest bucket) and decode steps
    ahead of time; require a Pallas kernel in each."""
    import jax
    import jax.numpy as jnp

    m, b = engine.model, cfg.max_batch
    steps = {
        "prefill": (jax.jit(functools.partial(m.prefill,
                                              max_len=cfg.max_len)),
                    (engine.params,
                     jnp.zeros((1, cfg.prompt_buckets[-1]), jnp.int32))),
        "decode": (jax.jit(m.decode_step),
                   (engine.params, engine.caches,
                    jnp.zeros((b, 1), jnp.int32),
                    jnp.zeros((b,), jnp.int32))),
    }
    for name, (fn, args) in steps.items():
        t0 = time.perf_counter()
        text = fn.lower(*args).compile().as_text()
        log(f"compile {name} step: {time.perf_counter() - t0:.2f} s, "
            f"{text.count('tpu_custom_call')} tpu_custom_call")
        require("tpu_custom_call" in text,
                f"the compiled {name} step holds no Pallas kernel")


def check_kernels() -> None:
    """Every pipeline kernel compiled for the chip at the grouped rung."""
    from repro import pipeline

    kernels = pipeline.default_cache().kernels()
    require(bool(kernels), "no pipeline kernel was compiled")
    for k in kernels:
        interpret = dict(k.key.opts).get("interpret")
        rep = k.lowering_report
        extents = {d: n * k.blocks[d] for d, n in k.dims.items()}
        log(f"kernel {'+'.join(k.out_names)}<-{','.join(k.in_names)} "
            f"extents={extents} blocks={k.blocks} rung={k.rung} "
            f"interpret={interpret} launches={rep.launches} "
            f"fallbacks={rep.fallbacks}")
        require(interpret is False, f"kernel {k.dims} runs interpreted")
        require(k.rung == "grouped", f"kernel {k.dims} served at {k.rung}")
        require(rep.fallbacks == 0, f"kernel {k.dims} fell back to jax")


def check_logits(engine, cfg, trace) -> None:
    """Served prefill logits of the shortest and the longest prompt
    against the plain XLA model at precision "highest"."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import build_model

    ref_cfg = dataclasses.replace(engine.cfg, attn_impl="xla",
                                  mlp_impl="unfused", pipeline_options=None)
    ref = jax.jit(build_model(ref_cfg).forward)
    served = jax.jit(functools.partial(engine.model.prefill,
                                       max_len=cfg.max_len))
    by_len = sorted(trace, key=lambda r: len(r.prompt))
    for req in (by_len[0], by_len[-1]):
        plen = len(req.prompt)
        bucket = next(b for b in cfg.prompt_buckets if plen <= b)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :plen] = req.prompt
        got = {"default": served(engine.params, jnp.asarray(padded))[0]}
        with jax.default_matmul_precision("highest"):
            got["highest"] = served(engine.params, jnp.asarray(padded))[0]
            want = np.asarray(ref(engine.params,
                                  jnp.asarray(padded[:, :plen])))[0]
        for prec, tol in (("highest", LOGIT_ATOL_HIGHEST),
                          ("default", LOGIT_ATOL_DEFAULT)):
            row = np.asarray(got[prec])[0, :plen]
            err = float(np.max(np.abs(row - want)))
            log(f"logits request {req.rid}: prompt {plen} (bucket "
                f"{bucket}) served at {prec} precision: max_abs_err="
                f"{err!r} max_abs_ref={float(np.max(np.abs(want))):.4f} "
                f"tol={tol}")
            require(bool(np.isfinite(row).all()),
                    f"non-finite served logits for request {req.rid}")
            require(err <= tol,
                    f"request {req.rid}: served logits at {prec} precision "
                    f"differ from the XLA reference by {err} > {tol}")


def smoke(cfg) -> None:
    from repro import pipeline
    from repro.launch import serve

    pipeline.reset_default_cache()
    engine = serve.build_engine(cfg)
    mc = engine.cfg
    log(f"model {mc.name}: layers={mc.n_layers} d_model={mc.d_model} "
        f"heads={mc.n_heads}/{mc.n_kv_heads} d_ff={mc.d_ff} "
        f"vocab={mc.vocab} tied={mc.tie_embeddings} dtype={cfg.dtype}; "
        f"max_batch={cfg.max_batch} max_len={cfg.max_len} "
        f"buckets={cfg.prompt_buckets}")

    t0 = time.perf_counter()
    engine.warmup()
    log(f"warm-up: {time.perf_counter() - t0:.2f} s, "
        f"{engine.warmup_compiles} pipeline compiles")
    compile_steps(engine, cfg)

    trace = serve.trace_of(cfg, mc.vocab)
    report = serve.run(cfg, engine)
    log(f"served: {report.n_completed}/{report.n_requests} requests, "
        f"{report.steps} steps, prefill_tokens={report.prefill_tokens} "
        f"decode_tokens={report.decode_tokens} "
        f"warmup_compiles={report.warmup_compiles} "
        f"decode_recompiles={report.decode_recompiles} "
        f"pallas_fallbacks={report.pallas_fallbacks} "
        f"degradations={report.degradations} "
        f"poisoned={report.n_poisoned} "
        f"decode_backend={report.decode_backend}")
    require(report.n_completed == cfg.n_requests,
            f"only {report.n_completed}/{cfg.n_requests} requests completed")
    require(report.degradations == 0, "a kernel or decode rung degraded")
    require(report.pallas_fallbacks == 0, "a Pallas region fell back")
    require(report.n_poisoned == 0, "a request was poisoned")
    require(report.decode_recompiles == 0, "decode recompiled")
    require(report.decode_backend == "pipeline-pallas",
            f"decode ended on {report.decode_backend}")
    check_kernels()
    check_logits(engine, cfg, trace)


def main() -> int:
    import jax

    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if dev.platform != "tpu":
        print("chip_smoke: JAX found no TPU", file=sys.stderr)
        return 1

    from repro.launch.serve import ServeConfig
    from repro.runtime import compile_cache

    log(f"jax compilation cache: {compile_cache.configure()}")
    with tempfile.TemporaryDirectory(prefix="repro-kernels-") as kdir:
        os.environ["REPRO_KERNEL_CACHE"] = kdir
        smoke(ServeConfig(**SERVE))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
