"""Continuous-batching LM serving on pipeline megakernels.

Programmatic API::

    from repro.launch.serve import ServeConfig, run
    report = run(ServeConfig(arch="smollm-135m", n_requests=16))

``ServeConfig`` describes the whole run (model, scheduler shape,
synthetic open-loop trace, sampling); ``run`` builds the engine,
replays the trace and returns a :class:`~repro.launch.engine.ServeReport`
(tokens/sec, p50/p99 per-token latency, occupancy, kernel-cache hit
rate, zero-recompile proof).  The CLI is a thin argparse veneer::

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
        --n-requests 16 --sampling greedy --json report.json

Sampling is ``--sampling {greedy,categorical}`` (+ ``--temperature``);
the old ``--greedy`` store-true flag defaulted to True and therefore
could never be disabled — replaced by the explicit choice.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
from dataclasses import dataclass
from typing import Optional, Tuple

from repro.launch.engine import Engine, ServeReport, synth_trace
from repro.runtime import compile_cache


@dataclass(frozen=True)
class ServeConfig:
    """Everything a serving run needs — model, scheduler, trace, sampling."""
    arch: str = "smollm-135m"
    reduced: bool = True
    backend: str = "pallas"      # pipeline codegen backend for the kernels
    dtype: str = "float32"
    # -- scheduler ----------------------------------------------------------
    max_batch: int = 4
    max_len: int = 96
    prompt_buckets: Tuple[int, ...] = (8, 16, 32)
    max_queue: Optional[int] = None  # bounded admission; None = unbounded
    # deepest compile-ladder rung a kernel may demote to; "grouped"
    # permits none, so a lowering failure raises with its own message
    max_rung: str = "interpreter"
    # -- synthetic open-loop trace -------------------------------------------
    n_requests: int = 16
    arrival_rate: float = 1.0    # requests per engine step
    prompt_lens: Tuple[int, int] = (4, 24)
    gen_lens: Tuple[int, int] = (4, 16)
    # -- sampling -----------------------------------------------------------
    sampling: str = "greedy"     # greedy | categorical
    temperature: float = 1.0
    seed: int = 0
    # -- run ----------------------------------------------------------------
    max_steps: Optional[int] = None
    keep_per_step: bool = True
    strict_no_recompile: bool = True


def build_engine(cfg: ServeConfig) -> Engine:
    """The configured engine (kernels not yet compiled — call
    ``warmup()`` or let ``Engine.run`` do it)."""
    import jax.numpy as jnp

    from repro import configs, pipeline, resilience

    options = pipeline.CompileOptions(
        backend=cfg.backend,
        resilience=resilience.ResiliencePolicy(max_rung=cfg.max_rung))
    mc = (configs.get_reduced_config(cfg.arch)
          if cfg.reduced else configs.get_config(cfg.arch))
    mc = dataclasses.replace(mc, dtype=getattr(jnp, cfg.dtype))
    mc = configs.with_pipeline(mc, options=options)
    return Engine(mc, max_batch=cfg.max_batch, max_len=cfg.max_len,
                  prompt_buckets=cfg.prompt_buckets,
                  sampling=cfg.sampling, temperature=cfg.temperature,
                  seed=cfg.seed, keep_per_step=cfg.keep_per_step,
                  strict_no_recompile=cfg.strict_no_recompile,
                  max_queue=cfg.max_queue)


def trace_of(cfg: ServeConfig, vocab: int):
    """The configured synthetic open-loop trace."""
    return synth_trace(cfg.n_requests, seed=cfg.seed,
                       arrival_rate=cfg.arrival_rate,
                       prompt_lens=cfg.prompt_lens, gen_lens=cfg.gen_lens,
                       vocab=vocab)


def run(cfg: ServeConfig, engine: Optional[Engine] = None) -> ServeReport:
    """Build the engine (unless given one), warm the kernel set unless it
    is warm already, and replay the trace."""
    engine = engine if engine is not None else build_engine(cfg)
    return engine.run(trace_of(cfg, engine.cfg.vocab),
                      max_steps=cfg.max_steps)


def main(argv=None) -> ServeReport:
    ap = argparse.ArgumentParser(
        description="continuous-batching serving on pipeline megakernels")
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--full", action="store_true",
                    help="full-size config (default: reduced)")
    ap.add_argument("--backend", default="pallas",
                    choices=("py", "jax", "pallas"))
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=96)
    ap.add_argument("--buckets", type=int, nargs="+", default=[8, 16, 32])
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bounded admission: reject arrivals past this "
                         "queue depth (default: unbounded)")
    ap.add_argument("--n-requests", type=int, default=16)
    ap.add_argument("--arrival-rate", type=float, default=1.0)
    ap.add_argument("--prompt-lens", type=int, nargs=2, default=[4, 24])
    ap.add_argument("--gen-lens", type=int, nargs=2, default=[4, 16])
    ap.add_argument("--sampling", default="greedy",
                    choices=("greedy", "categorical"))
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-steps", type=int, default=None)
    ap.add_argument("--json", dest="json_out", default=None,
                    help="write the full ServeReport as JSON")
    args = ap.parse_args(argv)
    compile_cache.configure()

    cfg = ServeConfig(arch=args.arch, reduced=not args.full,
                      backend=args.backend, max_batch=args.max_batch,
                      max_len=args.max_len,
                      prompt_buckets=tuple(args.buckets),
                      max_queue=args.max_queue,
                      n_requests=args.n_requests,
                      arrival_rate=args.arrival_rate,
                      prompt_lens=tuple(args.prompt_lens),
                      gen_lens=tuple(args.gen_lens),
                      sampling=args.sampling,
                      temperature=args.temperature, seed=args.seed,
                      max_steps=args.max_steps)
    report = run(cfg)
    print(f"arch={args.arch} backend={args.backend} "
          f"requests={report.n_completed}/{report.n_requests} "
          f"steps={report.steps} tokens={report.decode_tokens} "
          f"({report.tokens_per_s:.1f} tok/s incl. prefill) "
          f"ttft p50/p99={report.ttft_p50_ms:.2f}/{report.ttft_p99_ms:.2f}ms "
          f"itl p50/p99={report.itl_p50_ms:.2f}/{report.itl_p99_ms:.2f}ms "
          f"occupancy={report.mean_occupancy:.2f} "
          f"cache_hit_rate={report.cache_hit_rate:.3f} "
          f"recompiles={report.decode_recompiles}")
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(report.to_json(), f, indent=1)
    return report


if __name__ == "__main__":
    main()
