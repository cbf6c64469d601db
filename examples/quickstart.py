"""Quickstart: rediscover Flash Attention with the Blockbuster fusion
algorithm (paper Example 1), end to end in ~2 seconds on CPU.

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.core import array_program as AP
from repro.core import blocks as B
from repro.core import cost as C
from repro.core.codegen_py import render
from repro.core.fusion import FusionTrace, fuse
from repro.core.graph import internal_buffered_edges
from repro.core.interpreter import run
from repro.core.numerics import run_stabilized

# 1. the array program: Attention = Q@K^T -> /sqrt(d) -> softmax -> @V
dims = {"M": 4, "D": 2, "N": 8, "L": 2}
d_model = 64
graph = AP.attention_program(scale=1.0 / np.sqrt(d_model))

print("=" * 72)
print("INITIAL block program (paper Table 2 expansion, fully unfused):")
print("=" * 72)
print(render(graph))

# 2. run the fusion algorithm (rules applied in priority 8->4->5->9->3->1->2)
trace = FusionTrace()
snapshots = fuse(graph, trace)
print()
print(f"fusion applied {len(trace.steps)} rules "
      f"(the paper's Example 1 trace has 17 steps):")
for rule, path in trace.steps:
    print(f"  {path:8s} {rule}")

print()
print("=" * 72)
print("FINAL fused program == Flash Attention (paper Example 1 epilogue):")
print("=" * 72)
print(render(snapshots[-1]))
assert internal_buffered_edges(snapshots[-1]) == [], "fully fused!"

# 3. the objective: global-memory traffic collapse
t0, t1 = C.traffic(graph, dims), C.traffic(snapshots[-1], dims)
print()
print(f"kernel launches : {t0.launches} -> {t1.launches}")
print(f"block stores    : {sum(t0.stores.values())} -> "
      f"{sum(t1.stores.values())}")
print(f"block loads     : {sum(t0.loads.values())} -> "
      f"{sum(t1.loads.values())}")

# 4. logic preservation: interpret both against dense numpy
rng = np.random.default_rng(0)
Q = rng.normal(size=(4 * 8, d_model))
K = rng.normal(size=(8 * 8, d_model))
V = rng.normal(size=(8 * 8, 2 * 16))
inputs = {"Q": B.split(Q, 4, 2), "KT": B.split(K, 8, 2),
          "VT": B.split(V.T, 2, 8)}
S = (Q @ K.T) / np.sqrt(d_model)
P = np.exp(S - S.max(1, keepdims=True))
ref = (P / P.sum(1, keepdims=True)) @ V

out = B.merge(run_stabilized(snapshots[-1], inputs, dims)["O"])
print(f"max |fused - numpy| = {np.abs(out - ref).max():.2e}  "
      "(with the appendix's significand-exponent safety)")

# 5. the end-to-end pipeline: one call drives fuse -> selection (traffic
#    cost model) -> codegen and returns a cached, executing kernel that
#    takes plain dense arrays.  Swap backend="jax" for "py" (interpreter
#    oracle) or "pallas" (one mega-kernel; interpret-mode off-TPU).
from repro import pipeline

kern = pipeline.compile(graph, dims, backend="jax")
fused_out = np.asarray(kern({"Q": Q, "KT": K, "VT": V.T})["O"])
print()
print(f"pipeline.compile: backend={kern.backend} "
      f"snapshot={kern.snapshot_index} "
      f"predicted traffic x{kern.predicted_traffic_reduction:.2f} "
      f"max |kernel - numpy| = {np.abs(fused_out - ref).max():.2e}")
again = pipeline.compile(graph, dims, backend="jax")
print(f"second compile: cache_hit={again.cache_hit!r} "
      "(in-process; plans also persist on disk across processes)")

# 6. the decoder path: causal attention as a block program.  The mask is
#    a block-level operator fed by global query/key *position vectors*
#    (ordinary kernel inputs), so the same compiled kernel serves any
#    decode position.  The cost model knows fully-masked tiles are
#    skipped: predicted traffic is ~half the non-causal program's.
# queries and keys tile the SAME sequence (M == N block counts), which
# is what the mask-aware cost model assumes when it skips masked tiles
cdims = {"M": dims["N"], "D": dims["D"], "N": dims["N"], "L": dims["L"]}
seq = K.shape[0]
causal_graph = AP.causal_attention_program(scale=1.0 / np.sqrt(d_model))
ckern = pipeline.compile(causal_graph, cdims, backend="jax")
pos = np.arange(seq, dtype=np.float32)
Qc = np.concatenate([Q, Q], axis=0)[:seq]  # pad queries to the kv length
causal_out = np.asarray(ckern({"Q": Qc, "KT": K, "VT": V.T,
                               "QP": pos, "KP": pos})["O"])
Sc = (Qc @ K.T) / np.sqrt(d_model)
Sc = np.where(pos[:, None] >= pos[None, :], Sc, -np.inf)
Pc = np.exp(Sc - Sc.max(1, keepdims=True))
causal_ref = (Pc / Pc.sum(1, keepdims=True)) @ V
print()
print(f"causal pipeline.compile: snapshot={ckern.snapshot_index} "
      f"predicted traffic x{ckern.predicted_traffic_reduction:.2f} "
      f"max |kernel - numpy| = "
      f"{np.abs(causal_out - causal_ref).max():.2e}")
nc = C.traffic(fuse(AP.attention_program(1.0 / np.sqrt(d_model)))[-1],
               cdims).total_items()
cc = C.traffic(ckern.graph, cdims).total_items()
print(f"mask-aware cost model: causal moves {cc:.0f} items vs "
      f"{nc} non-causal at equal shapes (fully-masked tiles are free)")

# 7. multi-region Pallas lowering: EVERY snapshot lowers, not just the
#    fully fused one, and programs may have several outputs.  Here the
#    program returns both LayerNorm(X) @ Y and the normalized rows —
#    the partitioner (core/regions.py) splits the selected snapshot
#    into spine regions, emits one multi-output pallas_call per region,
#    and threads the intermediates; lowering_report proves no region
#    fell back off Pallas.
KK = 32.0
apb = AP.ArrayProgramBuilder()
x_in = apb.input("X", ("M", "K"))
yt_in = apb.input("YT", ("N", "K"))
ln = apb.layernorm_rows(x_in, KK)
z = apb.matmul_t(ln, yt_in, out_dim="N")
apb.output("Z", z)
apb.output("XN", ln)
multi = apb.build()

mdims = {"M": 2, "K": 4, "N": 2}
mblocks = {"M": 8, "K": 8, "N": 8}
mkern = pipeline.compile(multi, mdims, backend="pallas", blocks=mblocks)
X = rng.normal(size=(16, 32)).astype(np.float32)
Y = rng.normal(size=(32, 16)).astype(np.float32)
mout = mkern({"X": X, "YT": Y.T})
mu = X.mean(1, keepdims=True)
sd = np.sqrt((X ** 2).mean(1, keepdims=True) - mu ** 2)
xn_ref = (X - mu) / sd
print()
print(f"multi-output pallas: {mkern.lowering_report.summary()}")
print(f"  per-region predicted traffic: "
      + ", ".join(f"{c:.3g}" for c in mkern.region_costs))
print(f"  max |Z - numpy|  = "
      f"{np.abs(np.asarray(mout['Z']) - xn_ref @ Y).max():.2e}")
print(f"  max |XN - numpy| = "
      f"{np.abs(np.asarray(mout['XN']) - xn_ref).max():.2e}")
assert mkern.lowering_report.fallbacks == 0

# 8. measured autotuning: let selection optimize for TIME, not bytes.
#    The (calibrated) analytic traffic model prunes the block-count
#    sweep; only the top-K survivors are compiled and timed (warmup +
#    median-of-K, fenced); the wall-clock winner is what lowers and
#    caches.  The analytic choice is always among the timed candidates,
#    so the measured pick is never slower than it.  The pruning model's
#    coefficients come from the CalibrationProfile saved for this
#    (backend, device) in the kernel cache dir, if one exists —
#    `benchmarks/run.py --only pipeline` fits one for the *pallas*
#    backend from per-region kernel timings; other backends keep the
#    default constants until calibrated.
mkern2 = pipeline.compile(graph, backend="jax",
                          dim_candidates={"M": [2, 4], "D": [1, 2],
                                          "N": [4, 8], "L": [1, 2]},
                          autotune="measured", top_k=3)
print()
print(f"measured autotune: dims={mkern2.dims} "
      f"wall={mkern2.measured_s * 1e6:.0f}us "
      f"(predicted traffic x{mkern2.predicted_traffic_reduction:.2f})")
if mkern2.autotune_timings:  # None on a disk-plan hit: nothing re-timed
    for dkey, secs in mkern2.autotune_timings:
        print(f"  candidate {dict(dkey)}: {secs * 1e6:.0f}us")
else:
    print(f"  (cache_hit={mkern2.cache_hit!r}: the measured winner "
          "re-loaded without re-timing)")

# 9. region-group megakernels: the Pallas backend packs compatible
#    regions of the selected snapshot into one multi-stage pallas_call,
#    so cross-region intermediates stay VMEM-resident instead of
#    round-tripping through HBM.  Reading the lowering report:
#      - lowering_report.n_regions   how the snapshot partitioned
#      - lowering_report.launches    kernels actually launched per call
#                                    (groups; < n_regions == regions
#                                    sharing kernels)
#      - lowering_report.resident_edges  cross-region values that never
#                                    touched global memory
#      - region_costs / kernel_ids   residency-aware predicted cost per
#                                    *kernel*, paired by id (a
#                                    megakernel serving 3 regions is
#                                    one entry)
#    Example 3 is the paper's mega-kernel claim: rmsnorm -> two matmuls
#    + swish/hadamard -> matmul partitions into three regions on grids
#    (M,), (M,K), (M,N) that all share the M spine -> ONE kernel.
swiglu = AP.rmsnorm_ffn_swiglu_program(512.0)
sdims = {"M": 4, "D": 4, "K": 8, "N": 4}
sblocks = {"M": 16, "D": 16, "K": 16, "N": 16}
skern = pipeline.compile(swiglu, sdims, backend="pallas", blocks=sblocks)
srep = skern.lowering_report
print()
print(f"grouped pallas lowering: {srep.summary()}")
print(f"  {srep.n_regions} regions -> {srep.launches} launch(es), "
      f"{srep.resident_edges} VMEM-resident edges")
print(f"  predicted cost: snapshot (all edges global) {skern.cost:.3g} "
      f"-> grouped (resident edges free) {skern.grouped_cost:.3g}")
for gid, c in zip(skern.kernel_ids, skern.region_costs):
    print(f"  kernel {gid}: predicted {c:.3g}")
assert srep.fallbacks == 0 and srep.launches == 1
# group=False keeps the one-kernel-per-region schedule (spilled
# intermediates are donated via input_output_aliases); the grouped and
# ungrouped lowerings are differentially tested equal in CI
ukern = pipeline.compile(swiglu, sdims, backend="pallas", blocks=sblocks,
                         group=False)
print(f"  ungrouped for comparison: {ukern.lowering_report.launches} "
      "launches")

# 10. the compute-aware calibration profile: selection's cost model is
#     a CalibrationProfile — per-item-kind traffic coefficients plus,
#     since schema 2, per-op-class WORK coefficients (matmul /
#     elementwise / reduce FLOPs at the representative block extent), a
#     per-grid-cell instance coefficient, and per-dtype item scales
#     (bf16 blocks move half the bytes of f32, int8/fp8 a quarter).
#     The DEFAULT profile keeps every new coefficient at zero, so it
#     prices exactly the paper's bytes+launches objective —
#     bit-identical to the pre-compute-aware model.  A measured fit
#     (benchmarks/run.py --only pipeline fits one from per-kernel wall
#     times) turns the new terms on; with group=True (the pallas
#     default) selection then ranks snapshots by the SUM of grouped,
#     residency-aware kernel costs — the cost of what actually runs.
from dataclasses import replace

from repro.core import calibrate as CAL
from repro.core import selection as SEL

t_fused = C.traffic(snapshots[-1], dims)
base_cost = SEL.snapshot_cost(snapshots[-1], dims)
assert base_cost == (t_fused.bytes_moved(CAL.DEFAULT_ITEM_BYTES)
                     + CAL.KERNEL_LAUNCH_COST * t_fused.launches)
# units are arbitrary (selection only ranks): these price one matmul
# FLOP at ~1/100 the cost of moving one byte
compute_aware = replace(
    CAL.DEFAULT_PROFILE,
    work_coef={"matmul": 1e-2, "elementwise": 1e-3, "reduce": 1e-3},
    instance_coef=1e3)
print()
print("compute-aware profile (schema %d):" % CAL.PROFILE_SCHEMA)
print(f"  flops per class  : "
      + ", ".join(f"{k}={v:.3g}" for k, v in t_fused.flops().items()))
print(f"  traffic-only cost: {base_cost:.4g}")
print(f"  +work/instances  : "
      f"{SEL.snapshot_cost(snapshots[-1], dims, profile=compute_aware):.4g}")
print(f"  bf16 item coefs  : scaled x"
      f"{compute_aware.dtype_scale['bf16']} via item_coef_for('bf16')")
# grouped vs global objective on the same snapshot (what select ranks
# by under group=True):
print(f"  objective: global {SEL.objective_cost(snapshots[-1], dims):.4g}"
      f" vs grouped "
      f"{SEL.objective_cost(snapshots[-1], dims, group=True):.4g}")
# re-fit + re-pin loop: PYTHONPATH=src:. python benchmarks/run.py
#   --only pipeline --preset ci --json BENCH_ci.json   (fits + saves a
#   profile under the kernel cache; writes per-row region_spearman)
# then python benchmarks/check_regression.py --pin BENCH_ci.json \
#   benchmarks/baseline.json pins the gated keys, including the rank
#   agreement the compute-aware features bought.

# 11. numerical safety: the appendix's online-softmax pass, compiled.
#     pipeline.compile stabilizes softmax-bearing programs BY DEFAULT
#     (stabilize=None auto-detects a block-valued top-level exp via
#     numerics.needs_stabilization): numerics.stabilize rewrites the
#     exp producer into row_max / row_shift / exp(shifted) and threads
#     the exponent alongside the significand, so the fused serial spine
#     carries a running "max" with its accumulators retagged "+@k"
#     (rescale-on-new-max).  That IS Flash Attention's online softmax,
#     derived from the paper's fused program — and it lowers on every
#     backend, still as ONE Pallas launch with zero fallbacks.
#     The flag is part of the cache key (stabilized and raw kernels
#     never alias) and of the on-disk CachePlan; pass stabilize=False
#     to opt out (e.g. to reproduce the raw paper listings), or
#     stabilize=True to force it on an exp-free program (a no-op
#     rewrite there).  Exp-free programs (layernorm, swiglu) skip the
#     pass automatically: same graphs, same cache keys as before.
import warnings

huge = {"Q": (Q * 2000).astype(np.float32),   # |logit| ~ 1e4
        "KT": K.T.astype(np.float32),
        "VT": V.T.astype(np.float32)}
safe = pipeline.compile(graph, dims, backend="jax")
assert safe.stabilized           # auto-detected, no opt-in needed
out = np.asarray(safe(huge)["O"])
raw = pipeline.compile(graph, dims, backend="jax", stabilize=False)
with warnings.catch_warnings():
    warnings.simplefilter("ignore")      # overflow in exp, by design
    out_raw = np.asarray(raw(huge)["O"])
print()
print("numerical safety at |logit| ~ 1e4:")
print(f"  stabilized (default): finite={bool(np.isfinite(out).all())}")
print(f"  stabilize=False     : finite={bool(np.isfinite(out_raw).all())}")
assert np.isfinite(out).all() and not np.isfinite(out_raw).all()

# 12. the serving surface: pipeline.compile's knobs live in ONE frozen,
#     hashable CompileOptions dataclass (options=...), and the serving
#     engine (launch/engine.py) drives continuous-batching decode
#     through the compiled megakernels.  Migration note: the old flat
#     kwargs (pipeline.compile(g, dims, backend=..., blocks=...,
#     interpret=...)) still work — they are collected into a
#     CompileOptions internally and produce byte-identical cache keys —
#     but options= is the primary API: it can be stored on a
#     ModelConfig (configs.with_pipeline(cfg, options=o)), used as an
#     lru_cache/dict key, and .replace()'d per call site.  Passing both
#     forms at once is a TypeError.
opts = pipeline.CompileOptions(backend="jax", blocks={"M": 8})
k_opts = pipeline.compile(graph, dims, options=opts)
k_kw = pipeline.compile(graph, dims, backend="jax", blocks={"M": 8})
assert k_opts.key == k_kw.key          # the kwargs shim aliases exactly
assert opts == opts.replace()          # frozen + hashable
print()
print(f"CompileOptions: {opts.backend} blocks={opts.blocks_dict} "
      f"hash={hash(opts) & 0xffff:#x} (kwargs shim aliases: "
      f"{k_opts.key == k_kw.key})")

#     The serving engine: an open-loop arrival trace through a
#     slot-based scheduler.  Prompts prefill padded to a shape bucket
#     (exact under causal masking — pad keys sit at future positions);
#     every active sequence then advances one token per tick through a
#     SINGLE ragged decode step whose per-sequence cache positions are
#     kernel *data* (the §6 position vectors), so the same compiled
#     kernels serve every step: warmup compiles one prefill pipeline
#     per bucket plus the full-batch decode, and the run loop pins
#     steady-state recompiles to zero via kernel-cache stats.
#     benchmarks/serve_bench.py gates tokens/sec and the zero-recompile
#     pin in CI; python -m repro.launch.serve --backend pallas runs the
#     full CLI.
from repro import configs
from repro.launch.engine import Engine, synth_trace

serve_cfg = configs.with_pipeline(
    configs.get_reduced_config("smollm-135m", n_layers=2, d_model=64,
                               n_heads=2, n_kv_heads=2, d_head=32,
                               d_ff=128, vocab=256),
    options=pipeline.CompileOptions(backend="jax"))
engine = Engine(serve_cfg, max_batch=2, max_len=32, prompt_buckets=(8,),
                sampling="greedy", seed=0)
trace = synth_trace(4, seed=0, arrival_rate=1.0, prompt_lens=(3, 8),
                    gen_lens=(2, 4), vocab=serve_cfg.vocab)
report = engine.run(trace)
print(f"serving: {report.n_completed}/{report.n_requests} requests in "
      f"{report.steps} steps, {report.decode_tokens} tokens, "
      f"occupancy {report.mean_occupancy:.2f}, "
      f"recompiles after warmup = {report.decode_recompiles}")
assert report.n_completed == len(trace)
assert report.decode_recompiles == 0   # positions are data, not shape

# 13. what happens when things fail: every compile now runs on a
#     degradation LADDER — grouped megakernel -> ungrouped per-region
#     pallas -> jax -> interpreter.  When a rung raises,
#     pipeline.compile demotes one rung and keeps going; the kernel you
#     get back carries the full provenance in .resilience_report.  The
#     happy path costs one `try` around the lowering call that already
#     existed, nothing is remembered between compiles, and demotion
#     never swallows YOUR mistakes: configuration errors (pallas
#     without blocks) still raise ValueError before any rung runs.
#
#     Triage runbook, in the order things break:
#       * kernel.resilience_report.summary() — which rung served the
#         compile and every failed attempt (rung, elapsed, error);
#         demotions > 0 in production is a backend bug to
#         file, not a crash to page on.
#       * pipeline.default_cache().stats — corrupt_plans /
#         corrupt_graphs / quarantined / write_errors name every
#         recovered cache error; the corrupt bytes sit untouched in
#         <cache>/quarantine/ for inspection (entries are checksummed
#         envelopes, verified on every read, written atomically);
#         at startup KernelCache() also sweeps orphaned *.tmp files and
#         stale unheld .lock files and caps <cache>/quarantine/.
#       * ServeReport.failures — one structured record per poisoned /
#         deadline-evicted / rejected request and per watchdog decode
#         demotion; report.degradations + report.quarantined roll the
#         run's counters up (both pinned to ZERO on the clean path by
#         benchmarks/check_regression.py, and chaos-tested in the CI
#         `chaos` job via a seeded resilience.FaultPlan —
#         $REPRO_FAULT_PLAN drives the same machinery from the shell).
from repro import resilience as RZ

outage = RZ.FaultPlan([RZ.FaultSpec(site="compile:grouped",
                                    kind="raise",
                                    message="demo outage")])
with RZ.faults(outage), warnings.catch_warnings():
    warnings.simplefilter("ignore")  # the demotion warns; demo hides it
    k_demoted = pipeline.compile(multi, mdims, backend="pallas",
                                 blocks=mblocks,
                                 cache=pipeline.KernelCache(disk=False))
print()
print("resilience: injected a grouped-rung failure ->")
print(f"  {k_demoted.resilience_report.summary()}")
z_demoted = np.asarray(k_demoted({"X": X, "YT": Y.T})["Z"])
np.testing.assert_allclose(z_demoted, xn_ref @ Y, rtol=2e-4, atol=2e-4)
print(f"  demoted kernel output matches the reference: True "
      f"(served by rung {k_demoted.rung!r})")
assert k_demoted.rung == "ungrouped"
assert k_demoted.resilience_report.demotions == 1

# a bounded policy turns exhaustion into a typed, report-carrying error
strict = pipeline.CompileOptions(
    backend="pallas", blocks=mblocks,
    resilience=RZ.ResiliencePolicy(max_rung="ungrouped"))
both_down = RZ.FaultPlan([RZ.FaultSpec(site="compile:grouped"),
                          RZ.FaultSpec(site="compile:ungrouped")])
with RZ.faults(both_down), warnings.catch_warnings():
    warnings.simplefilter("ignore")
    try:
        pipeline.compile(multi, mdims, options=strict,
                         cache=pipeline.KernelCache(disk=False))
        raise AssertionError("bounded ladder should have exhausted")
    except RZ.LadderError as e:
        print(f"  bounded ladder exhausted as designed: "
              f"{len(e.report.attempts)} attempts, "
              f"last rung {e.report.attempts[-1].rung!r}")
