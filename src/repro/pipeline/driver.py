"""The end-to-end compile driver: array/block program -> fusion ->
snapshot + block-shape selection -> backend codegen -> cached callable.

    kern = pipeline.compile(AP.attention_program(0.125),
                            dims={"M": 2, "D": 2, "N": 4, "L": 2},
                            backend="jax")
    out = kern({"Q": Q, "KT": K, "VT": V.T})["O"]

Backends:

* ``"py"``     — the reference interpreter (``codegen_py.compile_py``);
                 slow, numpy-level, the differential oracle.
* ``"jax"``    — ``codegen_jax.compile_program`` under ``jax.jit``
                 (vmap/scan lowering; runs everywhere, differentiable).
* ``"pallas"`` — ``codegen_pallas.emit_program``: the selected snapshot
                 is partitioned into spine regions, the regions are
                 packed into megakernel *groups* (compatible parallel
                 spines share one kernel, cross-region values stay
                 VMEM-resident, under a VMEM budget), and each group
                 lowers to one real multi-stage ``pallas_call``
                 (interpret-mode off-TPU); the chained schedule runs
                 under ``jax.jit`` with dying intermediates donated via
                 ``input_output_aliases``.  Requires ``blocks`` (per-dim
                 block sizes).  ``CompiledKernel.lowering_report``
                 records the regions emitted, kernels launched,
                 resident edges, and fallbacks taken (zero for every
                 in-repo program — there is no walk-back to a
                 differently-fused snapshot: what selection picked is
                 what runs).

Every compiled kernel takes and returns **merged dense arrays** keyed by
program input/output names, so all three backends are drop-in
interchangeable — that is what the differential test harness exploits.

Results are memoized in a two-level :class:`KernelCache` keyed by
``(Graph.fingerprint(), dims, backend, blocks, fused)`` plus the
``cache.CODEGEN_VERSION`` salt (on-disk plans written by an older
fusion/selection/codegen build are never loaded): in-process hits return
the existing jitted callable; on-disk hits skip fusion + selection and
only re-lower.
"""

from __future__ import annotations

import functools
import time
import warnings
from dataclasses import dataclass, replace
from math import lcm
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro import resilience as RZ
from repro.core import calibrate as CAL
from repro.core import numerics as NU
from repro.core import selection as SEL
from repro.core.fusion import FusionTrace, fuse
from repro.core.graph import Graph
from repro.pipeline import packing as P
from repro.pipeline.cache import (CacheKey, CachePlan, KernelCache,
                                  default_cache)
from repro.pipeline.options import DEFAULT_OPTIONS, CompileOptions

BACKENDS = ("py", "jax", "pallas")
AUTOTUNE_OBJECTIVES = ("analytic", "measured")


@dataclass
class CompiledKernel:
    """A ready-to-run fused kernel plus its compilation provenance."""

    key: CacheKey
    backend: str
    graph: Graph                      # the selected snapshot
    dims: Dict[str, int]
    blocks: Optional[Dict[str, int]]
    snapshot_index: int
    cost: float                       # predicted traffic cost (selected)
    initial_cost: float               # same model on the unfused program
    cache_hit: Optional[str]          # None | "memory" | "disk"
    # True when numerics.stabilize rewrote the snapshots before
    # selection/lowering (online-softmax-safe exp handling)
    stabilized: bool
    in_names: List[str]
    out_names: List[str]
    _fn: Callable[[Dict[str, Any]], Dict[str, Any]] = None  # type: ignore
    # pallas backend only: regions emitted / fallbacks taken / kernels
    # launched (see codegen_pallas.LoweringReport) and the cost model's
    # residency-aware per-kernel traffic attribution of the selected
    # snapshot, with the kernel ids the timing harness pairs against
    lowering_report: Optional[Any] = None
    region_costs: Optional[Tuple[float, ...]] = None
    kernel_ids: Optional[Tuple[str, ...]] = None
    # autotune="measured" only: the winner's wall seconds and every
    # (dims, seconds) candidate the autotuner timed (the analytic choice
    # is always among them)
    measured_s: Optional[float] = None
    autotune_timings: Optional[Tuple] = None
    # fault provenance (resilience.ResilienceReport): the rung requested,
    # the rung that actually served the compile, and every ladder attempt
    # in between — present on every compile (the happy path is a single
    # ok attempt at the requested rung, zero demotions)
    resilience_report: Optional[Any] = None

    def __call__(self, inputs: Dict[str, Any]) -> Dict[str, Any]:
        missing = [n for n in self.in_names if n not in inputs]
        if missing:
            raise KeyError(f"missing kernel inputs {missing}; "
                           f"expected {self.in_names}")
        return self._fn(inputs)

    @property
    def predicted_traffic_reduction(self) -> float:
        return self.initial_cost / max(self.cost, 1e-30)

    @property
    def launches(self) -> Optional[int]:
        """Kernels launched per call (pallas: groups emitted)."""
        return (self.lowering_report.launches
                if self.lowering_report is not None else None)

    @property
    def resident_edges(self) -> Optional[int]:
        """Cross-region values kept VMEM-resident instead of
        round-tripping through global memory (pallas grouped lowering)."""
        return (self.lowering_report.resident_edges
                if self.lowering_report is not None else None)

    @property
    def rung(self) -> Optional[str]:
        """The degradation-ladder rung that served this compile
        (``"grouped"``/``"ungrouped"``/``"jax"``/``"interpreter"``)."""
        return (self.resilience_report.rung
                if self.resilience_report is not None else None)

    @property
    def grouped_cost(self) -> Optional[float]:
        """Residency-aware predicted cost of what actually runs: the sum
        of the per-kernel attributions (``cost`` is the paper model's
        snapshot cost, which charges every cross-region edge)."""
        return (sum(self.region_costs)
                if self.region_costs is not None else None)


def _io_info(g: Graph):
    in_info = [(g.nodes[i].name, g.nodes[i].vtype) for i in g.input_ids]
    out_info = [(g.nodes[o].name, vt)
                for o, vt in zip(g.output_ids, P.output_types(g))]
    return in_info, out_info


def _lower_py(g: Graph, dims: Dict[str, int]):
    from repro.core.codegen_py import compile_py
    in_info, out_info = _io_info(g)
    prog = compile_py(g, dims)

    def call(inputs: Dict[str, Any]) -> Dict[str, Any]:
        nested = {nm: P.to_nested(np.asarray(inputs[nm]), vt, dims)
                  for nm, vt in in_info}
        outs = prog(nested)
        return {nm: P.from_nested(outs[nm], vt, dims)
                for nm, vt in out_info}

    return call


def _lower_jax(g: Graph, dims: Dict[str, int], jit):
    """``jit`` is ``True`` (whole-program ``jax.jit``), ``False`` (eager),
    or ``"per-op"``: every top-level operator jitted separately and
    dispatched from python — the honest launch-per-operator unfused
    baseline (whole-program jit would let XLA fuse the graph itself)."""
    import jax
    from repro.core.codegen_jax import compile_program
    in_info, out_info = _io_info(g)
    per_op = jit == "per-op"
    prog = compile_program(g, per_op_jit=per_op)

    def fn(*merged):
        stacked = [P.to_stacked(a, vt, dims)
                   for (_, vt), a in zip(in_info, merged)]
        outs = prog(*stacked)
        return tuple(P.from_stacked(o, vt, dims)
                     for (_, vt), o in zip(out_info, outs))

    if jit and not per_op:
        fn = jax.jit(fn)

    def call(inputs: Dict[str, Any]) -> Dict[str, Any]:
        outs = fn(*[inputs[nm] for nm, _ in in_info])
        return {nm: o for (nm, _), o in zip(out_info, outs)}

    return call


def _region_plan(g: Graph):
    """Partition the selected snapshot once; the plan is shared between
    per-kernel cost attribution and the Pallas lowering.  Returns
    ``(plan, error)``: when the partitioner cannot split, ``plan`` is
    ``None`` and ``error`` carries the ``RegionError`` text — recorded in
    ``LoweringReport.plan_error`` / ``ResilienceReport.plan_error`` so
    the demotion to emit_program's whole-program fallback is visible to
    ``check_regression.py`` and the serve warmup checks instead of being
    silently swallowed here."""
    from repro.core import regions as REG
    try:
        return REG.plan_program(g), None
    except REG.RegionError as err:
        return None, str(err)


def _grouped_plan(pplan, dims: Dict[str, int],
                  blocks: Optional[Dict[str, int]], group: bool):
    """Pack the region DAG into megakernel groups (or one-region groups
    when ``group=False``) — shared between costing and lowering."""
    from repro.core import regions as REG
    if pplan is None:
        return None
    return (REG.group_plan(pplan, dims, blocks) if group
            else REG.ungrouped_plan(pplan))


def _lower_pallas(g: Graph, dims: Dict[str, int],
                  blocks: Optional[Dict[str, int]], interpret: bool,
                  program_plan=None, grouped_plan=None,
                  group: bool = True, jit: bool = True,
                  name: Optional[str] = None):
    """Lower the selected snapshot itself — no walking back to a
    differently-fused candidate.  Returns (call, LoweringReport).  The
    chained kernel schedule runs under ``jax.jit`` (when ``jit``) so
    XLA plans the spilled intermediate buffers once and the per-kernel
    ``input_output_aliases`` donations actually reuse them."""
    import jax
    from repro.core.codegen_pallas import emit_program
    if blocks is None:
        raise ValueError(
            "backend='pallas' needs per-dim block sizes: pass blocks=")
    missing = [d for d in dims if d not in blocks]
    if missing:
        raise ValueError(f"blocks missing sizes for dims {missing}")
    f, report = emit_program(g, dims, blocks, interpret=interpret,
                             program_plan=program_plan,
                             grouped_plan=grouped_plan, group=group,
                             name=name)
    if report.fallbacks:
        warnings.warn(
            "pallas lowering fallback: "
            f"{report.fallbacks}/{report.n_regions} regions ran on the "
            f"jax backend ({report.summary()})", RuntimeWarning,
            stacklevel=3)
    in_info, out_info = _io_info(g)
    exec_f = jax.jit(f) if jit else f

    def call(inputs: Dict[str, Any]) -> Dict[str, Any]:
        outs = exec_f(*[inputs[nm] for nm, _ in in_info])
        return {nm: o for (nm, _), o in zip(out_info, outs)}

    # the raw (un-jitted) emit_program callable carries the per-kernel
    # runners the timing harness (core/timing.region_times) needs
    call.raw_program = f
    return call, report


def _rung_thunk(rung: str, g: Graph, dims: Dict[str, int], *,
                blocks: Optional[Dict[str, int]], interpret, jit,
                pplan, gplan, group: bool,
                name: Optional[str] = None) -> Callable[[], Tuple]:
    """The lowering a ladder rung runs; every thunk returns
    ``(call, LoweringReport-or-None)``.  ``gplan`` is only reusable at
    the rung it was packed for — a demoted rung recomputes its own."""
    if rung == "grouped":
        return lambda: _lower_pallas(
            g, dims, blocks, interpret, program_plan=pplan,
            grouped_plan=gplan if group else None, group=True,
            jit=bool(jit), name=name)
    if rung == "ungrouped":
        return lambda: _lower_pallas(
            g, dims, blocks, interpret, program_plan=pplan,
            grouped_plan=None if group else gplan, group=False,
            jit=bool(jit), name=name)
    if rung == "jax":
        return lambda: (_lower_jax(g, dims, jit), None)
    return lambda: (_lower_py(g, dims), None)


def _ladder_lower(rungs: Tuple[str, ...], make_thunk: Callable,
                  rr) -> Tuple:
    """Attempt each allowed rung once, in order, recording every attempt
    in the :class:`resilience.ResilienceReport` ``rr``.  Returns the
    first successful rung's ``(call, report)``; a failure warns and
    demotes to the next rung; raises :class:`resilience.LadderError`
    when every rung is exhausted.  The happy path costs one ``try``
    around the lowering call that already existed."""
    last: Optional[BaseException] = None
    for ri, rung in enumerate(rungs):
        thunk = make_thunk(rung)
        t0 = time.perf_counter()
        try:
            RZ.check(f"compile:{rung}")
            res = thunk()
        except Exception as e:  # any lowering failure demotes
            last = e
            rr.attempts.append(RZ.Attempt(
                rung, False, time.perf_counter() - t0,
                error=f"{type(e).__name__}: {e}"))
            if ri + 1 < len(rungs):
                RZ.METRICS.demotions += 1
                warnings.warn(
                    f"compile ladder: rung {rung!r} failed "
                    f"({rr.attempts[-1].error}); demoting to "
                    f"{rungs[ri + 1]!r}", RuntimeWarning, stacklevel=3)
            continue
        rr.attempts.append(RZ.Attempt(rung, True, time.perf_counter() - t0))
        rr.rung = rung
        return res
    RZ.METRICS.ladder_failures += 1
    raise RZ.LadderError(
        f"every allowed ladder rung failed ({rr.summary()}); "
        f"last error: {last}", rr)


def _measure_harness(graph: Graph,
                     dim_candidates: Dict[str, Sequence[int]], *,
                     options: CompileOptions, profile,
                     cache: KernelCache,
                     stabilize: bool = False) -> Callable:
    """The ``measure`` callback ``selection.autotune(objective=
    "measured")`` calls for each top-K survivor: compile the candidate
    through this same driver (so the in-process kernel cache absorbs
    repeats) and time it end-to-end on synthetic inputs.

    Every candidate runs the SAME total problem: per dim the total
    extent is a base block extent (the caller's ``blocks``, else 8;
    1 for stack dims) times the lcm of the candidate counts, and each
    candidate's block extent is ``total // count`` — varying the block
    *count* at fixed problem size, which is the choice the paper's
    selector owns.  Measurements are memoized process-wide
    (``timing.measured``) keyed by (fingerprint, dims, backend, device,
    totals), so re-sweeps never re-time a configuration."""
    from repro.core import timing as T
    o = options
    repeats = o.measure_repeats
    blocks = o.blocks_dict
    sd = T.stack_dims(graph)
    base = {d: (1 if d in sd else (blocks or {}).get(d, 8))
            for d in dim_candidates}
    total = {d: base[d] * lcm(*{int(c) for c in dim_candidates[d]})
             for d in dim_candidates}
    dev = CAL.device_kind()
    fp = graph.fingerprint()
    kernels: Dict[Tuple, CompiledKernel] = {}

    def measure(sel) -> float:
        cand_blocks = {d: total[d] // sel.dims[d] for d in sel.dims}
        bad = [d for d in sd
               if d in cand_blocks and cand_blocks[d] != 1]
        if bad:
            raise ValueError(
                f"stack dims {bad} need equal candidate counts (block "
                "size is pinned to 1)")
        dkey = tuple(sorted(sel.dims.items()))
        # everything the wall time depends on is in the memo key —
        # notably interpret mode (orders of magnitude slower) and the
        # repeat count
        mkey = (fp, dkey, o.backend, dev, tuple(sorted(total.items())),
                o.jit, o.fused, o.interpret, repeats, o.group, stabilize)

        def thunk() -> float:
            cand = o.replace(
                blocks=(cand_blocks if o.backend == "pallas"
                        else o.blocks),
                stabilize=stabilize, autotune="analytic",
                profile=profile)
            kern = compile(graph, dict(sel.dims), options=cand,
                           cache=cache)
            kernels[dkey] = kern
            inputs = T.synth_inputs(graph, sel.dims, cand_blocks)
            return T.time_callable(kern, inputs, warmup=1,
                                   repeats=repeats).median_s

        return T.measured(mkey, thunk)

    measure.kernels = kernels
    return measure


def compile(graph: Graph, dims: Optional[Dict[str, int]] = None, *,
            options: Optional[CompileOptions] = None,
            dim_candidates: Optional[Dict[str, Sequence[int]]] = None,
            cache: Optional[KernelCache] = None,
            name: Optional[str] = None,
            **kwargs) -> CompiledKernel:
    """Compile a block program into an executing, cached kernel.

    How the program compiles is described by ``options``, a frozen
    hashable :class:`CompileOptions` (``backend``, ``blocks``,
    ``stabilize``, ``autotune``, ``group``, ...).  The historical flat
    keyword form — ``compile(g, dims, backend="pallas", blocks=...)`` —
    is kept as a back-compat shim that builds a ``CompileOptions``
    internally; it is **deprecated** and new call sites should pass
    ``options=`` (passing both forms at once is a ``TypeError``).  The
    options hash directly into the kernel-cache key
    (``CompileOptions.cache_opts``), so equal options can never compile
    twice and unequal options can never alias.

    Either ``dims`` (fixed block counts -> ``selection.select``) or
    ``dim_candidates`` (a per-dim sweep -> ``selection.autotune``, which
    also picks the dims) must be given.  ``fused=False`` skips the fusion
    algorithm — the unfused Table-2 program compiles as-is; that is the
    benchmark baseline.  ``jit`` (jax backend) additionally accepts
    ``"per-op"``: each top-level operator is jitted separately and
    dispatched from python, the launch-per-operator unfused baseline.

    ``stabilize`` controls the graph-level numerical-safety rewrite
    (``numerics.stabilize``): top-level ``exp`` producers become
    significand/exponent pairs with running-max rescaled serial carries
    (online softmax), so attention stays finite at any logit magnitude.
    ``None`` (the default) auto-enables it exactly when the program
    contains a block-typed top-level ``exp``
    (``numerics.needs_stabilization``) — attention programs get it,
    exp-free programs compile unchanged.  The flag is part of the cache
    key: stabilized and raw kernels never alias.

    ``group`` (pallas backend) controls region-group megakernel
    lowering: by default compatible regions of the selected snapshot
    share one multi-stage ``pallas_call`` with cross-region values held
    in VMEM (``regions.group_plan``, gated by the
    ``$REPRO_VMEM_BUDGET_BYTES`` budget); ``group=False`` keeps the
    one-kernel-per-region lowering.  When grouping is on, snapshot
    selection also ranks by the grouped residency-aware objective
    (``selection.objective_cost(group=True)`` — resident edges free,
    one launch per group) instead of the paper's all-edges-global sum,
    so what is picked is what is cheapest to actually run.

    ``autotune="measured"`` (with ``dim_candidates``) closes the
    predict -> run -> measure loop: the calibrated analytic model prunes
    the sweep, the ``top_k`` cheapest distinct candidates are compiled
    and *timed* (median of ``measure_repeats`` fenced calls on synthetic
    inputs at a fixed total problem size), and the wall-clock winner is
    what lowers, caches, and re-loads.  ``profile`` overrides the
    calibration profile; by default the measured path loads the one
    fitted for this (backend, device) from the cache dir if a
    calibration run saved one — ``benchmarks/run.py --only pipeline``
    fits a ``backend="pallas"`` profile from per-region timings; other
    backends keep the default constants until calibrated (see
    ``core/calibrate.py``).  The analytic path always keeps the
    deterministic defaults.

    ``name`` (pallas backend) names the emitted kernels by kind
    (``codegen_pallas.emit_program``), so that a profiler trace shows
    which kernel ran.  It is no part of the cache key: a program is
    served from the cache under the name it was first compiled with.
    """
    if options is None:
        try:
            options = CompileOptions(**kwargs) if kwargs else DEFAULT_OPTIONS
        except TypeError as e:
            raise TypeError(f"pipeline.compile: {e}") from None
    elif kwargs:
        raise TypeError(
            "pipeline.compile: pass either options=CompileOptions(...) or "
            f"the flat keyword form, not both (extra: {sorted(kwargs)})")
    o = options
    backend, item_bytes, fused = o.backend, o.item_bytes_dict, o.fused
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; one of {BACKENDS}")
    if dims is None and dim_candidates is None:
        raise ValueError("pass dims= (fixed) or dim_candidates= (autotune)")
    if o.autotune not in AUTOTUNE_OBJECTIVES:
        raise ValueError(f"unknown autotune objective {o.autotune!r}; "
                         f"one of {AUTOTUNE_OBJECTIVES}")
    if o.autotune == "measured" and dim_candidates is None:
        raise ValueError("autotune='measured' needs dim_candidates=")
    cache = cache if cache is not None else default_cache()
    profile = o.profile
    if profile is None and o.autotune == "measured":
        # the measured path runs under the calibrated cost model fitted
        # for this backend+device (default constants if none saved)
        profile = CAL.load_or_default(cache.root, backend=backend,
                                      device_kind=CAL.device_kind())

    # default: stabilize exactly the programs that need it (block-typed
    # top-level exp, i.e. softmax-bearing programs like attention)
    stab = (NU.needs_stabilization(graph) if o.stabilize is None
            else bool(o.stabilize))

    vmem_budget = None
    if backend == "pallas":
        from repro.core import regions as REG
        from repro.core.codegen_pallas import resolve_interpret
        o = o.replace(interpret=resolve_interpret(o.interpret))
        if o.group:
            vmem_budget = REG.vmem_budget()
    blocks, interpret, jit, group = o.blocks_dict, o.interpret, o.jit, o.group

    # autotune keys embed the full candidate sweep, so two sweeps over the
    # same dim names but different candidate sets never collide
    key_dims = (dims if dims is not None
                else {k: tuple(v) for k, v in dim_candidates.items()})
    # every option that changes the emitted kernel or the selection plan
    # is part of the key, else a later compile is served a stale kernel
    # (CompileOptions.cache_opts is the single source of truth)
    opts = o.cache_opts(stabilized=stab,
                        autotuned=dim_candidates is not None,
                        profile=profile, vmem_budget=vmem_budget)
    key = CacheKey.make(graph.fingerprint(), backend, key_dims, blocks,
                        fused, opts)
    hit = cache.get_kernel(key)
    if hit is not None:
        return replace(hit, cache_hit="memory")

    plan, selected_graph = cache.get_plan(key)
    snaps: Optional[List[Graph]] = None
    pplan = None  # shared region partition (pallas cache-miss path)
    gplan = None  # shared region grouping (costing + lowering)
    plan_err = None  # RegionError text when the partitioner couldn't split
    timings = None
    measure = None
    # the pallas grouped lowering runs the grouped megakernel schedule,
    # so its snapshots are ranked by the residency-aware grouped
    # objective (sum of group costs); every other backend runs the
    # whole program as one unit and keeps the paper's global objective
    sel_group = bool(group) and backend == "pallas"
    if plan is None:
        # -- the full pipeline: fuse -> select/autotune --------------------
        if fused:
            trace = FusionTrace()
            snaps = fuse(graph, trace)
        else:
            snaps = [graph.clone()]
        # stabilization rewrites every snapshot (and the unfused base
        # used for init_cost) BEFORE selection, so the cost model ranks
        # the graphs that will actually lower — exponent-vector edges
        # and rescale work included
        base = graph
        if stab:
            snaps = [NU.stabilize(s) for s in snaps]
            base = NU.stabilize(graph)
        if dim_candidates is not None:
            if o.autotune == "measured":
                measure = _measure_harness(
                    graph, dim_candidates, options=o, profile=profile,
                    cache=cache, stabilize=stab)
                sel = SEL.autotune(base, dim_candidates, item_bytes,
                                   snapshots=snaps, objective="measured",
                                   profile=profile, measure=measure,
                                   top_k=o.top_k, group=sel_group,
                                   blocks=blocks)
                timings = sel.timings
            else:
                sel = SEL.autotune(base, dim_candidates, item_bytes,
                                   snapshots=snaps, profile=profile,
                                   group=sel_group, blocks=blocks)
        else:
            sel = SEL.select(base, dims, item_bytes, snapshots=snaps,
                             profile=profile, group=sel_group,
                             blocks=blocks)
        selected_graph = snaps[sel.snapshot_index]
        # residency-aware per-kernel traffic attribution of the snapshot
        # that will run (pallas packs its regions into megakernel
        # groups; the same grouping is reused by the lowering below)
        rcosts = kids = None
        launches = resident = None
        if backend == "pallas" and blocks is not None:
            pplan, plan_err = _region_plan(selected_graph)
            gplan = _grouped_plan(pplan, sel.dims, blocks, group)
            if gplan is not None:
                rcosts = SEL.region_costs(selected_graph, sel.dims,
                                          item_bytes, plan=gplan,
                                          profile=profile)
                kids = tuple(grp.gid for grp in gplan.groups)
                launches = gplan.n_launches
                resident = gplan.n_resident_edges
        # the unfused program priced under the SAME objective as the
        # winner, so predicted_traffic_reduction compares like with like
        init_cost = SEL.objective_cost(base, sel.dims, item_bytes,
                                       profile, group=sel_group,
                                       blocks=blocks)
        plan = CachePlan(sel.snapshot_index, sel.dims, sel.cost,
                         sel.costs, init_cost,
                         region_costs=rcosts, measured_s=sel.measured_s,
                         kernel_ids=kids, launches=launches,
                         resident_edges=resident, stabilized=stab)
        cache.put_plan(key, plan, selected_graph)
        cache_hit = None
    else:
        cache_hit = "disk"
        if selected_graph is None:
            # plan-only disk entry (un-picklable graph): re-fuse and
            # re-apply the same deterministic stabilization pass so
            # snapshot_index addresses the graph the plan described
            snaps = fuse(graph) if fused else [graph.clone()]
            if stab:
                snaps = [NU.stabilize(s) for s in snaps]
            selected_graph = snaps[plan.snapshot_index]

    use_dims = plan.dims

    # -- backend lowering: the selected snapshot, nothing else --------------
    # the measured sweep already compiled its candidates through this
    # driver; if the winner's kernel is lowering-identical to what we
    # would emit (same backend, and for pallas the same block extents),
    # reuse it instead of recompiling the same plan
    policy = o._policy()
    start = RZ.start_rung(backend, bool(group))
    rr = RZ.ResilienceReport(requested=start, plan_error=plan_err)
    fn = report = None
    if measure is not None:
        cand = measure.kernels.get(tuple(sorted(use_dims.items())))
        if cand is not None and (
                backend != "pallas"
                or cand.blocks == (dict(blocks) if blocks else None)):
            fn, report = cand._fn, cand.lowering_report
            # the sweep compiled it through this driver; adopt its
            # provenance instead of claiming a fresh zero-cost attempt
            rr = cand.resilience_report or rr
    if fn is None:
        # configuration errors are the caller's, not the ladder's: raise
        # before any rung runs instead of demoting past them
        if backend == "pallas":
            if blocks is None:
                raise ValueError(
                    "backend='pallas' needs per-dim block sizes: pass "
                    "blocks=")
            missing = [d for d in use_dims if d not in blocks]
            if missing:
                raise ValueError(
                    f"blocks missing sizes for dims {missing}")
        fn, report = _ladder_lower(
            RZ.rungs_from(start, policy.max_rung),
            functools.partial(_rung_thunk, g=selected_graph,
                              dims=use_dims, blocks=blocks,
                              interpret=interpret, jit=jit, pplan=pplan,
                              gplan=gplan, group=group, name=name),
            rr)
    # thread the partitioner's RegionError (or emit_program's own
    # whole-program fallback, on the disk-hit path where the driver
    # never partitioned) through both provenance records
    if report is not None and report.plan_error is None and plan_err:
        report.plan_error = plan_err
    if report is not None and report.plan_error and not rr.plan_error:
        rr.plan_error = report.plan_error

    # emission may diverge from the planned grouping (a group the
    # emitter cannot express degrades to per-region kernels): the
    # per-kernel cost provenance must describe what actually runs, or
    # costs would claim residency savings the fallback never realized
    # and id-based time pairing would silently drop kernels
    if backend == "pallas" and report is not None:
        actual = getattr(getattr(fn, "raw_program", None),
                         "emitted_kernels", None)
        if (actual is not None and plan.kernel_ids is not None
                and tuple(gid for gid, _ in actual) != plan.kernel_ids):
            rcosts = []
            for gid, unit in actual:
                if hasattr(unit, "members"):  # a whole RegionGroup
                    rcosts.append(SEL.group_cost(unit, use_dims,
                                                 item_bytes, profile))
                else:  # a single RegionSpec (degraded / singleton)
                    rcosts.append(SEL.snapshot_cost(unit.graph, use_dims,
                                                    item_bytes, profile))
            plan = replace(plan, region_costs=tuple(rcosts),
                           kernel_ids=tuple(g for g, _ in actual),
                           launches=report.launches,
                           resident_edges=report.resident_edges)
            cache.put_plan(key, plan, selected_graph)

    in_info, out_info = _io_info(selected_graph)
    kern = CompiledKernel(
        key=key, backend=backend, graph=selected_graph, dims=dict(use_dims),
        blocks=dict(blocks) if blocks else None,
        snapshot_index=plan.snapshot_index, cost=plan.cost,
        initial_cost=plan.initial_cost, cache_hit=cache_hit,
        stabilized=stab,
        in_names=[n for n, _ in in_info],
        out_names=[n for n, _ in out_info], _fn=fn,
        lowering_report=report, region_costs=plan.region_costs,
        kernel_ids=plan.kernel_ids,
        measured_s=plan.measured_s, autotune_timings=timings,
        resilience_report=rr)
    cache.put_kernel(key, kern)
    return kern
