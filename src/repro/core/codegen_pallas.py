"""Emit Pallas TPU kernels from *any* fusion snapshot.

The lowering is region-based (``core/regions.py``): the snapshot is
partitioned into a DAG of spine regions — each a nest of parallel maps
(-> pallas grid dimensions) around at most one accumulating node (a
serial map or a reduce -> the trailing sequential grid dimension with
f32 VMEM scratch carries) — the regions are packed into megakernel
*groups* (``regions.group_plan``: compatible parallel spines merge
under a VMEM budget), and ``emit_program`` emits one multi-stage
``pallas_call`` per group.  Stages run in sequence inside the kernel
body with their off-grid dims evaluated over whole-VMEM-resident data;
cross-region values whose producer and consumers share a group are
kernel-local VMEM carries, and only values that cross a *group*
boundary spill to merged global arrays between kernels (with dying
intermediates donated via ``input_output_aliases``).
The fully fused snapshots still lower to exactly one mega-kernel (the
paper's Example 1 epilogue == ``kernels/flash_attention.py`` modulo the
online-softmax rescale); partially fused snapshots and multi-output
programs lower to the multi-kernel schedule their traffic cost already
described, instead of raising ``"expected a single-map-spine"``.

Layout convention (program boundary and inter-region values alike): a
value typed ``block[A,B]`` is one merged array; leading list dims beyond
the item rank are plain stack axes of extent ``dims[d]``, the remaining
list dims split the item's axes in order — with the *actual* per-axis
item extents, which for intermediates (e.g. matmul partials
``block[M,N,K]``) need not equal ``blocks[d]``.  Item shapes are
propagated region-to-region via ``pipeline/packing.py`` helpers.  Dims
on a region's grid are tiled by BlockSpecs; other dims are
whole-resident in VMEM and in-kernel loops slice them.
"""

from __future__ import annotations

import warnings
from dataclasses import dataclass, field, replace
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import ops as O
from repro.core import regions as R
from repro.core.blocks import item_shape as infer_item_shape
from repro.core.blocks import merged_shape
from repro.core.graph import (FuncNode, Graph, InputNode, MapNode,
                              OutputNode, Ref, ReduceNode, VType)
from repro.core.regions import ProgramPlan, RegionError, RegionSpec


# ---------------------------------------------------------------------------
# Reports: what lowered, how, and what (if anything) fell back
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class RegionReport:
    label: str
    grid_dims: Tuple[str, ...]
    red_dim: Optional[str]
    n_outputs: int
    fallback: Optional[str] = None  # reason, when not lowered to Pallas
    group: str = ""                 # id of the kernel serving this region


@dataclass(frozen=True)
class KernelRun:
    """One emitted ``pallas_call``: the unit the executor launches.  The
    timing harness pairs each kernel's wall time with the per-kernel
    cost attribution by ``gid``, never by position."""

    gid: str
    label: str
    in_refs: Tuple[Ref, ...]
    out_refs: Tuple[Ref, ...]


@dataclass
class LoweringReport:
    """Provenance of one ``emit_program`` call: every region emitted,
    every fallback taken (which must be zero for in-repo programs), how
    many kernels actually launch (grouped regions share one), and how
    many cross-region values stayed VMEM-resident instead of
    round-tripping through global memory."""

    regions: List[RegionReport] = field(default_factory=list)
    launches: int = 0
    resident_edges: int = 0
    # the RegionError that made partitioning fall back to one
    # whole-program jax region (None when the partitioner succeeded) —
    # recorded so check_regression.py and the serve warmup fallback
    # checks can see the demotion instead of a silent except
    plan_error: Optional[str] = None

    @property
    def n_regions(self) -> int:
        return len(self.regions)

    @property
    def fallbacks(self) -> int:
        return sum(1 for r in self.regions if r.fallback is not None)

    def summary(self) -> str:
        parts = []
        for r in self.regions:
            grid = ",".join(r.grid_dims)
            tail = f"+{r.red_dim}*" if r.red_dim else ""
            note = f" FALLBACK({r.fallback})" if r.fallback else ""
            tag = f"@{r.group}" if r.group else ""
            parts.append(f"{r.label}[{grid}{tail}]{tag}{note}")
        return (f"{self.n_regions} regions in {self.launches} kernels "
                f"({self.resident_edges} resident edges): "
                + "; ".join(parts))


def plan(g: Graph) -> ProgramPlan:
    """Partition ``g`` into its Pallas region DAG (no codegen)."""
    return R.plan_program(g)


def resolve_interpret(interpret) -> bool:
    """``"auto"``/``None`` -> interpret everywhere except a real TPU
    backend.  Single source of the policy for emit and pipeline.compile."""
    if interpret in (None, "auto"):
        return jax.default_backend() != "tpu"
    return bool(interpret)


# ---------------------------------------------------------------------------
# Merged-layout helpers (actual item extents, not blocks[d])
# ---------------------------------------------------------------------------

def _axes(vt: VType, item_shape: Sequence[int]):
    """Per merged axis: ``(dim_or_None, per_block_extent)``.  Leading list
    dims beyond the item rank are stack axes (extent 1 per block); the
    next ``len(vt.dims) - lead`` item axes are split by the remaining
    dims; trailing item axes are untouched."""
    lead = max(len(vt.dims) - len(item_shape), 0)
    k = len(vt.dims) - lead
    axes = [(d, 1) for d in vt.dims[:lead]]
    axes += [(vt.dims[lead + j], item_shape[j]) for j in range(k)]
    axes += [(None, item_shape[j]) for j in range(k, len(item_shape))]
    return axes


def _block_shape(vt, item_shape, dims, grid_axes) -> Tuple[int, ...]:
    return tuple(b if d in grid_axes else (b * dims[d] if d else b)
                 for d, b in _axes(vt, item_shape))


def _row(shape: Sequence[int]) -> Tuple[int, ...]:
    """The shape a merged array has at the ``pallas_call`` boundary.
    Mosaic tiles a rank-1 operand by 128 lanes while XLA may tile it by
    its whole length, and refuses the mismatch; so rank-1 arrays (the
    position vectors, per-row statistics) cross as one ``(1, n)`` row,
    and the TPU's (8, 128)-or-whole block rule applies to them as to
    any matrix."""
    return (1, *shape) if len(shape) == 1 else tuple(shape)


def _block_spec(vt, item_shape, dims, grid_axes) -> pl.BlockSpec:
    axes = _axes(vt, item_shape)
    shape = _block_shape(vt, item_shape, dims, grid_axes)

    def index_map(*gids, axes=tuple(axes)):
        pos = dict(zip(grid_axes, gids))
        idx = tuple(pos[d] if d in grid_axes else 0 for d, _ in axes)
        return (0, *idx) if len(idx) == 1 else idx

    return pl.BlockSpec(_row(shape), index_map)


def _call_rows(kernel, merged_inputs, *, grid, in_specs, out_specs,
               out_full, scratch, donate, in_layouts, out_layouts,
               interpret, name=None):
    """``pallas_call`` over merged arrays, with rank-1 operands passed
    as ``(1, n)`` rows (:func:`_row`) and returned in their merged
    shapes.  ``name`` names the kernel (the Mosaic custom call, and so
    its op in a profiler trace); it changes nothing else."""
    dtype = (jnp.result_type(*merged_inputs) if merged_inputs
             else jnp.float32)
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[jax.ShapeDtypeStruct(_row(s), dtype) for s in out_full],
        scratch_shapes=scratch,
        input_output_aliases=_alias_map(merged_inputs, out_full, dtype,
                                        donate, in_layouts, out_layouts),
        interpret=interpret,
        name=name,
    )(*[a.reshape(_row(a.shape)) for a in merged_inputs])
    return tuple(o.reshape(s) for o, s in zip(outs, out_full))


def _split_whole(arr, vt_dims, dims, grid_axes, axis=0):
    """Split non-grid list dims of a kernel block into nested python
    lists (the IR's value layout)."""
    if not vt_dims:
        return arr
    d = vt_dims[0]
    if d in grid_axes:
        return _split_whole(arr, vt_dims[1:], dims, grid_axes, axis + 1)
    n = dims[d]
    size = arr.shape[axis] // n
    parts = []
    for i in range(n):
        idx = [slice(None)] * arr.ndim
        idx[axis] = slice(i * size, (i + 1) * size)
        parts.append(_split_whole(arr[tuple(idx)], vt_dims[1:], dims,
                                  grid_axes, axis + 1))
    return parts


def _split_value(arr, vt: VType, item_shape, dims, grid_axes):
    """Kernel block -> the IR's nested-list value layout: leading stack
    axes are squeezed when grid-selected or unrolled into in-kernel
    lists; the remaining list dims slice item axes."""
    lead = max(len(vt.dims) - len(item_shape), 0)

    def rec(a, vt_dims, lead):
        if lead:
            d = vt_dims[0]
            if d in grid_axes:
                return rec(a[0], vt_dims[1:], lead - 1)
            return [rec(a[i], vt_dims[1:], lead - 1)
                    for i in range(dims[d])]
        return _split_whole(a, list(vt_dims), dims, grid_axes)

    return rec(arr, vt.dims, lead)


def _merge_value(val, vt: VType, item_rank: int, dims, grid_axes):
    """Inverse of :func:`_split_value` for an output value: stack
    off-grid lead lists, concatenate off-grid split lists along their
    item axis.  Grid-selected dims contribute nothing (the BlockSpec
    positions the block); the caller reshapes to the out-ref block."""
    lead = max(len(vt.dims) - item_rank, 0)

    def rec(v, ds, lead, axis):
        if not ds:
            return v
        d = ds[0]
        if lead:
            if d in grid_axes:
                return rec(v, ds[1:], lead - 1, axis)
            return jnp.stack([rec(x, ds[1:], lead - 1, axis) for x in v],
                             axis=0)
        if d in grid_axes:
            return rec(v, ds[1:], 0, axis + 1)
        return jnp.concatenate([rec(x, ds[1:], 0, axis + 1) for x in v],
                               axis=axis)

    return rec(val, vt.dims, lead, 0)


def _first_item(v):
    while isinstance(v, list):
        v = v[0]
    return v


# ---------------------------------------------------------------------------
# In-kernel evaluation
# ---------------------------------------------------------------------------

def _eval_inner(g: Graph, env: Dict, dims: Dict[str, int],
                grid_axes: frozenset = frozenset()) -> List[Any]:
    """In-kernel evaluation; list values are python lists of VMEM slices,
    serial maps unroll statically.  A map over a dim in ``grid_axes``
    (the grouped-kernel path: the pallas grid already selected that
    block) runs a single iteration with mapped values passed through
    unsplit and outputs left unwrapped."""
    out: Dict[int, Any] = {}
    for nid in g.topo():
        node = g.nodes[nid]
        if isinstance(node, InputNode):
            continue
        ins = [env[(e.src, e.sp)] for e in g.in_edges(nid)]
        if isinstance(node, OutputNode):
            out[nid] = ins[0]
        elif isinstance(node, FuncNode):
            env[(nid, 0)] = node.op.apply(jnp, *ins)
        elif isinstance(node, ReduceNode):
            acc = ins[0][0]
            for item in ins[0][1:]:
                acc = (jnp.maximum(acc, item)
                       if node.op == O.REDUCE_MAX else acc + item)
            env[(nid, 0)] = acc
        elif isinstance(node, MapNode) and node.dim in grid_axes:
            if node.serial:
                raise RegionError(
                    f"serial map[{node.dim}] over a grid-selected dim")
            ienv: Dict = {}
            for p, e in enumerate(g.in_edges(nid)):
                ienv[(node.inner.input_ids[p], 0)] = env[(e.src, e.sp)]
            res = _eval_inner(node.inner, ienv, dims, grid_axes)
            for pp in range(node.n_out()):
                env[(nid, pp)] = res[pp]
        elif isinstance(node, MapNode):
            n = dims[node.dim]
            collected: List[Any] = [[] if r is None else None
                                    for r in node.reduced]
            for i in range(n):
                ienv: Dict = {}
                for p, e in enumerate(g.in_edges(nid)):
                    v = env[(e.src, e.sp)]
                    if node.mapped[p]:
                        v = v[i]
                    ienv[(node.inner.input_ids[p], 0)] = v
                res = _eval_inner(node.inner, ienv, dims, grid_axes)
                # handles plain "+" and the coupled "max"/"+@k" carries
                # of stabilized programs alike (static unroll)
                O.serial_accum_step(collected, res, node.reduced, jnp)
            for pp in range(node.n_out()):
                env[(nid, pp)] = collected[pp]
        else:
            raise TypeError(node)
    return [out[oid] for oid in g.output_ids]


def _eval_funcs(g: Graph, env: Dict, skip: set, dims) -> Dict:
    """Evaluate every FuncNode of one spine level except ``skip``
    (the spine map / the accumulator and its epilogue)."""
    env = dict(env)
    for nid in g.topo():
        node = g.nodes[nid]
        if isinstance(node, FuncNode) and nid not in skip:
            ins = [env[(e.src, e.sp)] for e in g.in_edges(nid)]
            env[(nid, 0)] = node.op.apply(jnp, *ins)
    return env


def _downstream(g: Graph, nid: int) -> set:
    seen = {nid}
    frontier = [nid]
    while frontier:
        n = frontier.pop()
        for e in g.out_edges(n):
            if e.dst not in seen:
                seen.add(e.dst)
                frontier.append(e.dst)
    return seen


# ---------------------------------------------------------------------------
# Region lowering
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class _OutSlot:
    kind: str            # "step" (written every serial step) | "final"
    level: int           # spine level index (len(levels) == base level)
    ref: Ref             # value ref at that level (final slots)
    step_port: int = -1  # acc list-port index (step slots)
    vt: VType = VType()


def _region_levels(spec: RegionSpec):
    """(parallel levels [(graph, map id)], base graph, acc id or None)."""
    rg = spec.graph
    root = [n for n in rg.op_nodes()][0]
    levels: List[Tuple[Graph, int]] = []
    g_lvl, node = rg, rg.nodes[root]
    nid = root
    while isinstance(node, MapNode) and not node.serial:
        gi = node.inner
        pars = [n for n in sorted(gi.op_nodes())
                if isinstance(gi.nodes[n], MapNode)
                and not gi.nodes[n].serial]
        accs = [n for n in sorted(gi.op_nodes())
                if (isinstance(gi.nodes[n], MapNode)
                    and gi.nodes[n].serial)
                or isinstance(gi.nodes[n], ReduceNode)]
        levels.append((g_lvl, nid))
        if len(pars) == 1 and not accs:
            g_lvl, nid, node = gi, pars[0], gi.nodes[pars[0]]
            continue
        if pars:
            raise RegionError(f"not a spine region: {spec.label}")
        return levels, gi, (accs[0] if accs else None)
    if isinstance(node, (MapNode, ReduceNode)):  # serial root / reduce root
        return levels, g_lvl, nid
    return levels, g_lvl, None  # func root


def _classify_outputs(spec: RegionSpec, levels, base_g, acc_id,
                      red_dim, types) -> List[_OutSlot]:
    rg = spec.graph
    slots: List[_OutSlot] = []
    for oid in rg.output_ids:
        e = rg.in_edge(oid, 0)
        ref: Ref = (e.src, e.sp)
        lvl = 0
        while lvl < len(levels) and ref[0] == levels[lvl][1]:
            mnode: MapNode = levels[lvl][0].nodes[levels[lvl][1]]
            inner = mnode.inner
            ie = inner.in_edge(inner.output_ids[ref[1]], 0)
            ref = (ie.src, ie.sp)
            lvl += 1
        vt = types[(e.src, e.sp)]
        if (acc_id is not None and ref[0] == acc_id
                and isinstance(base_g.nodes[acc_id], MapNode)
                and base_g.nodes[acc_id].reduced[ref[1]] is None):
            slots.append(_OutSlot("step", lvl, ref, ref[1], vt))
        else:
            slots.append(_OutSlot("final", lvl, ref, -1, vt))
    return slots


def _alias_map(merged_inputs, out_shapes, dtype, donate,
               in_layouts=None, out_layouts=None):
    """``input_output_aliases`` for one ``pallas_call``: donate each
    dying merged intermediate (``donate[i]`` True — its last consumer is
    this kernel and it is not a program value) to the first unclaimed
    output of identical shape, dtype, AND block layout
    (``(vt.dims, item_shape)`` — which fixes the BlockSpec/index map).
    The layout match matters for correctness: grid steps run in
    sequence, so an aliased pair with identical index maps means step
    *i* overwrites exactly the block it just read, while mismatched
    index maps could clobber blocks a later step still reads.  XLA
    copies when an aliased input is still live (e.g. the timing harness
    re-calling a kernel), so donation never corrupts caller data."""
    if not donate:
        return {}
    aliases: Dict[int, int] = {}
    used: set = set()
    for i, ok in enumerate(donate):
        if not ok or merged_inputs[i].dtype != dtype:
            continue
        for j, s in enumerate(out_shapes):
            if (j not in used
                    and tuple(merged_inputs[i].shape) == tuple(s)
                    and (in_layouts is None or out_layouts is None
                         or in_layouts[i] == out_layouts[j])):
                aliases[i] = j
                used.add(j)
                break
    return aliases


def emit_region(spec: RegionSpec, dims: Dict[str, int],
                in_item_shapes: List[Tuple[int, ...]], interpret: bool,
                donate: Optional[Sequence[bool]] = None,
                name: Optional[str] = None):
    """Lower one region to a single multi-output ``pallas_call``.

    Returns ``(fn, out_item_shapes, report)`` where ``fn`` maps merged
    input arrays to a tuple of merged output arrays.  ``donate[i]``
    marks input *i* as a dying intermediate whose buffer may be aliased
    to a same-shape output."""
    rg = spec.graph
    levels, base_g, acc_id = _region_levels(spec)
    red_dim = spec.red_dim
    grid_dims = list(spec.grid_dims)
    grid_axes = grid_dims + ([red_dim] if red_dim else [])
    for d in grid_axes:
        if d not in dims:
            raise RegionError(f"grid dim {d} missing from dims")

    in_types = [rg.nodes[i].vtype for i in rg.input_ids]
    types = rg.infer_types()
    acc_node = base_g.nodes[acc_id] if acc_id is not None else None
    if isinstance(acc_node, ReduceNode) and acc_node.op not in (
            O.REDUCE_ADD, O.REDUCE_MAX):
        raise RegionError(f"unsupported reduce {acc_node.op!r}")
    # reduced tags of the compressed accumulator list, and the port ->
    # accumulator-index map "+@k" tags resolve through
    if isinstance(acc_node, ReduceNode):
        acc_tags: List[Any] = [acc_node.op]
        acc_of_port: Dict[int, int] = {0: 0}
    elif acc_node is not None:
        acc_tags = [r for r in acc_node.reduced if r is not None]
        acc_of_port = {p: ai for ai, p in enumerate(
            p for p, r in enumerate(acc_node.reduced) if r is not None)}
        for r in acc_tags:
            if (r not in (O.REDUCE_ADD, O.REDUCE_MAX)
                    and O.rescaled_ref(r) is None):
                raise RegionError(f"unsupported reduced tag {r!r}")
    else:
        acc_tags, acc_of_port = [], {}
    epilogue_skip = (_downstream(base_g, acc_id)
                     if acc_id is not None else set())
    slots = _classify_outputs(spec, levels, base_g, acc_id, red_dim, types)

    def bind_values(values: Dict[int, Any]):
        """Walk the parallel levels, evaluating level funcs; returns the
        per-level envs plus the base-level env (pre-accumulator)."""
        envs: List[Dict] = []
        env = {(iid, 0): values[iid] for iid in rg.input_ids}
        for lg, mid in levels:
            env = _eval_funcs(lg, env, {mid}, dims)
            envs.append(env)
            mnode: MapNode = lg.nodes[mid]
            nxt = {}
            for p, e in enumerate(lg.in_edges(mid)):
                nxt[(mnode.inner.input_ids[p], 0)] = env[(e.src, e.sp)]
            env = nxt
        return envs, env

    def serial_step(values: Dict[int, Any]):
        """One accumulator step: (partials, {list port: step value})."""
        _, env = bind_values(values)
        env = _eval_funcs(base_g, env, epilogue_skip, dims)
        if isinstance(acc_node, ReduceNode):
            e = base_g.in_edge(acc_id, 0)
            return [env[(e.src, e.sp)]], {}
        senv: Dict = {}
        for p, e in enumerate(base_g.in_edges(acc_id)):
            senv[(acc_node.inner.input_ids[p], 0)] = env[(e.src, e.sp)]
        res = _eval_inner(acc_node.inner, senv, dims)
        partials = [res[p] for p, r in enumerate(acc_node.reduced)
                    if r is not None]
        steps = {p: res[p] for p, r in enumerate(acc_node.reduced)
                 if r is None}
        return partials, steps

    def final_envs(values: Dict[int, Any], acc_vals: List[Any]):
        envs, env = bind_values(values)
        if acc_id is not None:
            ai = 0
            if isinstance(acc_node, ReduceNode):
                env[(acc_id, 0)] = acc_vals[0]
            else:
                for p, r in enumerate(acc_node.reduced):
                    if r is not None:
                        env[(acc_id, p)] = acc_vals[ai]
                        ai += 1
        env = _eval_funcs(base_g, env, {acc_id} if acc_id is not None
                          else set(), dims)
        envs.append(env)
        return envs

    # -- abstract shape analysis (one invocation) ---------------------------
    abstract_ins = [
        jax.ShapeDtypeStruct(_block_shape(vt, ish, dims, grid_axes),
                             jnp.float32)
        for vt, ish in zip(in_types, in_item_shapes)]

    def abs_values(arrs):
        return {iid: _split_value(a, vt, ish, dims, grid_axes)
                for iid, a, vt, ish in zip(rg.input_ids, arrs, in_types,
                                           in_item_shapes)}

    n_acc = 0
    scratch: List[Any] = []
    if acc_id is not None:
        acc_shapes = jax.eval_shape(
            lambda *a: tuple(serial_step(abs_values(a))[0]), *abstract_ins)
        scratch = [pltpu.VMEM(a.shape, jnp.float32) for a in acc_shapes]
        n_acc = len(acc_shapes)

    def out_items(*arrs):
        values = abs_values(arrs)
        steps: Dict[int, Any] = {}
        if acc_id is not None:
            partials, steps = serial_step(values)
            envs = final_envs(values, list(partials))
        else:
            envs = final_envs(values, [])
        picked = []
        for s in slots:
            v = steps[s.step_port] if s.kind == "step" else envs[s.level][s.ref]
            picked.append(_first_item(v))
        return tuple(picked)

    out_item_abs = jax.eval_shape(out_items, *abstract_ins)
    out_item_shapes = [tuple(a.shape) for a in out_item_abs]
    out_full = [merged_shape(s.vt, ish, dims)
                for s, ish in zip(slots, out_item_shapes)]
    out_specs = [_block_spec(s.vt, ish, dims, grid_axes)
                 for s, ish in zip(slots, out_item_shapes)]
    in_specs = [_block_spec(vt, ish, dims, grid_axes)
                for vt, ish in zip(in_types, in_item_shapes)]

    n_in, n_out = len(rg.input_ids), len(slots)
    n_red = dims[red_dim] if red_dim else 0
    in_blocks = [a.shape for a in abstract_ins]

    def write(o_ref, slot, ish, v):
        merged = _merge_value(v, slot.vt, len(ish), dims, grid_axes)
        o_ref[...] = merged.reshape(o_ref.shape).astype(o_ref.dtype)

    def kernel(*refs):
        in_refs = refs[:n_in]
        out_refs = refs[n_in:n_in + n_out]
        acc_refs = refs[n_in + n_out:]
        values = {iid: _split_value(r[...].reshape(blk), vt, ish, dims,
                                    grid_axes)
                  for iid, r, blk, vt, ish in zip(rg.input_ids, in_refs,
                                                  in_blocks, in_types,
                                                  in_item_shapes)}
        if acc_id is None:
            envs = final_envs(values, [])
            for o_ref, slot, ish in zip(out_refs, slots, out_item_shapes):
                write(o_ref, slot, ish, envs[slot.level][slot.ref])
            return
        ri = pl.program_id(len(grid_dims))

        @pl.when(ri == 0)
        def _init():
            for a, tag in zip(acc_refs, acc_tags):
                a[...] = (jnp.full_like(a, -jnp.inf)
                          if tag == O.REDUCE_MAX else jnp.zeros_like(a))

        partials, steps = serial_step(values)
        vals = [p_val.astype(jnp.float32) for p_val in partials]
        # two-phase coupled update (see ops.serial_accum_step): read the
        # old running maxima before any scratch write, then advance every
        # accumulator — "+@k" ports rescale by exp(z_old-z_new) exactly as
        # in the online-softmax recurrence
        z_old: Dict[int, Any] = {}
        z_new: Dict[int, Any] = {}
        for ai, tag in enumerate(acc_tags):
            if tag == O.REDUCE_MAX:
                z_old[ai] = acc_refs[ai][...]
                z_new[ai] = jnp.maximum(z_old[ai], vals[ai])
        for ai, tag in enumerate(acc_tags):
            if tag == O.REDUCE_ADD:
                acc_refs[ai][...] += vals[ai]
            elif tag == O.REDUCE_MAX:
                acc_refs[ai][...] = z_new[ai]
            else:
                ak = acc_of_port[O.rescaled_ref(tag)]
                step = vals[ai] * O.bcast_to(
                    jnp, jnp.exp(vals[ak] - z_new[ak]), vals[ai])
                acc_refs[ai][...] = (
                    acc_refs[ai][...]
                    * O.bcast_to(jnp, jnp.exp(z_old[ak] - z_new[ak]),
                                 acc_refs[ai][...])
                    + step)
        for o_ref, slot, ish in zip(out_refs, slots, out_item_shapes):
            if slot.kind == "step":
                write(o_ref, slot, ish, steps[slot.step_port])

        @pl.when(ri == n_red - 1)
        def _done():
            envs = final_envs(values, [a[...] for a in acc_refs])
            for o_ref, slot, ish in zip(out_refs, slots, out_item_shapes):
                if slot.kind == "final":
                    write(o_ref, slot, ish, envs[slot.level][slot.ref])

    grid = tuple(dims[d] for d in grid_axes)

    in_layouts = [(vt.dims, tuple(ish))
                  for vt, ish in zip(in_types, in_item_shapes)]
    out_layouts = [(s.vt.dims, tuple(ish))
                   for s, ish in zip(slots, out_item_shapes)]

    def region_fn(*merged_inputs):
        return _call_rows(kernel, merged_inputs, grid=grid,
                          in_specs=in_specs, out_specs=out_specs,
                          out_full=out_full, scratch=scratch,
                          donate=donate, in_layouts=in_layouts,
                          out_layouts=out_layouts, interpret=interpret,
                          name=name)

    report = RegionReport(spec.label, tuple(grid_dims), red_dim, n_out)
    return region_fn, out_item_shapes, report


def emit_group(group, types: Dict[Ref, VType], dims: Dict[str, int],
               in_item_shapes: List[Tuple[int, ...]], interpret: bool,
               donate: Optional[Sequence[bool]] = None,
               name: Optional[str] = None):
    """Lower one region *group* to a single multi-stage ``pallas_call``.

    The kernel grid is the group's shared parallel spine; every member
    region runs in sequence inside the kernel body with its off-grid
    dims evaluated over whole-VMEM-resident data (serial spines unroll
    in-kernel), and every in-group cross-region value is carried as a
    kernel-local VMEM value — it never touches global memory.  Only the
    group's spilled ``out_refs`` are written out.

    Returns ``(fn, out_item_shapes, reports)`` with one
    :class:`RegionReport` per member."""
    grid_axes = list(group.grid_dims)
    gset = frozenset(grid_axes)
    for d in grid_axes:
        if d not in dims:
            raise RegionError(f"grid dim {d} missing from dims")
    in_types = [types[r] for r in group.in_refs]
    out_types = [types[r] for r in group.out_refs]

    def run_stages(values: Dict[Ref, Any]) -> Dict[Ref, Any]:
        env = dict(values)
        for spec in group.members:
            ienv = {}
            for iid, r in zip(spec.graph.input_ids, spec.in_refs):
                ienv[(iid, 0)] = env[r]
            res = _eval_inner(spec.graph, ienv, dims, gset)
            for r, v in zip(spec.out_refs, res):
                env[r] = v
        return env

    abstract_ins = [
        jax.ShapeDtypeStruct(_block_shape(vt, ish, dims, grid_axes),
                             jnp.float32)
        for vt, ish in zip(in_types, in_item_shapes)]

    def abs_values(arrs):
        return {r: _split_value(a, vt, ish, dims, grid_axes)
                for r, a, vt, ish in zip(group.in_refs, arrs, in_types,
                                         in_item_shapes)}

    def out_items(*arrs):
        env = run_stages(abs_values(arrs))
        return tuple(_first_item(env[r]) for r in group.out_refs)

    out_item_abs = jax.eval_shape(out_items, *abstract_ins)
    out_item_shapes = [tuple(a.shape) for a in out_item_abs]
    out_full = [merged_shape(vt, ish, dims)
                for vt, ish in zip(out_types, out_item_shapes)]
    out_specs = [_block_spec(vt, ish, dims, grid_axes)
                 for vt, ish in zip(out_types, out_item_shapes)]
    in_specs = [_block_spec(vt, ish, dims, grid_axes)
                for vt, ish in zip(in_types, in_item_shapes)]
    n_in, n_out = len(group.in_refs), len(group.out_refs)
    in_blocks = [a.shape for a in abstract_ins]

    def kernel(*refs):
        in_refs_, out_refs_ = refs[:n_in], refs[n_in:n_in + n_out]
        values = {r: _split_value(ref[...].reshape(blk), vt, ish, dims,
                                  grid_axes)
                  for r, ref, blk, vt, ish in zip(group.in_refs, in_refs_,
                                                  in_blocks, in_types,
                                                  in_item_shapes)}
        env = run_stages(values)
        for o_ref, r, vt, ish in zip(out_refs_, group.out_refs,
                                     out_types, out_item_shapes):
            merged = _merge_value(env[r], vt, len(ish), dims, grid_axes)
            o_ref[...] = merged.reshape(o_ref.shape).astype(o_ref.dtype)

    grid = tuple(dims[d] for d in grid_axes)

    in_layouts = [(vt.dims, tuple(ish))
                  for vt, ish in zip(in_types, in_item_shapes)]
    out_layouts = [(vt.dims, tuple(ish))
                   for vt, ish in zip(out_types, out_item_shapes)]

    def group_fn(*merged_inputs):
        return _call_rows(kernel, merged_inputs, grid=grid,
                          in_specs=in_specs, out_specs=out_specs,
                          out_full=out_full, scratch=(), donate=donate,
                          in_layouts=in_layouts, out_layouts=out_layouts,
                          interpret=interpret, name=name)

    reports = [RegionReport(spec.label, spec.grid_dims, spec.red_dim,
                            len(spec.out_refs), group=group.gid)
               for spec in group.members]
    return group_fn, out_item_shapes, reports


def _fallback_region(spec: RegionSpec, dims: Dict[str, int],
                     in_item_shapes, reason: str):
    """Region the Pallas emitter cannot express: lower it with the jax
    backend (vmap/scan) behind the same merged-array contract."""
    from repro.core.codegen_jax import compile_program
    from repro.pipeline import packing as P
    rg = spec.graph
    in_info = [(rg.nodes[i].name, rg.nodes[i].vtype)
               for i in rg.input_ids]
    out_types = P.output_types(rg)
    prog = compile_program(rg)

    def fn(*merged):
        stacked = [P.to_stacked(a, vt, dims)
                   for (_, vt), a in zip(in_info, merged)]
        outs = prog(*stacked)
        return tuple(P.from_stacked(o, vt, dims)
                     for vt, o in zip(out_types, outs))

    in_full = [merged_shape(vt, ish, dims)
               for (_, vt), ish in zip(in_info, in_item_shapes)]
    abs_out = jax.eval_shape(
        fn, *[jax.ShapeDtypeStruct(s, jnp.float32) for s in in_full])
    out_item_shapes = [infer_item_shape(a.shape, vt, dims)
                       for a, vt in zip(abs_out, out_types)]
    report = RegionReport(spec.label, tuple(spec.grid_dims), spec.red_dim,
                          len(out_types), fallback=reason)
    return fn, out_item_shapes, report


# ---------------------------------------------------------------------------
# Whole-program lowering
# ---------------------------------------------------------------------------

def emit_program(g: Graph, dims: Dict[str, int], blocks: Dict[str, int],
                 interpret="auto",
                 program_plan: Optional[ProgramPlan] = None,
                 grouped_plan=None, group: bool = True,
                 name: Optional[str] = None
                 ) -> Tuple[Callable[..., Tuple], LoweringReport]:
    """Lower every region of (the partition of) ``g``.

    Regions are first packed into megakernel groups
    (``regions.group_plan``, unless ``group=False``): one multi-stage
    ``pallas_call`` per group, with in-group cross-region values carried
    in VMEM.  Returns ``(fn, report)``: ``fn`` takes one merged array
    per program input and returns a tuple of merged arrays, one per
    program output; ``report`` records the regions emitted, the kernels
    launched (``report.launches``), the VMEM-resident edges, and any
    fallbacks taken (a region the Pallas emitter cannot express runs on
    the jax backend — zero for all in-repo programs, and pinned to zero
    by ``tests/test_lowering_coverage.py``).  Callers that already
    partitioned/grouped ``g`` (the driver shares one plan between
    lowering and per-kernel cost attribution) pass it via
    ``program_plan``/``grouped_plan``.  ``name`` names the emitted
    kernels by kind, with the group index appended where the program
    emits more than one (``rmsnorm_swiglu_0``, ``rmsnorm_swiglu_1``...),
    so a profiler trace tells them apart; it changes no numerics."""
    interpret = resolve_interpret(interpret)
    try:
        pp = program_plan if program_plan is not None else plan(g)
    except RegionError as err:
        # un-partitionable program (MiscNode, exotic pass-through): one
        # whole-program jax region, reported as a fallback
        whole = RegionSpec(-1, "program", (), None, g.clone(),
                           [(i, 0) for i in g.input_ids],
                           [(o, 0) for o in g.output_ids])
        in_items = [
            tuple(blocks[d] for d in vt.dims[vt.lead_dims:])
            for vt in (g.nodes[i].vtype for i in g.input_ids)]
        fn, _, rep = _fallback_region(whole, dims, in_items, str(err))
        fn.region_runners = [(KernelRun("g0:program", "program",
                                        tuple(whole.in_refs),
                                        tuple(whole.out_refs)), fn)]
        fn.input_refs = [(i, 0) for i in g.input_ids]
        fn.emitted_kernels = [("g0:program", whole)]
        return fn, LoweringReport([rep], launches=1,
                                  plan_error=str(err))
    gp = grouped_plan
    if gp is None:
        gp = (R.group_plan(pp, dims, blocks) if group
              else R.ungrouped_plan(pp))
    types = pp.graph.infer_types()
    report = LoweringReport()

    item_shapes: Dict[Ref, Tuple[int, ...]] = {}
    prog_in = set()
    for iid in pp.graph.input_ids:
        vt = pp.graph.nodes[iid].vtype
        for d in vt.dims[:vt.lead_dims]:
            if blocks.get(d, 1) != 1:
                raise ValueError(
                    f"stack dim {d} of {vt!r} needs block size 1, got "
                    f"{blocks[d]}")
        item_shapes[(iid, 0)] = tuple(blocks[d]
                                      for d in vt.dims[vt.lead_dims:])
        prog_in.add((iid, 0))
    prog_out = {(e.src, e.sp) for oid in pp.graph.output_ids
                for e in [pp.graph.in_edge(oid, 0)]}

    # a merged intermediate dies at its last consuming kernel: that
    # kernel may donate its buffer to a same-shape output
    last_use: Dict[Ref, int] = {}
    for gi, grp in enumerate(gp.groups):
        for r in grp.in_refs:
            last_use[r] = gi

    def donatable(refs: Sequence[Ref], gi: int) -> List[bool]:
        return [r not in prog_in and r not in prog_out
                and last_use.get(r) == gi for r in refs]

    lowered: List[Tuple[KernelRun, Callable]] = []
    # what each emitted kernel actually serves (a RegionGroup, or a
    # RegionSpec for singleton/degraded kernels) — the driver recomputes
    # per-kernel cost provenance from this when emission diverged from
    # the planned grouping
    emitted: List[Tuple[str, Any]] = []

    def kernel_name(gi: int) -> Optional[str]:
        if name is None or len(gp.groups) == 1:
            return name
        return f"{name}_{gi}"

    def lower_one(spec: RegionSpec, gid: str, gi: int) -> None:
        in_items = [item_shapes[r] for r in spec.in_refs]
        try:
            fn, out_items, rep = emit_region(
                spec, dims, in_items, interpret,
                donate=donatable(spec.in_refs, gi), name=kernel_name(gi))
        except (RegionError, NotImplementedError) as err:
            fn, out_items, rep = _fallback_region(spec, dims, in_items,
                                                  str(err))
        rep = replace(rep, group=gid)
        for ref, ish in zip(spec.out_refs, out_items):
            item_shapes[ref] = ish
        lowered.append((KernelRun(gid, rep.label, tuple(spec.in_refs),
                                  tuple(spec.out_refs)), fn))
        emitted.append((gid, spec))
        report.regions.append(rep)

    for gi, grp in enumerate(gp.groups):
        if len(grp.members) == 1:
            lower_one(grp.members[0], grp.gid, gi)
            continue
        try:
            in_items = [item_shapes[r] for r in grp.in_refs]
            fn, out_items, reps = emit_group(
                grp, types, dims, in_items, interpret,
                donate=donatable(grp.in_refs, gi), name=kernel_name(gi))
        except (RegionError, NotImplementedError) as err:
            # a group the emitter cannot express degrades to per-region
            # kernels (still Pallas when possible), never to one big
            # jax fallback
            warnings.warn(
                f"grouped lowering of {grp.gid} fell back to per-region "
                f"kernels ({err})", RuntimeWarning, stacklevel=2)
            for spec in grp.members:
                lower_one(spec, f"{grp.gid}.{spec.node}", gi)
            continue
        for ref, ish in zip(grp.out_refs, out_items):
            item_shapes[ref] = ish
        lowered.append((KernelRun(grp.gid, grp.label, tuple(grp.in_refs),
                                  tuple(grp.out_refs)), fn))
        emitted.append((grp.gid, grp))
        report.regions.extend(reps)
        report.resident_edges += len(grp.resident)
    report.launches = len(lowered)

    out_refs: List[Ref] = []
    for oid in pp.graph.output_ids:
        e = pp.graph.in_edge(oid, 0)
        out_refs.append((e.src, e.sp))

    def run(*merged_inputs):
        env: Dict[Ref, Any] = {
            (iid, 0): a
            for iid, a in zip(pp.graph.input_ids, merged_inputs)}
        for kr, fn in lowered:
            outs = fn(*[env[r] for r in kr.in_refs])
            for ref, o in zip(kr.out_refs, outs):
                env[ref] = o
        return tuple(env[r] for r in out_refs)

    # per-kernel callables for the timing harness: core/timing.py
    # re-threads the same env and times each kernel standalone, pairing
    # wall times with the per-kernel cost attribution by KernelRun.gid
    run.region_runners = lowered
    run.input_refs = [(iid, 0) for iid in pp.graph.input_ids]
    run.emitted_kernels = emitted
    return run, report


def emit(g: Graph, dims: Dict[str, int], blocks: Dict[str, int],
         interpret="auto") -> Callable[..., jax.Array]:
    """Strict single-output convenience wrapper around
    :func:`emit_program`: every region must lower to Pallas (no jax
    fallback) and the program must have exactly one output, which is
    returned as a bare array.  ``interpret`` may be a bool, ``None``, or
    ``"auto"`` (see :func:`resolve_interpret`)."""
    fn, report = emit_program(g, dims, blocks, interpret=interpret)
    if report.fallbacks:
        bad = [r for r in report.regions if r.fallback]
        raise ValueError(
            f"not fully Pallas-lowerable: {[r.fallback for r in bad]}")
    if len(g.output_ids) != 1:
        raise ValueError("emit() expects a single-output program; use "
                         "emit_program for multi-output lowering")

    def single(*merged_inputs):
        return fn(*merged_inputs)[0]

    return single
