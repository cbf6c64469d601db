"""``CompileOptions`` — the consolidated, hashable compile configuration.

``pipeline.compile`` grew ~15 keyword arguments across PRs 1-7
(``backend``, ``blocks``, ``autotune``, ``group``, ``stabilize``,
``profile``, ``top_k``, ...).  This module folds every option that
shapes the *emitted kernel* into one frozen dataclass that

* normalizes dict-valued fields (``blocks``, ``item_bytes``) into
  sorted tuples at construction, so two equal option sets compare and
  hash equal regardless of dict insertion order;
* is hashable — model layers key their per-shape kernel lru_caches on
  it, and serving engines key persistent per-(arch, shape-bucket)
  kernels on it;
* **hashes directly into the kernel-cache key**: ``cache_opts()``
  produces the canonical opts tuple ``CacheKey`` embeds, the single
  source of truth for "which options make two compiles distinct".

``pipeline.compile(graph, dims, options=CompileOptions(...))`` is the
primary API; the flat-kwargs form (``pipeline.compile(graph, dims,
backend=..., blocks=...)``) is kept as a back-compat shim — it builds a
``CompileOptions`` internally and is **deprecated**: new call sites
should construct options explicitly.

Problem *shape* stays out of the options on purpose: ``dims`` /
``dim_candidates`` describe what is being compiled, ``CompileOptions``
describes how.  The ``cache`` handle (a runtime resource, not a compile
decision) also stays a separate argument.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Mapping, Optional, Tuple

_MAP_FIELDS = ("blocks", "item_bytes")


def _norm_map(value) -> Optional[Tuple[Tuple[str, Any], ...]]:
    """dict | tuple-of-pairs | None -> canonical sorted tuple of pairs."""
    if value is None:
        return None
    if isinstance(value, Mapping):
        return tuple(sorted(value.items()))
    return tuple(sorted(tuple(value)))


@dataclass(frozen=True)
class CompileOptions:
    """Everything that decides *how* a block program compiles.

    Fields mirror the historical ``pipeline.compile`` keywords; see the
    driver docstring for full semantics.  ``blocks`` / ``item_bytes``
    accept plain dicts and are canonicalized to sorted tuples, so the
    instance is hashable and order-insensitive.
    """

    backend: str = "jax"
    # per-dim block sizes (pallas backend) — dict accepted, stored as a
    # sorted tuple of (dim, size) pairs
    blocks: Optional[Tuple[Tuple[str, int], ...]] = None
    # cost-model per-item-kind byte overrides
    item_bytes: Optional[Tuple[Tuple[str, int], ...]] = None
    fused: bool = True
    interpret: Optional[bool] = None   # pallas: None = resolve per device
    jit: Any = True                    # True | False | "per-op" (jax)
    stabilize: Optional[bool] = None   # None = auto (softmax-bearing)
    autotune: str = "analytic"         # analytic | measured
    top_k: int = 3
    measure_repeats: int = 3
    group: bool = True                 # pallas region-group megakernels
    # calibration profile override (CalibrationProfile); participates in
    # hashing/equality via its digest, not object identity
    profile: Optional[Any] = None
    # degradation-ladder policy (resilience.ResiliencePolicy): how far a
    # failing compile may demote (grouped -> ungrouped -> jax ->
    # interpreter).  None = the default policy (the full ladder), which
    # keeps cache keys byte-identical to pre-resilience builds
    resilience: Optional[Any] = None

    def __post_init__(self):
        for name in _MAP_FIELDS:
            object.__setattr__(self, name, _norm_map(getattr(self, name)))

    # -- dict views ---------------------------------------------------------
    @property
    def blocks_dict(self) -> Optional[Dict[str, int]]:
        return dict(self.blocks) if self.blocks is not None else None

    @property
    def item_bytes_dict(self) -> Optional[Dict[str, int]]:
        return dict(self.item_bytes) if self.item_bytes is not None else None

    # -- identity -----------------------------------------------------------
    def _profile_digest(self) -> Optional[str]:
        return self.profile.digest() if self.profile is not None else None

    def _policy(self):
        """The effective ResiliencePolicy (``None`` -> the default)."""
        from repro import resilience as RZ
        return (self.resilience if self.resilience is not None
                else RZ.DEFAULT_POLICY)

    def key(self) -> Tuple:
        """Canonical value tuple: what equality and hashing mean."""
        return (self.backend, self.blocks, self.item_bytes, self.fused,
                self.interpret,
                self.jit if self.jit == "per-op" else bool(self.jit),
                self.stabilize, self.autotune, int(self.top_k),
                int(self.measure_repeats), bool(self.group),
                self._profile_digest(), self._policy().key())

    def __hash__(self) -> int:
        return hash(self.key())

    def __eq__(self, other) -> bool:
        if not isinstance(other, CompileOptions):
            return NotImplemented
        return self.key() == other.key()

    def replace(self, **changes) -> "CompileOptions":
        """``dataclasses.replace`` that re-normalizes dict fields."""
        return dataclasses.replace(self, **changes)

    # -- the cache-key contribution -----------------------------------------
    def cache_opts(self, *, stabilized: bool, autotuned: bool,
                   profile=None, vmem_budget: Optional[int] = None
                   ) -> Tuple:
        """The opts tuple ``CacheKey`` embeds — every option that changes
        the emitted kernel or the selection plan, nothing that doesn't.

        ``stabilized`` is the *resolved* stabilization decision (the
        ``None`` auto-detect already applied), ``autotuned`` says whether
        a dim_candidates sweep is in play (the autotune mode only matters
        then), ``profile`` is the *effective* calibration profile (the
        driver may have auto-loaded one), and ``vmem_budget`` must be the
        resolved budget when grouping shapes a pallas plan.  For the
        pallas backend ``interpret`` must already be resolved to a bool.
        """
        from repro.core import calibrate as CAL
        opts: Tuple = ()
        if stabilized:
            opts += (("stabilize", True),)
        if self.backend == "jax":
            opts += (("jit", self.jit if self.jit == "per-op"
                      else bool(self.jit)),)
        if self.backend == "pallas":
            opts += (("interpret", self.interpret), ("jit", bool(self.jit)))
            if not self.group:
                opts += (("group", False),)
            else:
                # the VMEM budget shapes the grouping, so a plan cached
                # under one budget must never serve another (its
                # kernel_ids/launches would describe kernels that no
                # longer exist)
                opts += (("vmem_budget", vmem_budget),)
        if self.item_bytes:
            opts += (("item_bytes", self.item_bytes),)
        if autotuned and self.autotune != "analytic":
            opts += (("autotune", self.autotune),)
        if (profile is not None
                and profile.digest() != CAL.DEFAULT_PROFILE.digest()):
            # a different calibration profile can select a different
            # snapshot/dims: never serve its plan under the default's key
            opts += (("profile", profile.digest()),)
        from repro import resilience as RZ
        policy = self._policy()
        if policy != RZ.DEFAULT_POLICY:
            # a bounded ladder (max_rung above interpreter) can change
            # which rung's kernel gets cached in-process;
            # keyed only when non-default so existing keys stay
            # byte-identical
            opts += (("resilience", policy.key()),)
        return opts


#: the defaults, shared: ``CompileOptions()`` allocates nothing new
DEFAULT_OPTIONS = CompileOptions()
