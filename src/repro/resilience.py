"""``repro.resilience`` — the degradation ladder and deterministic fault
injection for the compile pipeline.

The paper's framework targets "any multiprocessor architecture", which
in production terms means lowering WILL fail on some backend/shape
combinations and on-disk state WILL corrupt.  This module is the shared
vocabulary for surviving both:

* **The ladder** — :data:`LADDER` orders the compile strategies from
  fastest to most conservative::

      grouped      one multi-stage megakernel pallas_call per region group
      ungrouped    one pallas_call per region (no VMEM residency)
      jax          codegen_jax under jax.jit (runs everywhere)
      interpreter  the numpy reference interpreter (always correct)

  ``pipeline.compile`` starts at the rung its options ask for and, when
  an attempt raises, *demotes* one rung at a time until
  :class:`ResiliencePolicy.max_rung`, recording every attempt in a
  :class:`ResilienceReport` on the returned kernel; exhausting the
  allowed rungs raises :class:`LadderError`.  The happy path costs one
  ``try`` around the lowering call that already existed.  Nothing is
  remembered between compiles or processes: every compile walks the
  ladder from its starting rung.

* **Fault injection** — :class:`FaultPlan` fires deterministic faults
  (exceptions, cache corruption) at chosen per-site call indices.  Sites
  are string names checked by the compile pipeline
  (``compile:<rung>``, ``cache:get_plan``); an inactive plan costs one
  ``None`` check.  Activate programmatically (:func:`install` /
  :func:`faults`) or via ``$REPRO_FAULT_PLAN`` (inline JSON or a path
  to a JSON file), so CI chaos jobs can drive every rung reproducibly.

* **Metrics** — :data:`METRICS` counts ladder demotions process-wide
  (the serving engine reports the delta per run), mirroring how
  ``pipeline.CacheStats`` counts quarantines.

The serving engine's own degradation — the tick watchdog that rebuilds
a failing decode step one rung down, and the finite-logits guard that
evicts a poisoned request — lives in ``repro.launch.engine``.
"""

from __future__ import annotations

import json
import os
import threading
from contextlib import contextmanager
from dataclasses import asdict, dataclass, field, fields, replace
from typing import Any, Dict, List, Optional, Sequence, Tuple

# fastest first; each entry is strictly more conservative than the one
# before it.  ``pipeline.compile`` maps its options to a starting rung
# (pallas+group -> grouped, pallas -> ungrouped, jax -> jax, py ->
# interpreter) and only ever moves DOWN the list.
LADDER = ("grouped", "ungrouped", "jax", "interpreter")

FAULT_KINDS = ("raise", "corrupt")


class InjectedFault(RuntimeError):
    """Raised by :func:`check` at a site a :class:`FaultPlan` targets."""


class LadderError(RuntimeError):
    """Every allowed rung failed.  ``.report`` carries the full
    per-attempt record (rung, elapsed, error) for triage."""

    def __init__(self, msg: str, report: "ResilienceReport"):
        super().__init__(msg)
        self.report = report


def rung_index(rung: str) -> int:
    if rung not in LADDER:
        raise ValueError(f"unknown ladder rung {rung!r}; one of {LADDER}")
    return LADDER.index(rung)


def start_rung(backend: str, group: bool) -> str:
    """The rung ``pipeline.compile`` starts at for a backend/group pair."""
    if backend == "pallas":
        return "grouped" if group else "ungrouped"
    if backend == "jax":
        return "jax"
    return "interpreter"


def rungs_from(start: str, max_rung: str) -> Tuple[str, ...]:
    """The rungs a compile may attempt, in order: ``start`` down to
    ``max_rung`` inclusive.  A ``max_rung`` *above* the start permits no
    demotion at all — only the starting rung is attempted."""
    s, m = rung_index(start), rung_index(max_rung)
    if m < s:
        return (start,)
    return LADDER[s:m + 1]


@dataclass(frozen=True)
class ResiliencePolicy:
    """How far a compile may demote.  Frozen and hashable: lives on
    ``CompileOptions`` and participates in the kernel-cache key
    (non-default policies only, so default cache keys stay
    byte-identical to pre-resilience builds).

    * ``max_rung`` — the deepest ladder rung a compile may demote to;
      exhausting it raises :class:`LadderError`.
    """

    max_rung: str = "interpreter"

    def __post_init__(self):
        rung_index(self.max_rung)  # validate

    def key(self) -> Tuple:
        """Canonical value tuple (hashing / cache-key embedding)."""
        return (self.max_rung,)


DEFAULT_POLICY = ResiliencePolicy()


@dataclass
class Attempt:
    """One ladder attempt: the rung tried and how it went."""

    rung: str
    ok: bool
    elapsed_s: float              # wall time of this attempt
    error: Optional[str] = None   # "ExcType: message" when not ok


@dataclass
class ResilienceReport:
    """The compile's fault provenance: which rung was requested, which
    rung actually served it, and every attempt in between.  Attached to
    ``CompiledKernel.resilience_report`` on every compile (the happy
    path is one ok attempt at the requested rung, zero demotions)."""

    requested: str = "grouped"
    rung: Optional[str] = None        # the rung that served the compile
    attempts: List[Attempt] = field(default_factory=list)
    # RegionError from the driver's region partitioning, when the
    # partitioner could not split the selected snapshot (the lowering
    # then took emit_program's whole-program fallback)
    plan_error: Optional[str] = None

    @property
    def demotions(self) -> int:
        """Rungs descended from the requested one (0 on the happy path)."""
        if self.rung is None:
            return 0
        return max(rung_index(self.rung) - rung_index(self.requested), 0)

    @property
    def errors(self) -> List[str]:
        return [a.error for a in self.attempts if a.error]

    def to_json(self) -> Dict[str, Any]:
        d = asdict(self)
        d["demotions"] = self.demotions
        return d

    def summary(self) -> str:
        steps = ", ".join(f"{a.rung}:{'ok' if a.ok else 'fail'}"
                          for a in self.attempts)
        return (f"requested={self.requested} served={self.rung} "
                f"demotions={self.demotions} [{steps}]")


# ---------------------------------------------------------------------------
# process-wide resilience metrics (mirrors pipeline.CacheStats)
# ---------------------------------------------------------------------------

@dataclass
class ResilienceMetrics:
    demotions: int = 0         # ladder rungs descended (compile pipeline)
    ladder_failures: int = 0   # compiles that exhausted every rung
    faults_fired: int = 0      # injected faults that actually fired

    def snapshot(self) -> "ResilienceMetrics":
        return replace(self)

    def delta(self, since: "ResilienceMetrics") -> "ResilienceMetrics":
        return ResilienceMetrics(**{
            f.name: getattr(self, f.name) - getattr(since, f.name)
            for f in fields(self)})


METRICS = ResilienceMetrics()


# ---------------------------------------------------------------------------
# deterministic fault injection
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class FaultSpec:
    """Fire ``kind`` at ``site`` on the listed 0-based call indices.

    Kinds: ``raise`` (an :class:`InjectedFault` from :func:`check`) and
    ``corrupt`` (returned to the caller, which applies the mutation
    itself: the kernel cache garbles the on-disk entry so the REAL
    integrity machinery detects it)."""

    site: str
    indices: Tuple[int, ...] = (0,)
    kind: str = "raise"
    message: str = "injected fault"

    def __post_init__(self):
        if self.kind not in FAULT_KINDS:
            raise ValueError(
                f"unknown fault kind {self.kind!r}; one of {FAULT_KINDS}")
        object.__setattr__(self, "indices",
                           tuple(int(i) for i in self.indices))

    def to_json(self) -> Dict[str, Any]:
        return asdict(self)

    @classmethod
    def from_json(cls, d: Dict[str, Any]) -> "FaultSpec":
        return cls(site=str(d["site"]),
                   indices=tuple(d.get("indices", (0,))),
                   kind=str(d.get("kind", "raise")),
                   message=str(d.get("message", "injected fault")))


class FaultPlan:
    """A deterministic schedule of faults.  Each production site calls
    :func:`fire`; the plan counts the call (per site) and fires the
    matching :class:`FaultSpec` when the count hits one of its indices.
    Everything is index-based, so the same plan against the same code
    path fires identically every run — that is what lets the chaos CI
    job pin quarantine/demotion counters *exactly*.

    ``seed`` is provenance (recorded in reports) and the randomness
    source for :meth:`seeded` helpers; the plan itself is deterministic
    by construction."""

    def __init__(self, specs: Sequence[FaultSpec] = (), *, seed: int = 0):
        self.specs = tuple(specs)
        self.seed = int(seed)
        self._by_site: Dict[str, List[FaultSpec]] = {}
        for s in self.specs:
            self._by_site.setdefault(s.site, []).append(s)
        self._calls: Dict[str, int] = {}
        self.fired: List[Tuple[str, int, str]] = []  # (site, index, kind)
        self._lock = threading.Lock()

    def fire(self, site: str) -> Optional[FaultSpec]:
        """Count one call at ``site``; return the spec that fires at
        this index, if any (thread-safe)."""
        with self._lock:
            idx = self._calls.get(site, 0)
            self._calls[site] = idx + 1
            for spec in self._by_site.get(site, ()):
                if idx in spec.indices:
                    self.fired.append((site, idx, spec.kind))
                    METRICS.faults_fired += 1
                    return spec
        return None

    def calls(self, site: str) -> int:
        return self._calls.get(site, 0)

    def fired_count(self, site: Optional[str] = None) -> int:
        if site is None:
            return len(self.fired)
        return sum(1 for s, _, _ in self.fired if s == site)

    def expected_count(self, site_prefix: str = "") -> int:
        """How many faults this plan schedules at sites matching the
        prefix — what the chaos gate pins counters against."""
        return sum(len(s.indices) for s in self.specs
                   if s.site.startswith(site_prefix))

    def reset(self) -> None:
        with self._lock:
            self._calls.clear()
            self.fired.clear()

    def to_json(self) -> Dict[str, Any]:
        return {"seed": self.seed,
                "faults": [s.to_json() for s in self.specs]}

    @classmethod
    def from_json(cls, d: Dict[str, Any]) -> "FaultPlan":
        return cls([FaultSpec.from_json(s) for s in d.get("faults", ())],
                   seed=int(d.get("seed", 0)))


_ACTIVE: Optional[FaultPlan] = None
# lazily-parsed $REPRO_FAULT_PLAN, cached per env value so per-site call
# counters survive across active() calls
_ENV_PLAN: Tuple[Optional[str], Optional[FaultPlan]] = (None, None)


def install(plan: Optional[FaultPlan]) -> None:
    """Set (or clear, with ``None``) the process-wide fault plan."""
    global _ACTIVE
    _ACTIVE = plan


@contextmanager
def faults(plan: FaultPlan):
    """Scope a fault plan: ``with resilience.faults(plan): ...``."""
    prev = _ACTIVE
    install(plan)
    try:
        yield plan
    finally:
        install(prev)


def active() -> Optional[FaultPlan]:
    """The installed plan, else one parsed from ``$REPRO_FAULT_PLAN``
    (inline JSON or a path to a JSON file), else ``None``."""
    if _ACTIVE is not None:
        return _ACTIVE
    global _ENV_PLAN
    raw = os.environ.get("REPRO_FAULT_PLAN")
    if not raw:
        return None
    if _ENV_PLAN[0] == raw:
        return _ENV_PLAN[1]
    text = raw
    if not raw.lstrip().startswith("{"):
        with open(raw) as f:
            text = f.read()
    plan = FaultPlan.from_json(json.loads(text))
    _ENV_PLAN = (raw, plan)
    return plan


def fire(site: str) -> Optional[FaultSpec]:
    """Consult the active plan at ``site``.  No plan -> ``None`` (one
    global read: the cost injection adds to the happy path)."""
    plan = active()
    return plan.fire(site) if plan is not None else None


def check(site: str) -> None:
    """The compile-site hook: raise on ``raise`` faults, ignore kinds
    the site does not implement."""
    spec = fire(site)
    if spec is not None and spec.kind == "raise":
        raise InjectedFault(f"{site}[{spec.message}]")
