"""Reduction of a profiler trace to the benchmark's device numbers.

Two steps.  ``load`` extracts from the ``.xplane.pb`` the profiler wrote
what the reduction needs, as plain lists: per TPU, the "XLA Modules"
line (one event per run of a compiled program) and the "XLA Ops" line
(one event per operation, with a flag for Mosaic kernels, the
``tpu_custom_call`` that a Pallas kernel compiles to); and the events of
the host thread that carries the harness's own spans (``bench.tick``
around each ``Engine.run`` call, ``bench.wait_arrival`` around waits
for the next request).  ``summarize`` reduces that to numbers; it is
pure Python and is tested on a small trace recorded on the chip.

Host and device events share the trace's clock.  The traced interval
runs from the start of the first harness span to the end of the last;
it holds whole ticks only.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List

# the engine's jitted programs, by the module names JAX gives them
MODULES = {"jit_decode_step": "decode", "jit__lambda": "prefill",
           "jit_insert": "insert"}
HOST_SPANS = ("bench.tick", "bench.wait_arrival")
MOSAIC = 'custom_call_target="tpu_custom_call"'


def _op_name(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion``."""
    name = text[1:text.index(" = ")] if text.startswith("%") and " = " in \
        text else text.split(" ")[0]
    return re.sub(r"\.\d+$", "", name)


def load(trace_dir: Path) -> dict:
    """Extract the newest trace under ``trace_dir``."""
    import jax

    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(str(files[-1]))
    out: dict = {"devices": [], "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev["modules"] = [[e.name.split("(")[0], e.start_ns,
                                       e.duration_ns] for e in line.events]
                elif line.name == "XLA Ops":
                    dev["ops"] = [[_op_name(e.name), e.start_ns,
                                   e.duration_ns, MOSAIC in e.name]
                                  for e in line.events]
            out["devices"].append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                evs = [[e.name, e.start_ns, e.duration_ns]
                       for e in line.events]
                if any(e[0] in HOST_SPANS for e in evs):
                    out["host"] += evs
    return out


def _union(intervals) -> List[List[float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclass
class Summary:
    """Device numbers of one traced interval, averaged over the TPUs."""
    window_s: float
    busy_s: float
    modules: Dict[str, List[float]] = field(default_factory=dict)
    kernels: Dict[str, List[float]] = field(default_factory=dict)
    top_ops: List[list] = field(default_factory=list)
    idle_gaps: List[list] = field(default_factory=list)

    # modules[kind] / kernels[kind] = [count, seconds]
    def module_count(self, kind: str) -> float:
        return self.modules.get(kind, [0, 0.0])[0]

    def module_seconds(self, kind: str) -> float:
        return self.modules.get(kind, [0, 0.0])[1]

    def kernel_count(self, kind: str) -> float:
        return self.kernels.get(kind, [0, 0.0])[0]

    def kernel_seconds(self, kind: str) -> float:
        return self.kernels.get(kind, [0, 0.0])[1]


def _host_label(host, spans, t: float) -> str:
    """What the host was doing at ``t``: the innermost host event over
    it, else the harness span, else "outside the harness"."""
    best = None
    for name, s, d in host:
        if s <= t < s + d and name not in HOST_SPANS:
            if best is None or d < best[1]:
                best = (name, d)
    if best:
        return best[0]
    for name, s, d in spans:
        if s <= t < s + d:
            return name
    return "outside the harness"


def summarize(ex: dict) -> Summary:
    """Reduce an extracted trace (``load``) to a Summary."""
    spans = [e for e in ex["host"] if e[0] in HOST_SPANS]
    if not spans or not ex["devices"]:
        raise ValueError("the trace holds no harness span or no TPU")
    w0 = min(s for _, s, _ in spans)
    w1 = max(s + d for _, s, d in spans)
    n_dev = len(ex["devices"])
    busy = 0.0
    modules: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    kernels: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    self_time: Dict[str, float] = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    for dev in ex["devices"]:
        mods = sorted((s, s + d, MODULES.get(n, "other"))
                      for n, s, d in dev["modules"] if w0 <= s < w1)
        for s, e, kind in mods:
            modules[kind][0] += 1 / n_dev
            modules[kind][1] += (e - s) * 1e-9 / n_dev
        starts = [m[0] for m in mods]

        def kind_at(t):
            # one stream runs one program at a time: an op belongs to the
            # latest program started before it, even past that program's
            # recorded end
            i = bisect.bisect_right(starts, t) - 1
            return mods[i][2] if i >= 0 else "other"

        ops = sorted(([n, s, d, mo] for n, s, d, mo in dev["ops"]
                      if w0 <= s < w1), key=lambda o: (o[1], -o[2]))
        stack: list = []
        for o in ops:
            name, s, d, mosaic = o
            kind = kind_at(s)
            if mosaic:
                kernels[kind][0] += 1 / n_dev
                kernels[kind][1] += d * 1e-9 / n_dev
            # self time: an op's duration less that of the ops it holds
            while stack and stack[-1][0] <= s:
                stack.pop()
            key = f"{kind}:{name}"
            if stack:
                self_time[stack[-1][1]] -= d * 1e-9 / n_dev
            self_time[key] += d * 1e-9 / n_dev
            stack.append((s + d, key))
        iv = _union([(max(s, w0), min(e, w1)) for s, e, _ in mods]
                    + [(max(s, w0), min(s + d, w1)) for _, s, d, _ in ops])
        busy += sum(e - s for s, e in iv) / n_dev
        edges = [w0] + [x for s_e in iv for x in s_e] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                label = _host_label(ex["host"], spans, (a + b) / 2)
                gaps[label] += (b - a) * 1e-9 / n_dev
    top = sorted(self_time.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return Summary(window_s=(w1 - w0) * 1e-9, busy_s=busy * 1e-9,
                   modules=dict(modules), kernels=dict(kernels),
                   top_ops=[[k, v] for k, v in top],
                   idle_gaps=[[k, v] for k, v in idle])


def trim(ex: dict, n_spans: int) -> dict:
    """The part of an extracted trace that covers its first ``n_spans``
    harness spans, for a test fixture."""
    spans = sorted(e for e in ex["host"] if e[0] in HOST_SPANS)
    spans = sorted(spans, key=lambda e: e[1])[:n_spans]
    w0, w1 = spans[0][1], max(s + d for _, s, d in spans)

    def inside(evs):
        return [e for e in evs if w0 <= e[1] and e[1] + e[2] <= w1]

    return {"devices": [{"modules": inside(d["modules"]),
                         "ops": inside(d["ops"])} for d in ex["devices"]],
            "host": inside(ex["host"])}
