"""CI benchmark-regression gate.

    python benchmarks/check_regression.py BENCH_ci.json benchmarks/baseline.json

Compares a fresh ``run.py --only pipeline --preset ci --json BENCH_ci.json``
run against the committed baseline and exits non-zero if

  * any pipeline row's **predicted traffic reduction** regresses more
    than 10% below the baseline (the fusion objective got worse for the
    same program/config),
  * any **Pallas region falls back** off the Pallas backend in ANY row,
    baseline-listed or new (``pallas_fallbacks != 0`` — the selected
    snapshot must lower),
  * any pinned row's **kernel launch count** grows (``launches`` — the
    grouped megakernel schedule split apart, paying launches and HBM
    round-trips the baseline avoided),
  * any pinned row's **calibrated region rank agreement**
    (``region_spearman`` — predicted vs measured per-kernel times under
    the fitted profile) drops more than 0.5 below the baseline (the
    compute-aware cost model re-learned a rank inversion),
  * the **wall-clock fused-vs-unfused speedup** — the geometric mean of
    the per-row ratios — collapses by more than ``WALL_TOLERANCE``
    (1.5x) below the baseline's.  Generous on purpose: absolute wall
    times are never compared across machines, only the same-machine
    fused/unfused *ratio*; it is aggregated over every program so
    single-row scheduler noise averages out; and only a >1.5x collapse
    fails so shared-runner noise cannot,
  * the same geomean speedup falls below **1.0x** in absolute terms —
    fusion slower than the launch-per-operator baseline is wrong no
    matter what the pin says (the baseline is per-op jitted, so this is
    fusion vs genuinely-no-fusion, not vs XLA's own fusion), or
  * a baseline row is missing from the fresh run.

``serve_*`` rows (from ``benchmarks/serve_bench.py``) are gated too:
the deterministic scheduler counters (completed/rejected/stalled
requests, warmup compile count) are pinned **exactly** — the synthetic
trace is seeded, so any drift is a scheduler behaviour change — while
**decode recompiles** and **Pallas fallbacks** must be zero on every
current serve row, pinned or not (one persistent megakernel per shape
bucket is the whole point of the serving tentpole), and so must the
resilience counters (``degradations``, ``quarantined`` — the clean
path never demotes and never quarantines).  Throughput
(``tokens_per_s``) gets the same generous same-machine treatment as the
speedup ratio: only a >1.5x collapse below the pin fails.

Absolute wall-clock columns are never gated — CI runners are too noisy;
the tightly-gated quantities are deterministic functions of the cost
model and the lowering, and the only timing key gated (the speedup
ratio) gets the generous threshold above.

Re-pin the baseline with

    python benchmarks/check_regression.py --pin BENCH_ci.json benchmarks/baseline.json

which writes ONLY the gated keys (predicted traffic reduction, region
and fallback counts, speedup ratio) so baseline diffs show real
changes, not machine-local wall-clock noise.
"""

from __future__ import annotations

import json
import sys

TOLERANCE = 0.10  # fail when reduction drops >10% below baseline
WALL_TOLERANCE = 1.5  # fail when speedup collapses >1.5x below baseline
SPEARMAN_TOLERANCE = 0.5  # fail when region rank agreement drops by more
GATED_KEYS = ("pred_traffic_reduction", "pallas_regions",
              "pallas_fallbacks", "launches", "resident_edges", "speedup",
              "region_spearman")
# serving rows: exact pins for the deterministic scheduler counters,
# ratio-gated throughput, and the zero-recompile / zero-fallback pins.
# degradations/quarantined are the resilience counters, pinned at zero
# on the clean path (the fault machinery may not cost the happy path)
GATED_SERVE_KEYS = ("tokens_per_s", "completed", "rejected", "stalled",
                    "warmup_compiles", "decode_recompiles",
                    "pallas_fallbacks", "degradations", "quarantined")
SERVE_EXACT_KEYS = ("completed", "rejected", "stalled", "warmup_compiles",
                    "degradations", "quarantined")


def _parse_derived(derived: str) -> dict:
    out = {}
    for part in derived.split(";"):
        if "=" in part:
            k, v = part.split("=", 1)
            out[k] = v
    return out


def _rows(path: str, prefix: str = "pipeline_") -> dict:
    with open(path) as f:
        data = json.load(f)
    rows = data["rows"] if isinstance(data, dict) else data
    return {r["name"]: _parse_derived(r["derived"]) for r in rows
            if r["name"].startswith(prefix)}


def _reduction(derived: dict) -> float:
    return float(derived["pred_traffic_reduction"].rstrip("x"))


def _pin(current_path: str, baseline_path: str) -> int:
    """Write a gated-keys-only baseline from a fresh run."""
    with open(current_path) as f:
        data = json.load(f)
    rows = data["rows"] if isinstance(data, dict) else data
    pinned = []
    for r in rows:
        if r["name"].startswith("pipeline_"):
            keys = GATED_KEYS
        elif r["name"].startswith("serve_"):
            keys = GATED_SERVE_KEYS
        else:
            continue
        derived = _parse_derived(r["derived"])
        kept = ";".join(f"{k}={derived[k]}" for k in keys if k in derived)
        pinned.append({"name": r["name"], "derived": kept})
    with open(baseline_path, "w") as f:
        json.dump({"preset": data.get("preset", "ci"), "rows": pinned}, f,
                  indent=2)
        f.write("\n")
    print(f"pinned {len(pinned)} row(s) -> {baseline_path}")
    return 0


def main(argv) -> int:
    if len(argv) == 4 and argv[1] == "--pin":
        return _pin(argv[2], argv[3])
    if len(argv) != 3:
        print(__doc__)
        return 2
    current, baseline = _rows(argv[1]), _rows(argv[2])
    failures, improved = [], []
    print(f"{'benchmark':32s} {'base':>8s} {'now':>8s}  verdict")
    for name, base in sorted(baseline.items()):
        cur = current.get(name)
        if cur is None:
            failures.append(f"{name}: missing from current run")
            continue
        base_red, cur_red = _reduction(base), _reduction(cur)
        floor = base_red * (1.0 - TOLERANCE)
        verdict = "ok"
        if cur_red < floor:
            verdict = "REGRESSED"
            failures.append(
                f"{name}: predicted traffic reduction {cur_red:.2f}x < "
                f"{floor:.2f}x (baseline {base_red:.2f}x - {TOLERANCE:.0%})")
        elif cur_red > base_red * (1.0 + TOLERANCE):
            verdict = "improved (re-pin baseline?)"
            improved.append(name)
        # region count is pinned too: MORE regions for the same program
        # is a partitioning regression; fewer is an improvement worth
        # re-pinning
        base_rg, cur_rg = base.get("pallas_regions"), cur.get(
            "pallas_regions")
        if base_rg is not None and cur_rg is not None:
            if int(cur_rg) > int(base_rg):
                verdict = "MORE REGIONS"
                failures.append(
                    f"{name}: selected snapshot now partitions into "
                    f"{cur_rg} regions (baseline {base_rg})")
            elif int(cur_rg) < int(base_rg) and verdict == "ok":
                verdict = "improved (re-pin baseline?)"
                improved.append(name)
        # launch count: the grouped megakernel schedule must not split
        # apart (every extra launch pays a cross-kernel HBM round-trip)
        base_l, cur_l = base.get("launches"), cur.get("launches")
        if base_l is not None and cur_l is not None:
            if int(cur_l) > int(base_l):
                verdict = "MORE LAUNCHES"
                failures.append(
                    f"{name}: grouped lowering now launches {cur_l} "
                    f"kernels (baseline {base_l})")
            elif int(cur_l) < int(base_l) and verdict == "ok":
                verdict = "improved (re-pin baseline?)"
                improved.append(name)
        # calibrated region rank agreement: the per-row Spearman of
        # predicted vs measured per-kernel times must not collapse (a
        # drop > SPEARMAN_TOLERANCE below the pin means the cost model
        # re-learned a rank inversion the baseline had fixed); measured
        # per-kernel seconds are noisy on shared runners, so only a
        # large drop fails
        base_sp, cur_sp = base.get("region_spearman"), cur.get(
            "region_spearman")
        if base_sp is not None and cur_sp is not None:
            if float(cur_sp) < float(base_sp) - SPEARMAN_TOLERANCE:
                verdict = "RANK INVERTED"
                failures.append(
                    f"{name}: region_spearman {float(cur_sp):.2f} < "
                    f"{float(base_sp):.2f} - {SPEARMAN_TOLERANCE} "
                    "(predicted-vs-measured region ranking collapsed)")
        print(f"{name:32s} {base_red:7.2f}x {cur_red:7.2f}x  {verdict}")
    # wall-clock gate: the same-machine fused/unfused speedup ratio,
    # aggregated (geometric mean) over every row both runs share so
    # single-row scheduler noise averages out, with a deliberately
    # generous threshold for shared runners
    shared = [(float(baseline[n]["speedup"].rstrip("x")),
               float(current[n]["speedup"].rstrip("x")))
              for n in sorted(set(baseline) & set(current))
              if "speedup" in baseline[n] and "speedup" in current[n]]
    if shared:
        import math
        base_geo = math.exp(sum(math.log(max(b, 1e-9))
                                for b, _ in shared) / len(shared))
        cur_geo = math.exp(sum(math.log(max(c, 1e-9))
                               for _, c in shared) / len(shared))
        floor = base_geo / WALL_TOLERANCE
        print(f"{'wall-clock (geomean speedup)':32s} {base_geo:7.2f}x "
              f"{cur_geo:7.2f}x  "
              f"{'ok' if cur_geo >= floor else 'WALL REGRESSED'}")
        if cur_geo < floor:
            failures.append(
                f"wall-clock: geomean fused-vs-unfused speedup "
                f"{cur_geo:.2f}x < {floor:.2f}x (baseline "
                f"{base_geo:.2f}x / {WALL_TOLERANCE})")
        # absolute floor, independent of the pin: fused code slower
        # than the launch-per-operator baseline is a regression even if
        # an old baseline was pinned that low
        if cur_geo < 1.0:
            failures.append(
                f"wall-clock: geomean fused-vs-unfused speedup "
                f"{cur_geo:.2f}x < 1.00x — fusion is slower than the "
                "per-op unfused baseline")
    # the fallback gate covers EVERY current row, including programs not
    # yet pinned into the baseline — a new benchmark may not sneak a
    # non-lowering snapshot past the gate
    for name, cur in sorted(current.items()):
        fb = cur.get("pallas_fallbacks")
        if fb is not None and fb != "0":
            failures.append(f"{name}: {fb} Pallas region(s) fell back to "
                            "the jax backend")
    # -- serving rows (benchmarks/serve_bench.py) ---------------------------
    cur_srv, base_srv = _rows(argv[1], "serve_"), _rows(argv[2], "serve_")
    for name, base in sorted(base_srv.items()):
        cur = cur_srv.get(name)
        if cur is None:
            failures.append(f"{name}: missing from current run")
            continue
        verdict = "ok"
        base_tps = float(base["tokens_per_s"])
        cur_tps = float(cur["tokens_per_s"])
        floor = base_tps / WALL_TOLERANCE
        if cur_tps < floor:
            verdict = "THROUGHPUT COLLAPSED"
            failures.append(
                f"{name}: {cur_tps:.0f} tokens/s < {floor:.0f} (baseline "
                f"{base_tps:.0f} / {WALL_TOLERANCE})")
        # the trace is seeded: scheduler counters are deterministic and
        # pinned exactly — any drift is a behaviour change, not noise
        for k in SERVE_EXACT_KEYS:
            if k in base and k in cur and base[k] != cur[k]:
                verdict = "SCHEDULER DRIFT"
                failures.append(f"{name}: {k}={cur[k]} (baseline pinned "
                                f"{base[k]})")
        print(f"{name:32s} {base_tps:7.0f}t {cur_tps:7.0f}t  {verdict}")
    # zero-recompile / zero-fallback pins cover EVERY current serve row,
    # baseline-listed or new — a steady-state decode step that compiles
    # (or a region that falls off the megakernel path) always fails
    for name, cur in sorted(cur_srv.items()):
        for k in ("decode_recompiles", "pallas_fallbacks",
                  "degradations", "quarantined"):
            v = cur.get(k)
            if v is not None and v != "0":
                failures.append(f"{name}: {k}={v} (must be 0)")
    extra = sorted(set(current) - set(baseline))
    if extra:
        print("note: rows not in baseline (traffic unchecked, fallbacks "
              f"still gated): {', '.join(extra)}")
    if failures:
        print("\nBENCH GATE FAILED:")
        for f in failures:
            print(f"  - {f}")
        return 1
    print("\nbench gate passed"
          + (f" ({len(improved)} row(s) improved)" if improved else ""))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
