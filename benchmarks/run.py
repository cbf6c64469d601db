"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV:
  * fusion_*    — the paper's three worked examples: traffic collapse,
                  launch counts, work replication, rule applications;
  * pipeline_*  — the same examples *executed* through
                  ``pipeline.compile``: fused vs unfused wall time next to
                  the cost model's predicted traffic (the end-to-end loop);
  * kernel_*    — fused vs naive kernel wall times (host backend);
  * roofline_*  — per (arch x shape x mesh) bound times from the dry-run
                  artifact (if dryrun_results.json exists).

``--only SECTION`` (fusion | pipeline | kernel | roofline) restricts the
run; default runs everything.  ``--preset ci`` shrinks the pipeline
section to the tiny fixed configuration the CI benchmark gate compares
against ``benchmarks/baseline.json``; ``--json PATH`` additionally
writes the rows as JSON (CI uploads it as the ``BENCH_ci.json``
artifact and feeds it to ``benchmarks/check_regression.py``).
"""

from __future__ import annotations

import argparse
import functools
import json


def main() -> None:
    from benchmarks import fusion_bench, kernel_bench, roofline

    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=["fusion", "pipeline", "kernel",
                                       "roofline"], default=None)
    ap.add_argument("--preset", choices=sorted(fusion_bench.PRESETS),
                    default="full")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as JSON (the CI artifact)")
    ap.add_argument("--profile-out", default=None, metavar="PATH",
                    help="also write the fitted calibration profile "
                         "(pipeline section) to PATH; it is always "
                         "saved to the kernel cache dir")
    ap.add_argument("--lowering-out", default=None, metavar="PATH",
                    help="write the per-program Pallas lowering reports "
                         "(launches, resident edges, kernel ids) as "
                         "JSON — CI uploads it as an artifact")
    args = ap.parse_args()
    from repro.runtime import compile_cache
    compile_cache.configure()

    sections = {
        "fusion": fusion_bench.run,
        "pipeline": functools.partial(fusion_bench.run_pipeline,
                                      preset=args.preset,
                                      profile_out=args.profile_out,
                                      lowering_out=args.lowering_out),
        "kernel": kernel_bench.run,
        "roofline": roofline.run,
    }
    rows = []
    for name, fn in sections.items():
        if args.only is None or args.only == name:
            rows += fn()

    print("name,us_per_call,derived")
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"preset": args.preset, "rows": rows}, f, indent=2)


if __name__ == "__main__":
    main()
