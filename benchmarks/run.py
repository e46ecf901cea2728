# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness.

  fig6_latency      — paper Figure 6 (inference latency, 3 designs)
  fig7_energy       — paper Figure 7 (communication energy split)
  fig8_edp          — paper Figure 8 (normalized EDP)
  table3_primitives — paper Table 3 (per-primitive cost/area analogue)
  activation_sweep  — paper §6.1 (gap vs activation cost)
  claims            — pass/fail of the paper's quantitative claims
  fusion            — measured wall-clock sidebar-vs-DMA on this host
  depth_sweep       — ring-depth sweep T in {2,3,4,8}: measured wall +
                      measured/modeled stall and overlap cycles
  roofline          — per-(arch x shape x mesh) dry-run roofline terms
  serving           — scan-vs-loop decode, per-layer plan dispatch, and
                      continuous-vs-static batching (tokens/s, p50/p95)

Run: PYTHONPATH=src python -m benchmarks.run [section ...] [--json out.json]

``--json PATH`` additionally writes the rows as a JSON list of
``{"section", "name", "us_per_call", "derived"}`` objects — the
machine-readable form committed as BENCH_*.json trajectory files.
"""

from __future__ import annotations

import json
import sys


def main() -> None:
    from benchmarks import (
        fusion_bench,
        paper_figures,
        roofline_report,
        serving_bench,
    )
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    sections = {
        "fig6_latency": paper_figures.fig6_latency,
        "fig7_energy": paper_figures.fig7_energy,
        "fig8_edp": paper_figures.fig8_edp,
        "table3_primitives": paper_figures.table3_primitives,
        "activation_sweep": paper_figures.activation_sweep,
        "claims": paper_figures.validate_paper_claims,
        "fusion": fusion_bench.rows,
        "depth_sweep": fusion_bench.depth_sweep_rows,
        "roofline": roofline_report.rows,
        "serving": serving_bench.rows,
    }
    argv = sys.argv[1:]
    json_path = None
    if "--json" in argv:
        i = argv.index("--json")
        try:
            json_path = argv[i + 1]
        except IndexError:
            raise SystemExit("--json requires an output path")
        argv = argv[:i] + argv[i + 2:]
    wanted = argv or list(sections)
    print("name,us_per_call,derived")
    failures = 0
    records = []
    for name in wanted:
        fn = sections[name]
        try:
            for row in fn():
                tag, us, derived = row
                print(f"{tag},{us:.3f},{derived:.6e}")
                records.append({
                    "section": name, "name": tag,
                    "us_per_call": round(float(us), 3),
                    "derived": float(derived),
                })
        except Exception as e:  # pragma: no cover
            failures += 1
            print(f"{name}/ERROR,0,0  # {type(e).__name__}: {e}",
                  file=sys.stderr)
    if json_path:
        with open(json_path, "w") as f:
            json.dump(records, f, indent=1)
        print(f"wrote {len(records)} rows to {json_path}", file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
