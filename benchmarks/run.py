"""Benchmark entrypoint: one section per paper table/figure + system extras.

  PYTHONPATH=src python -m benchmarks.run [--full]

Sections:
  fig2      staleness vs K (paper Fig. 2)
  fig3      accuracy vs global cycles (paper Fig. 3)
  solvers   analytic SAI vs numerical solvers (Sec. IV/V)
  alloc     batched allocation engine vs per-problem Python loop (BENCH_alloc.json)
  realloc   per-cycle reallocation under drift: batched re-solves + the
            in-scan reallocating orchestrator (merges into BENCH_alloc.json)
  async     event-driven async federation: cycle-gated vs FedAsync vs
            buffered under drift + eager-vs-bucketed engine wall-time
            (merges into BENCH_alloc.json)
  churn     adaptive KKT vs static/equal allocation under client churn +
            fault injection at rising dropout rates (merges into
            BENCH_alloc.json)
  energy    accuracy-vs-energy frontier: budgeted kkt_energy vs the
            energy-blind schemes across battery budgets (merges into
            BENCH_alloc.json)
  multimodel multi-tenant scheduler: deficit-driven cross-model allocation
            vs the equal split on the laggard's time-to-accuracy (merges
            into BENCH_alloc.json)
  fleet     fleet-of-fleets scale: FleetEngine rounds at 10^4 learners +
            the sharded dispatch solve at 10^6 learners (merges into
            BENCH_alloc.json)
  kernels   hot-spot micro-benchmarks
  roofline  per (arch x shape x mesh) roofline terms from dry-run artifacts
"""

from __future__ import annotations

import argparse
import sys
import time

from benchmarks import (
    accuracy_vs_cycles,
    alloc_bench,
    async_bench,
    churn_bench,
    energy_bench,
    fleet_scale,
    kernel_bench,
    multimodel_bench,
    roofline_report,
    solver_table,
    staleness_vs_k,
)

SECTIONS = [
    ("fig2_staleness_vs_k", staleness_vs_k.main),
    ("solver_table", solver_table.main),
    ("alloc_bench", alloc_bench.main),
    ("realloc_bench", alloc_bench.realloc_main),
    ("async_bench", async_bench.main),
    ("churn_bench", churn_bench.main),
    ("energy_bench", energy_bench.main),
    ("multimodel_bench", multimodel_bench.main),
    ("fleet_scale", fleet_scale.main),
    ("kernel_bench", kernel_bench.main),
    ("megakernel_bench", kernel_bench.megakernel_main),
    ("roofline_report", roofline_report.main),
    ("fig3_accuracy_vs_cycles", accuracy_vs_cycles.main),
]


def _count_rows(payload) -> int:
    """Row count of one merged section: list payloads count directly,
    dict payloads count their largest list value (sweep rows)."""
    if isinstance(payload, list):
        return len(payload)
    if isinstance(payload, dict):
        return max(
            (_count_rows(v) for v in payload.values() if isinstance(v, (list, dict))),
            default=1,
        )
    return 1


def _section_summary(before: dict) -> str | None:
    """One line per section the last bench merged into BENCH_alloc.json:
    rows, producing device, written_at — compared against the file state
    BEFORE the bench ran, so only freshly (re)written sections print."""
    import json

    if not alloc_bench.OUT_PATH.exists():
        return None
    data = json.loads(alloc_bench.OUT_PATH.read_text())
    lines = []
    for name, sec in data.items():
        if name == "bench" or sec == before.get(name):
            continue
        if not (isinstance(sec, dict) and "data" in sec):
            continue
        lines.append(
            f"# {name}: {_count_rows(sec['data'])} rows, "
            f"device={sec.get('device')}, written_at={sec.get('written_at')}"
        )
    return "\n".join(lines) if lines else None


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale sweeps (slow)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    quick = not args.full

    import json

    from repro.launch.compile_cache import enable_compile_cache

    print(f"# compile cache: {enable_compile_cache()}", flush=True)

    for name, fn in SECTIONS:
        if args.only and args.only not in name:
            continue
        print(f"\n===== {name} =====", flush=True)
        before = (json.loads(alloc_bench.OUT_PATH.read_text())
                  if alloc_bench.OUT_PATH.exists() else {})
        t0 = time.time()
        fn(quick=quick)
        summary = _section_summary(before)
        if summary:
            print(summary, flush=True)
        print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)


if __name__ == "__main__":
    main()
