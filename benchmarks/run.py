"""Benchmark harness entry: one module per paper table/figure plus the
framework benches.  Prints ``name,us_per_call,derived`` CSV and writes
one machine-readable ``results/BENCH_summary.json`` aggregating every
registered bench (schema: EXPERIMENTS.md §Bench summary), so perf can be
tracked across PRs from a single artifact."""
from __future__ import annotations

import json
import os
import sys
import time

SUMMARY_VERSION = 2   # v2: per-bench {rows, planner} records, row "algo"

RESULTS = os.path.join(os.environ.get("REPRO_RESULTS", os.getcwd()),
                       "results")


def _row_record(name: str, us: float, derived) -> dict:
    """One CSV row as a record: the row name's first path component is
    the op/bench family, the remainder the configuration.  The selected
    candidate name (``algo=...`` in the derived string) is promoted to a
    first-class ``algo`` field so perf dashboards can track selection
    flips without string-parsing."""
    op, _, config = name.partition("/")
    metrics = {}
    for part in str(derived).split(";"):
        k, _, v = part.partition("=")
        if _ and k:
            metrics[k] = v
    return {"name": name, "op": op, "config": config,
            "us_per_call": float(us), "derived": str(derived),
            "algo": metrics.get("algo"), "metrics": metrics}


def _planner_block(payload) -> dict | None:
    """The plan-cache hit/miss counters + selected-candidate names a
    bench's run() reported (``payload["planner"]``), if any."""
    if isinstance(payload, dict):
        return payload.get("planner")
    return None


def write_summary(benches: dict[str, tuple], total_s: float,
                  out_path: str | None = None) -> str:
    payload = {
        "version": SUMMARY_VERSION,
        "total_seconds": total_s,
        "benches": {
            name: {"rows": [_row_record(*row) for row in rows],
                   "planner": _planner_block(bench_payload)}
            for name, (rows, bench_payload) in benches.items()
        },
    }
    if out_path is None:
        out_path = os.path.join(RESULTS, "BENCH_summary.json")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
    return out_path


def main() -> None:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from . import chaos_bench, extensions_bench, guidelines_bench, \
        moe_dispatch, moe_e2e, opttree_bench, paper_tables, \
        pipeline_bench, roofline, serve_bench, tuner_bench, variants
    t0 = time.time()
    print("name,us_per_call,derived")
    benches: dict[str, tuple] = {}
    benches["paper_tables"] = paper_tables.run()
    benches["variants"] = variants.run()
    benches["guidelines"] = guidelines_bench.run()
    benches["extensions"] = extensions_bench.run()
    benches["moe_dispatch"] = moe_dispatch.run()
    benches["tuner"] = tuner_bench.run(synthetic=True)
    benches["pipeline"] = pipeline_bench.run()
    benches["moe_e2e"] = moe_e2e.run()
    benches["serve"] = serve_bench.run()
    benches["roofline"] = roofline.run()
    benches["chaos"] = chaos_bench.run(quick=True)
    benches["opttree"] = opttree_bench.run(quick=True)
    total = time.time() - t0
    out = write_summary(benches, total)
    print(f"# total {total:.1f}s", file=sys.stderr)
    print(f"# wrote {out}", file=sys.stderr)


if __name__ == '__main__':
    main()
