"""Benchmark runner: one harness per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig1a,fig3,...] [--json]

Prints CSV per figure.  ``--json`` additionally writes one machine-readable
``BENCH_<name>.json`` per harness (records + wall time) so the perf
trajectory is recorded across PRs; CI uploads them as artifacts.  The
``roofline`` harness classifies each compiled engine step as compute-,
memory-, or collective-bound (benchmarks/roofline.py; the same module's
``main`` still consumes the launch dry-run JSON standalone).
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from benchmarks import churn_bench
from benchmarks import gas_bench
from benchmarks import obs_bench
from benchmarks import paper_figures as pf
from benchmarks import pipeline_bench
from benchmarks import roofline
from benchmarks import snapshot_bench
from benchmarks import stream_bench
from benchmarks import wire_bench
from repro.launch.compile_cache import use_compile_cache

HARNESSES = {
    "fig1a": pf.fig1a_async_vs_sync_convergence,
    "fig1b": pf.fig1b_update_distribution,
    "fig1d": pf.fig1d_serializable_vs_racing,
    "fig3": pf.fig3_pipeline_sweep,
    "fig4": pf.fig4_snapshot_overhead,
    "fig6": pf.fig6_scaling_and_intensity,
    "fig9a": pf.fig9a_dynamic_vs_static_als,
    "table2": pf.table2_throughput,
    "churn": churn_bench.churn_chaos,
    "gas": gas_bench.gas_microbenchmark,
    "obs": obs_bench.obs_overhead,
    "pipeline": pipeline_bench.pipeline_sweep,
    "roofline": roofline.engine_roofline,
    "snapshot": snapshot_bench.snapshot_overhead,
    "stream": stream_bench.stream_reconvergence,
    "wire": wire_bench.wire_roundtwo,
}


def _write_json(name: str, payload: dict) -> None:
    path = f"BENCH_{name}.json"
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    print(f"wrote {path}")


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated harness names")
    ap.add_argument("--smoke", action="store_true",
                    help="collection check: verify every harness resolves "
                         "to a callable with a docstring, run nothing")
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_<name>.json per harness "
                         "(BENCH_smoke.json under --smoke)")
    args = ap.parse_args()
    names = (args.only.split(",") if args.only else list(HARNESSES))
    unknown = [n for n in names if n not in HARNESSES]
    if unknown:
        print(f"unknown harness names {unknown} (known: {list(HARNESSES)})")
        sys.exit(2)

    if args.smoke:
        bad = [n for n in names
               if not (callable(HARNESSES.get(n))
                       and (HARNESSES[n].__doc__ or "").strip())]
        for n in names:
            if n not in bad:
                print(f"collected {n}: "
                      f"{HARNESSES[n].__doc__.splitlines()[0]}")
        if bad:
            print(f"FAILED collection: {bad} (known: {list(HARNESSES)})")
            sys.exit(1)
        print(f"{len(names)} harnesses collected")
        if args.json:
            _write_json("smoke", {"collected": names})
        return

    failures = 0
    for name in names:
        fn = HARNESSES[name]
        print(f"\n===== {name}: {fn.__doc__.splitlines()[0]} =====",
              flush=True)
        t0 = time.time()
        try:
            records = fn()
        except Exception as e:  # pragma: no cover
            failures += 1
            print(f"FAILED: {type(e).__name__}: {e}")
            continue
        wall = time.time() - t0
        if records:
            cols = sorted({k for r in records for k in r})
            print(",".join(cols))
            for r in records:
                print(",".join(str(r.get(c, "")) for c in cols))
        print(f"({wall:.1f}s)")
        if args.json:
            _write_json(name, {"name": name, "wall_s": round(wall, 2),
                               "records": records or []})
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
