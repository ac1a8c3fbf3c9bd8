#!/usr/bin/env python3
"""The lower-precision control of a cell, on the chip:

    python3 bench/control.py --workload <cell> --seeds 1 2 3

For each seed the cell's generator builds its graph, the plain reference's
``control`` solves it in the precision below the configuration's
(bfloat16 for float32), in the program's place, and the run's own
comparison (``bench/compare.py``: the reference's ``check`` against the
configuration's limits) judges that answer as it judges the program's.
One JSON line per seed: ``correct`` and each compared number beside its
limit; a limit stands only where the control comes out not correct.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import compare, spec  # noqa: E402


def judge(inst, cfg, ref, answer) -> dict:
    """The control's answer compared as a run compares the program's: the
    control solves to its end, so it counts as finished."""
    return compare.checks(cfg, ref.check(inst, cfg, answer), finished=True)


def main(argv=None, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload, root)
    cfg = cell.config
    gen = spec.module("graphs", cfg["generator"], root)
    ref = spec.module("reference", cfg["app"], root)
    import jax

    dev = jax.devices()[0]
    for seed in args.seeds:
        t0 = time.perf_counter()
        inst = gen.generate(cfg, seed)
        answer = ref.control(inst, cfg, dtype=args.dtype)
        checks = judge(inst, cfg, ref, answer)
        print(json.dumps({
            "workload": cell.name, "seed": seed, "dtype": args.dtype,
            "platform": dev.platform, "kind": dev.device_kind,
            "sweeps": answer.get("sweeps"),
            "seconds": time.perf_counter() - t0,
            "correct": compare.correct(checks),
            "checks": {k: {"value": c["value"], "limit": c["limit"]}
                       for k, c in checks.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
