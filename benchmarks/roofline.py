"""Roofline analysis (deliverable g): the three terms per (arch x shape).

Reads the dry-run JSON (launch/dryrun.py --out) and derives, per cell:

    compute term    = HLO_FLOPs / (chips x 197 TF/s bf16)
    memory term     = HLO_bytes / (chips x 819 GB/s)
    collective term = collective_bytes / (chips x 50 GB/s/link)

cost_analysis and the HLO collective scan are PER-DEVICE quantities after
SPMD partitioning, so 'chips' is already divided out — the terms below use
the per-device numbers against per-chip peaks directly.

Usage:
    PYTHONPATH=src python -m benchmarks.roofline dryrun_results.json

``engine_roofline`` is the same analysis pointed at the *engines*: it
lowers + compiles the jitted step of each GraphLab engine (dense/fused
local, chromatic, distributed) and classifies every cell as compute-,
memory-, or collective-bound against the TPU peaks.  Wired into
``benchmarks/run.py`` as the ``roofline`` harness (BENCH_roofline.json
in CI).
"""
from __future__ import annotations

import json
import re
import sys
from typing import Dict, List

from repro.launch.mesh import HBM_BW, ICI_BW_PER_LINK, PEAK_FLOPS_BF16

COLL_KEYS = ("coll_all-gather", "coll_all-reduce", "coll_reduce-scatter",
             "coll_all-to-all", "coll_collective-permute")


def analyze(records: List[Dict]) -> List[Dict]:
    out = []
    for r in records:
        if r.get("status") != "OK":
            out.append({"arch": r["arch"], "shape": r["shape"],
                        "mesh": r["mesh"], "status": r.get("status"),
                        "note": r.get("reason", r.get("error", ""))[:80]})
            continue
        cost = r.get("cost") or r["cost_raw"]
        flops = cost.get("flops", 0.0)
        byts = cost.get("bytes_accessed", 0.0)
        coll = sum(cost.get(k, 0.0) for k in COLL_KEYS)

        t_compute = flops / PEAK_FLOPS_BF16
        t_memory = byts / HBM_BW
        t_coll = coll / ICI_BW_PER_LINK
        terms = {"compute": t_compute, "memory": t_memory,
                 "collective": t_coll}
        dominant = max(terms, key=terms.get)
        bound = max(terms.values())
        # roofline fraction: how much of the bound step is useful compute
        frac = t_compute / bound if bound > 0 else 0.0

        meta = r.get("meta", {})
        model_flops = None
        if "model_active_params" in meta and "tokens" in meta:
            fwd_mult = 2 if meta.get("step_kind") == "train" else 0
            # 6*N*D for train (fwd+bwd), 2*N*D for inference
            model_flops = (6 if meta.get("step_kind") == "train" else 2) \
                * meta["model_active_params"] * meta["tokens"]
        elif "model_flops_fwd" in meta:
            model_flops = meta["model_flops_fwd"] * (
                3 if meta.get("step_kind") == "train" else 1)

        chips = 512 if r["mesh"] == "2x16x16" else 256
        useful_ratio = (model_flops / chips / flops
                        if model_flops and flops else None)

        out.append({
            "arch": r["arch"], "shape": r["shape"], "mesh": r["mesh"],
            "status": "OK",
            "t_compute_s": round(t_compute, 6),
            "t_memory_s": round(t_memory, 6),
            "t_collective_s": round(t_coll, 6),
            "dominant": dominant,
            "roofline_fraction": round(frac, 4),
            "useful_flops_ratio": (round(useful_ratio, 4)
                                   if useful_ratio else ""),
            "hbm_peak_GB": round(r["memory"]["peak_bytes"] / 1e9, 2),
            "fits_16GB": r["memory"]["peak_bytes"] <= 16e9,
        })
    return out


# -- engine-step roofline (the ``roofline`` harness of benchmarks/run.py) ---

_DT_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
             "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
             "u64": 8}
_COLL_RE = re.compile(
    r"^[%\w.\-]+\s*=\s*(\(?[a-z0-9]+\[[^=]*?)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _hlo_collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Result-shape bytes of every collective op in the compiled HLO
    (cost_analysis does not report these)."""
    out = {k: 0.0 for k in COLL_KEYS}
    for line in hlo_text.splitlines():
        m = _COLL_RE.match(line.strip())
        if not m:
            continue
        total = 0
        for dt, dims in _SHAPE_RE.findall(m.group(1)):
            if dt not in _DT_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * _DT_BYTES[dt]
        out[f"coll_{m.group(2)}"] += float(total)
    return out


def _step_cell(name: str, shape: str, mesh_name: str, engine,
               state) -> Dict:
    """Lower + compile one engine's jitted step, extract cost/memory."""
    compiled = engine.compile(state)
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    mem = compiled.memory_analysis()
    peak = (getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "output_size_in_bytes", 0)
            + getattr(mem, "temp_size_in_bytes", 0))
    return {
        "arch": name, "shape": shape, "mesh": mesh_name, "status": "OK",
        "cost": {"flops": float(cost.get("flops", 0.0)),
                 "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
                 **_hlo_collective_bytes(compiled.as_text())},
        "memory": {"peak_bytes": int(peak)},
    }


def engine_roofline() -> List[Dict]:
    """Roofline terms of the jitted engine steps: compute vs memory vs
    collective bound, per engine (dense/fused local, chromatic, dist)."""
    import jax
    import numpy as np

    from repro.apps.lbp import LoopyBPProgram, make_mrf_graph
    from repro.apps.pagerank import PageRankProgram, make_pagerank_graph
    from repro.core import ChromaticEngine, Engine
    from repro.graphs.generators import power_law_graph

    records = []
    st = power_law_graph(2000, avg_degree=8, seed=0)
    g = make_pagerank_graph(st)
    prog = PageRankProgram(0.15, st.n_vertices)
    shape = f"v{st.n_vertices}-e{st.n_edges}"
    for name, fused in (("pagerank-dense", False), ("pagerank-fused", True)):
        eng = Engine(prog, g, tolerance=1e-6, use_fused=fused)
        records.append(_step_cell(name, shape, "local", eng, eng.init(g)))

    mst = power_law_graph(1500, avg_degree=6, seed=1)
    mg = make_mrf_graph(mst, 4, seed=0)
    lbp = LoopyBPProgram(4, smoothing=0.7)
    ce = ChromaticEngine(lbp, mg, tolerance=1e-6)
    records.append(_step_cell("lbp-chromatic",
                              f"v{mst.n_vertices}-e{mst.n_edges}",
                              "local", ce, ce.init(mg)))

    if jax.device_count() >= 4:
        from repro.dist.engine import DistributedEngine
        devs = np.asarray(jax.devices()[:4]).reshape(4, 1)
        mesh = jax.sharding.Mesh(devs, ("data", "model"))
        de = DistributedEngine(prog, g, mesh, tolerance=1e-6)
        records.append(_step_cell("pagerank-dist-sweep", shape, "1x4",
                                  de, de.init()))
    else:
        records.append({"arch": "pagerank-dist-sweep", "shape": shape,
                        "mesh": "1x4", "status": "SKIP",
                        "reason": "needs 4 devices "
                        "(XLA_FLAGS=--xla_force_host_platform_"
                        "device_count=4)"})
    return analyze(records)


def main():
    path = sys.argv[1] if len(sys.argv) > 1 else "dryrun_results.json"
    with open(path) as f:
        records = json.load(f)
    rows = analyze(records)
    cols = ["arch", "shape", "mesh", "status", "t_compute_s", "t_memory_s",
            "t_collective_s", "dominant", "roofline_fraction",
            "useful_flops_ratio", "hbm_peak_GB", "fits_16GB"]
    print(",".join(cols))
    for row in rows:
        print(",".join(str(row.get(c, "")) for c in cols))


if __name__ == "__main__":
    main()
