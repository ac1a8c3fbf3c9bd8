"""What decides ``correct``: each number the reference reads, held to its
limit from the configuration's ``checks``, and that the solve finished.
The harness's runs and the lower-precision control are judged by the same
code."""
from __future__ import annotations

import math


def _finite(x):
    return float(x) if x is not None and math.isfinite(float(x)) else None


def checks(cfg: dict, values: dict, finished: bool) -> dict:
    """``{name: {"value", "limit", "rule"}}``; a value that is missing or
    not finite is None, which fails."""
    out = {"solve_finished": {"value": 1.0 if finished else 0.0,
                              "limit": 1.0, "rule": ">="}}
    for name, rule in cfg["checks"].items():
        out[name] = {"value": _finite(values.get(name)),
                     "limit": rule["limit"], "rule": "<="}
    return out


def correct(compared: dict) -> bool:
    return all(
        c["value"] is not None and (c["value"] <= c["limit"]
                                    if c["rule"] == "<="
                                    else c["value"] >= c["limit"])
        for c in compared.values())
