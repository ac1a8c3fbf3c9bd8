"""Tests of the benchmark itself, on the CPU at small sizes:

    python -m pytest bench/tests

``tiny_root`` is a copy of the benchmark (``BENCHMARK.json`` and
``bench/``) whose configurations are cut to a size a test can hold; runs
driven there use the same harness code, found by the same names.
"""
from __future__ import annotations

import json
import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(REPO, "src"), REPO):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

TINY = {"pagerank-kron19": {"scale": 10}}


def copy_benchmark(dest: str, sizes=TINY) -> str:
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(dest, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dest)
    bench = json.load(open(os.path.join(dest, "BENCHMARK.json")))
    for conf in bench["configs"]:
        path = os.path.join(dest, conf["file"])
        cfg = json.load(open(path))
        cfg.update(sizes.get(conf["name"], {}))
        json.dump(cfg, open(path, "w"), indent=1)
    return dest


@pytest.fixture
def tiny_root(tmp_path):
    return copy_benchmark(str(tmp_path))
