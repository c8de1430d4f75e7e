"""Shared set-up of the benchmark's CPU tests: import paths and the
committed benchmark cut to a size a test run holds."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """The committed benchmark with its configurations cut to 8x8 images
    and a batch of 8."""
    root = tmp_path_factory.mktemp("bench_tiny")
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", root / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    for f in (root / "bench" / "configs").glob("*.json"):
        cfg = json.loads(f.read_text())
        cfg.update(image=[cfg["image"][0], 8, 8], batch=8)
        f.write_text(json.dumps(cfg))
    return root
