"""Puts the benchmark's modules and the program on the path, and builds a
throwaway checkout root holding the benchmark plus test-only cells."""
from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
DATA = os.path.join(BENCH, "tests", "data")
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, BENCH)

TINY_CELLS = ("tiny.kivi2.tinymix", "tiny.full.tinymix")


def make_root(dst: str) -> str:
    """A checkout root with a copy of bench/ and a BENCHMARK.json whose
    cells are the tiny test cells (plus the real ones)."""
    shutil.copytree(BENCH, os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    b = os.path.join(dst, "bench")
    shutil.copy(os.path.join(DATA, "tiny.json"),
                os.path.join(b, "configs", "tiny.json"))
    shutil.copy(os.path.join(DATA, "tinymix.json"),
                os.path.join(b, "traffic", "tinymix.json"))
    for pol in ("kivi2", "full"):
        shutil.copy(os.path.join(DATA, f"tiny.{pol}.json"),
                    os.path.join(b, "cells", f"tiny.{pol}.tinymix.json"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    for name in TINY_CELLS:
        bench["workloads"].append({"name": name, "config": "tiny",
                                   "traffic": "tinymix", "chips": 1,
                                   "why": "test"})
        for m in bench["per_layer"]:
            if "workloads" in m:
                m["workloads"].append(name)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return dst


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("checkout")))
