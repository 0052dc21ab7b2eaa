"""Helpers for the benchmark's own tests, which run on the CPU at a tiny
size: ``python -m pytest bench/tests``.  They are not among the
repository's tier-1 tests."""

import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

TINY = {
    "name": "tiny",
    "source": "a test size of the sift1m shape",
    "n_vectors": 3000, "dim": 16, "dtype": "float32", "metric": "l2",
    "pq_m": 4, "pq_nbits": 8, "top_m": 8, "top_n": 64, "top_k": 10,
    "n_posting_fraction": 0.02,
    "corpus": {"corpus_seed": 1, "points_per_cluster": 100, "spread": 0.15,
               "normalize": False},
    "reduced": {}, "assumed": {},
    "guarantees": {"answers_every_request": True, "recall_at_10_floor": 0.5,
                   "dist_gap_limit": 1e-4},
}


def tiny_tree(tmp_path, loop="closed"):
    """A checkout-shaped copy of ``bench/`` whose BENCHMARK.json runs the
    tiny configuration under a short mix of the given loop."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".traces",
                                                  "__pycache__"))
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(TINY))
    mix = ({"loop": "closed", "outstanding": 8, "k": 10, "warmup_s": 0.5}
           if loop == "closed" else
           {"loop": "open", "rate_qps": 40, "k": 10, "warmup_s": 0.5})
    (root / "bench" / "traffic" / f"tiny_{loop}.json").write_text(
        json.dumps(mix))
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json", "reduced": [],
                            "why": "test"})
    spec["workloads"].append({"name": "tiny.t", "config": "tiny",
                              "traffic": f"tiny_{loop}", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("tiny.t")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture
def cache_env(tmp_path, monkeypatch):
    """Compile cache, snapshots and traces of a test run in its own
    temporary directory."""
    import harness
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(harness, "SNAPSHOT_DIR", str(tmp_path / "snapshots"))
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "traces"))
