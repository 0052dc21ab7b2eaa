"""Whole runs of the harness on the CPU at a tiny size, with the look for
a chip skipped: discovery of new files, the result line, refusals, and
faults planted under the timed path."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import harness
from conftest import BENCH, tiny_tree

RUN = os.path.join(BENCH, "run.py")


def _run(root, trace=False, seconds=2.0):
    return harness.run_cell("tiny.t", 2**33 + 3, seconds, trace,
                            t_start=time.perf_counter(), root=str(root),
                            require_chip=False)


def test_new_config_mix_and_metric_are_found_by_name(tmp_path, cache_env):
    """A configuration, a traffic mix and a per-layer metric, each added
    as a new file plus a BENCHMARK.json entry, run with no existing file
    edited."""
    root = tiny_tree(tmp_path, loop="open")
    (root / "bench" / "metrics" / "answered_share.py").write_text(
        "def read(run):\n"
        "    return len(run.answers_in_window) / max(len(run.answers), 1)\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["per_layer"].append({
        "name": "answered_share.open", "unit": "fraction",
        "better": "higher", "source": "host_clock", "layer": "service",
        "moves": "latency_p50_ms", "workloads": ["tiny.t"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    out = _run(root, trace=True)
    assert out["correct"], out["checks"]
    assert out["attempted"] == 80 and out["failed"] == 0
    assert 0 < out["metrics"]["answered_share.open"]["value"] <= 1
    assert out["metrics"]["ssd_ios_per_query.sat"]["unit"] == "ios/query"
    assert out["metrics"]["latency_p99_ms.sat"]["value"] > 0
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(out)[-1] == "checks"


def test_end_to_end_result_line(tmp_path, cache_env):
    out = _run(tiny_tree(tmp_path, loop="closed"))
    assert out["correct"], out["checks"]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(m) == {"qps", "latency_p50_ms", "recall_at_10", "setup_s"}
    assert all(v > 0 for v in m.values())
    assert out["device"] == {"platform": "cpu", "kind": "cpu", "count": 1,
                             "memory_peak_bytes": 0}


def test_a_second_run_loads_the_index_it_saved(tmp_path, cache_env,
                                               capsys):
    root = tiny_tree(tmp_path, loop="closed")
    first = _run(root)
    assert "index: built" in capsys.readouterr().out
    second = _run(root)
    assert "index: loaded" in capsys.readouterr().out
    assert first["correct"] and second["correct"]
    assert (first["index"], second["index"]) == ("built", "loaded")
    assert list(second)[-1] == "checks"


def test_changed_program_source_builds_anew(tmp_path, cache_env,
                                            monkeypatch):
    root = tiny_tree(tmp_path, loop="closed")
    assert _run(root, seconds=1.0)["index"] == "built"
    monkeypatch.setattr(harness, "program_digest", lambda: "other code")
    assert _run(root, seconds=1.0)["index"] == "built"
    assert _run(root, seconds=1.0)["index"] == "loaded"


def _shift_ids(monkeypatch):
    """An answer altered where it is produced: the re-rank names the row
    next to each one it scored."""
    import repro.core.executor as ex
    real = ex.heuristic_rerank

    def shifted(*a, **kw):
        r = real(*a, **kw)
        r.ids = (r.ids + 1) % 3000
        return r
    monkeypatch.setattr(ex, "heuristic_rerank", shifted)


def _half_batch(monkeypatch):
    """Half of each scan window left out: its queries are answered from
    the other half's."""
    import repro.core.executor as ex
    real = ex.QueryExecutor._dispatch

    def halved(self, queries, plans):
        h = (len(queries) + 1) // 2
        reps = np.concatenate([queries[:h]] * 2)[:len(queries)]
        return real(self, reps, plans)
    monkeypatch.setattr(ex.QueryExecutor, "_dispatch", halved)


@pytest.mark.parametrize("fault", [_shift_ids, _half_batch])
def test_a_broken_timed_path_is_not_correct(tmp_path, cache_env,
                                            monkeypatch, fault):
    fault(monkeypatch)
    out = _run(tiny_tree(tmp_path, loop="closed"))
    assert not out["correct"]
    assert out["checks"]["dist_gap"]["value"] > \
        out["checks"]["dist_gap"]["limit"]


def test_a_query_that_collects_no_candidate_is_not_correct(
        tmp_path, cache_env, monkeypatch):
    """The program's fault at N = 1M: the graph search returns only empty
    posting lists, and the request is answered with no ids at all."""
    from repro.core.segments import IndexView
    real = IndexView.collect_candidates
    calls = [0]

    def sometimes_none(self, query, top_m, **kw):
        ids, pre = real(self, query, top_m, **kw)
        calls[0] += 1
        if calls[0] % 97 == 0:
            return ids[:0], pre[:0]
        return ids, pre
    monkeypatch.setattr(IndexView, "collect_candidates", sometimes_none)
    out = _run(tiny_tree(tmp_path, loop="closed"))
    assert not out["correct"]
    assert out["checks"]["malformed"]["value"] > 0
    assert out["checks"]["unanswered"]["value"] == 0


def test_no_accelerator_means_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, RUN, "--workload", "sift250k.sat",
                        "--seed", "1", "--seconds", "1"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == 1
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


def test_without_the_program_beside_it_there_is_no_result(tmp_path):
    """A directory holding only BENCHMARK.json and bench/."""
    root = tiny_tree(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(root / "bench" / "run.py"),
                        "--workload", "tiny.t", "--seed", "1",
                        "--seconds", "1"],
                       capture_output=True, text=True, env=env, timeout=300,
                       cwd=str(root))
    assert p.returncode == 2
    assert p.stdout.strip() == ""
