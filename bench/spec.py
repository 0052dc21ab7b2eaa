"""Finds what ``BENCHMARK.json`` names: cells, configurations, traffic
mixes and per-layer metric readers.

Everything that belongs to one configuration, traffic mix or metric sits in
a file of its own, found by name, so a new one is added as a new file plus
an entry in ``BENCHMARK.json`` and no existing file changes:

- a configuration is the JSON file its ``configs`` entry names;
- a traffic mix ``<mix>`` is ``bench/traffic/<mix>.json``;
- a per-layer metric ``<base>.<split>`` is read by ``bench/metrics/<base>.py``
  (the part of the name before the first dot), whose ``read(run)`` returns
  a number or ``None`` when the run gave it nothing to read.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)


class SpecError(ValueError):
    """BENCHMARK.json names something that is not there."""


def load(root: str = CHECKOUT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _named(entries: List[Dict], name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SpecError(f"no {what} named {name!r} in BENCHMARK.json")


def workload(spec: Dict, name: str) -> Dict:
    return _named(spec["workloads"], name, "workload")


def config(spec: Dict, name: str, root: str = CHECKOUT) -> Dict:
    entry = _named(spec["configs"], name, "config")
    with open(os.path.join(root, entry["file"])) as fh:
        cfg = json.load(fh)
    if cfg.get("name") != name:
        raise SpecError(f"{entry['file']} holds config {cfg.get('name')!r}, "
                        f"not {name!r}")
    return cfg


def traffic(name: str, root: str = CHECKOUT) -> Dict:
    path = os.path.join(root, "bench", "traffic", f"{name}.json")
    if not os.path.isfile(path):
        raise SpecError(f"no traffic mix file {path}")
    with open(path) as fh:
        return json.load(fh)


def metrics_for(spec: Dict, cell: str, trace: bool) -> List[Dict]:
    """The metrics a run of ``cell`` reports: end-to-end ones without
    ``--trace``, per-layer ones with it.  A metric with a ``workloads`` list
    belongs to the cells it lists, one without it to every cell."""
    group = spec["per_layer" if trace else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def metric_reader(name: str, root: str = CHECKOUT
                  ) -> Callable[[object], Optional[float]]:
    base = name.split(".", 1)[0]
    path = os.path.join(root, "bench", "metrics", f"{base}.py")
    if not os.path.isfile(path):
        raise SpecError(f"no reader {path} for metric {name!r}")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{base}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
