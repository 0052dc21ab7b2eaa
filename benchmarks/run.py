"""Benchmark harness: one module per paper table/figure.  Prints
``name,us_per_call,derived`` CSV (harness contract).

``--persist`` additionally writes one ``BENCH_<area>.json`` artifact per
area at the repo root and compares each row's ``us_per_call`` against the
previous artifact: a row slower than ``BENCH_REGRESSION_FACTOR`` (default
1.6x) times its previous value fails the run — the per-PR perf ratchet
scripts/check.sh's ``kernels`` target enforces in CI.

``--selftest`` runs ``scripts/check.sh smoke`` (pytest on the CPU) before
this process imports JAX: a parent holding the chip would starve the
child."""

import argparse
import importlib
import json
import os
import subprocess
import sys
import time
import traceback

# allow `python -m benchmarks.run` / `python benchmarks/run.py` without a
# PYTHONPATH=src export
_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

# figure name -> benchmarks module, imported only after the selftest
ALL = {
    "fig3": "fig3_spann_scaling",
    "fig4": "fig4_combos",
    "fig5": "fig5_rerank",
    "fig9": "fig9_throughput_latency",
    "fig10": "fig10_accuracy_levels",
    "fig11": "fig11_thread_scaling",
    "fig12": "fig12_ablation",
    "tab2_tab3": "tab2_tab3_cost",
    "kernels": "kernels_bench",
    "beyond": "beyond_fused_batch",
}


def _persist_and_compare(area: str, rows, root: str,
                         factor: float) -> list:
    """Write BENCH_<area>.json and diff against the previous artifact.
    Returns a list of regression strings (empty = pass).  Rows that are
    new or removed never fail — only a matched name that got slower than
    ``factor`` x its previous us_per_call does."""
    path = os.path.join(root, f"BENCH_{area}.json")
    prev = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                prev = {r["name"]: r for r in json.load(f)["rows"]}
        except (json.JSONDecodeError, KeyError, TypeError):
            prev = {}                     # unreadable artifact: rewrite it
    regressions = []
    for r in rows:
        old = prev.get(r["name"])
        if old and old.get("us_per_call"):
            ratio = r["us_per_call"] / old["us_per_call"]
            if ratio > factor:
                regressions.append(
                    f"{r['name']}: {old['us_per_call']:.1f} -> "
                    f"{r['us_per_call']:.1f} us/call ({ratio:.2f}x, "
                    f"threshold {factor}x)")
    if not regressions:       # keep the old baseline when the run regressed
        with open(path, "w") as f:
            json.dump({"area": area,
                       "rows": [{"name": r["name"],
                                 "us_per_call": r["us_per_call"],
                                 "derived": str(r["derived"])}
                                for r in rows]}, f, indent=1)
    return regressions


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", choices=sorted(ALL),
                    help="run a subset of figures")
    ap.add_argument("--selftest", action="store_true",
                    help="run the fast correctness smoke (scripts/check.sh "
                         "smoke); add --only to continue to those figures "
                         "afterwards, else only a selftest row is emitted")
    ap.add_argument("--persist", action="store_true",
                    help="write BENCH_<area>.json per area and fail on "
                         "rows slower than BENCH_REGRESSION_FACTOR "
                         "(default 1.6) x the previous artifact")
    args = ap.parse_args()
    if args.selftest:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        rc = subprocess.run(
            ["bash", os.path.join(root, "scripts", "check.sh"), "smoke"],
            cwd=root, env={**os.environ, "JAX_PLATFORMS": "cpu"}).returncode
        if rc != 0:
            print(f"# selftest FAILED (rc={rc})", file=sys.stderr)
            sys.exit(rc)
        print("# selftest passed", file=sys.stderr)
        if not args.only:                 # keep the CSV contract
            print("name,us_per_call,derived")
            print("selftest,0.0,scripts/check.sh smoke passed")
            return
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    names = args.only or list(ALL)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    factor = float(os.environ.get("BENCH_REGRESSION_FACTOR", "1.6"))
    print("name,us_per_call,derived")
    ok = True
    regressions = []
    for name in names:
        t0 = time.time()
        try:
            rows = importlib.import_module(f"benchmarks.{ALL[name]}").run()
            for r in rows:
                derived = str(r["derived"]).replace(",", ";")
                print(f"{r['name']},{r['us_per_call']:.1f},{derived}")
            if args.persist:
                regressions += _persist_and_compare(name, rows, root, factor)
        except Exception:  # noqa: BLE001
            ok = False
            print(f"{name},0,ERROR", file=sys.stdout)
            traceback.print_exc()
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
    for msg in regressions:
        print(f"# PERF REGRESSION: {msg}", file=sys.stderr)
    if not ok or regressions:
        sys.exit(1)


if __name__ == "__main__":
    main()
