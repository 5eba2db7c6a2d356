"""Benchmark aggregator: one module per paper table + substrate benches.

Usage: PYTHONPATH=src python -m benchmarks.run [--only table3] [--smoke]
                                               [--json out.json]

``--smoke`` drives the eight CI smoke benches (columnar / index /
residency / ingest / fuzzy / feeds / serve / mesh) at reduced sizes
with one combined exit code —
this is what ``scripts/verify.sh`` and the CI workflow invoke, replacing
the old per-bench invocations.  Each smoke bench carries its own hard
assertions (engine equivalence, no silent index/fuzzy fallback, zero
kernel retraces on repeated queries, zero host->device bytes on warm
chains, zero torn reads / lost acks under concurrent serving), so a
nonzero exit means a real regression, not a slow machine.

``--json out.json`` additionally writes a machine-readable report:

    {"schema_version": 1,
     "smoke": true,
     "benches": {"<bench>": {"us_per_call": ..., "module": "columnar",
                             ...bench-specific fields...}, ...},
    "modules": {"<module>": {"seconds": ...}},
     "metrics": {<obs metric snapshot taken after all benches ran>},
     "failures": ["<module>: <error>", ...]}

CI archives this file per run; ``scripts/verify.sh`` asserts it parses
and contains rows from every smoke module.

Prints ``name,us_per_call,derived`` CSV (plus table-specific columns).
"""

from __future__ import annotations

import argparse
import inspect
import json
import sys

from repro import obs

from ._timing import stopwatch

SMOKE_MODULES = ("columnar", "index", "residency", "ingest", "fuzzy",
                 "feeds", "serve", "mesh")
JSON_SCHEMA_VERSION = 1


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--only", default="")
    p.add_argument("--smoke", action="store_true",
                   help="run the CI smoke benches (reduced sizes, "
                        "one exit code)")
    p.add_argument("--json", default="", metavar="PATH",
                   help="write a structured JSON report (bench rows + "
                        "obs metric snapshot) to PATH")
    args = p.parse_args()
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    from . import (columnar_bench, feeds_bench, fuzzy_bench, index_bench,
                   ingest_bench, mesh_bench, residency_bench, serve_bench,
                   step_bench, table2_storage, table3_queries,
                   table4_inserts)
    modules = {
        "table2": table2_storage,
        "table3": table3_queries,
        "table4": table4_inserts,
        "columnar": columnar_bench,
        "index": index_bench,
        "residency": residency_bench,
        "fuzzy": fuzzy_bench,
        "ingest": ingest_bench,
        "feeds": feeds_bench,
        "serve": serve_bench,
        "mesh": mesh_bench,
        "steps": step_bench,
    }
    if args.smoke:
        modules = {k: modules[k] for k in SMOKE_MODULES}
    print("name,us_per_call,derived")
    report = {"schema_version": JSON_SCHEMA_VERSION, "smoke": args.smoke,
              "benches": {}, "modules": {}, "metrics": {}, "failures": []}
    for name, mod in modules.items():
        if args.only and args.only not in name:
            continue
        kwargs = {}
        if args.smoke and "smoke" in inspect.signature(mod.run).parameters:
            kwargs["smoke"] = True
        try:
            with stopwatch() as sw:
                rows = mod.run(**kwargs)
        except Exception as e:  # noqa: BLE001
            print(f"{name},FAILED,{type(e).__name__}: {e}")
            report["failures"].append(f"{name}: {type(e).__name__}: {e}")
            continue
        report["modules"][name] = {"seconds": sw.seconds}
        for r in rows:
            main_t = r.get("us_per_call", "")
            extra = r.get("derived", "")
            for k, v in r.items():
                if k not in ("bench", "us_per_call", "derived"):
                    extra += f" | {k}={v}"
            t_str = f"{main_t:.1f}" if isinstance(main_t, float) else main_t
            print(f"{r['bench']},{t_str},{extra}")
            report["benches"][r["bench"]] = dict(
                {k: v for k, v in r.items() if k != "bench"}, module=name)
        print(f"# {name} done in {sw.seconds:.1f}s"
              f"{' (smoke)' if args.smoke else ''}", file=sys.stderr)
    if args.json:
        report["metrics"] = obs.snapshot()
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True, default=str)
            f.write("\n")
        print(f"# json report -> {args.json}", file=sys.stderr)
    sys.exit(1 if report["failures"] else 0)


if __name__ == "__main__":
    main()
