"""Benchmark harness — one module per paper table.

Prints ``name,us_per_call,derived`` CSV rows and writes a
machine-readable ``BENCH_<timestamp>.json`` (override with ``--out``)
so the perf trajectory is tracked across PRs.  Tables:
  T4 (creation O(1))      -> branch_create
  T5 (commit ∝ Δ)        -> commit_abort
  T6 (throughput)         -> throughput
  serving-scale branching -> kvbranch_bench
  vectorized fork fan-out -> fork_fanout
  serve throughput        -> serve_throughput
  sharded (tp) serving    -> shard_serve
  in-program exploration  -> explore_bench
  exploration policies    -> explore_policies
  decode fast path        -> decode_step
  fused spec verify       -> spec_verify
  HTTP/SSE front door     -> front_door
  branchlint self-host    -> lint_selfhost

  tiered KV + prefix hits -> kv_tier

``--compare <baseline.json>`` checks the run against a committed
baseline and fails on a >20% drop of any throughput-like row
(``*_per_s``, ``*speedup*``, ``*gain*``); latency rows only warn —
shared CI machines make microsecond medians too noisy to gate on.

Rows whose ``derived`` label embeds a paper target (``...<350us``) are
checked against it: violations warn by default and fail the run under
``--strict-derived`` (same noise rationale as the latency compare).
"""

from __future__ import annotations

import argparse
import json
import platform
import re
import subprocess
import sys
import time
import traceback
from pathlib import Path

_DERIVED_TARGET = re.compile(r"<\s*(\d+(?:\.\d+)?)\s*us\b")


def check_derived(records: list) -> list:
    """Rows claiming a paper latency bar in their derived label
    (``paper_T4<350us``) are held to it.  Returns violation strings."""
    out = []
    for r in records:
        m = _DERIVED_TARGET.search(r.get("derived", "") or "")
        if m and r["value"] > float(m.group(1)):
            out.append(f"derived target missed {r['module']}.{r['name']}: "
                       f"{r['value']:.1f}us > {m.group(1)}us "
                       f"({r['derived']})")
    return out


def compare(baseline_path: Path, records: list) -> list:
    """Regression check vs a committed baseline JSON.

    Throughput-like rows (``*_per_s``, ``*speedup*``, ``*gain*``) fail
    on a >20% drop; ``*_us*`` latency rows print a warning only (CI
    wall-clock noise); everything else is informational.  Returns the
    list of failure strings.
    """
    base = json.loads(Path(baseline_path).read_text())
    base_rows = {(r["module"], r["name"]): r["value"]
                 for r in base.get("rows", [])}
    failures = []
    for r in records:
        key = (r["module"], r["name"])
        name = r["name"]
        if name.startswith("_") or key not in base_rows:
            continue
        old, new = base_rows[key], r["value"]
        if old <= 0:
            continue
        label = f"{key[0]}.{name}: {old:.3f} -> {new:.3f}"
        # suffix match: "us_per_step" latency rows contain "per_s"
        if (name.endswith("per_s") or "speedup" in name
                or "gain" in name):
            if new < 0.8 * old:
                failures.append(f"throughput regression {label} "
                                f"({new / old - 1:+.0%})")
        elif "_us" in name and new > 1.5 * old:
            print(f"warning: latency grew {label}", file=sys.stderr)
    return failures


def _git_rev() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
            text=True, timeout=10).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None,
                    help="path for the JSON record (default: "
                         "BENCH_<timestamp>.json in the cwd)")
    ap.add_argument("--only", default=None,
                    help="comma-separated module names to run")
    ap.add_argument("--compare", default=None,
                    help="baseline BENCH_*.json to regression-check "
                         "against (fail on >20%% throughput drop)")
    ap.add_argument("--strict-derived", action="store_true",
                    help="fail (not just warn) when a row misses the "
                         "paper target embedded in its derived label "
                         "(e.g. paper_T4<350us)")
    args = ap.parse_args(argv)

    from benchmarks import (
        branch_create,
        commit_abort,
        decode_step,
        explore_bench,
        explore_policies,
        fork_fanout,
        front_door,
        kv_tier,
        kvbranch_bench,
        lint_selfhost,
        serve_throughput,
        shard_serve,
        spec_verify,
        throughput,
    )

    modules = [
        ("branch_create", branch_create),
        ("commit_abort", commit_abort),
        ("throughput", throughput),
        ("kvbranch_bench", kvbranch_bench),
        ("fork_fanout", fork_fanout),
        ("serve_throughput", serve_throughput),
        ("shard_serve", shard_serve),
        ("explore_bench", explore_bench),
        ("explore_policies", explore_policies),
        ("decode_step", decode_step),
        ("spec_verify", spec_verify),
        ("front_door", front_door),
        ("lint_selfhost", lint_selfhost),
        ("kv_tier", kv_tier),
    ]
    if args.only:
        keep = set(args.only.split(","))
        unknown = keep - {n for n, _ in modules}
        if unknown:
            ap.error(f"unknown benchmark module(s): {sorted(unknown)}")
        modules = [(n, m) for n, m in modules if n in keep]

    print("name,us_per_call,derived")
    records = []
    failed = []
    for name, mod in modules:
        t0 = time.time()
        try:
            for row, value, derived in mod.run():
                print(f"{name}.{row},{value:.3f},{derived}")
                records.append({"module": name, "name": row,
                                "value": value, "derived": derived})
        except Exception:
            traceback.print_exc()
            failed.append(name)
        records.append({"module": name, "name": "_wall_s",
                        "value": round(time.time() - t0, 3),
                        "derived": "harness"})

    stamp = time.strftime("%Y%m%d_%H%M%S")
    out = Path(args.out) if args.out else Path(f"BENCH_{stamp}.json")
    out.write_text(json.dumps({
        "schema": 2,
        "created": stamp,
        "git_rev": _git_rev(),
        "python": platform.python_version(),
        "platform": platform.platform(),
        "failed": failed,
        "rows": records,
    }, indent=2))
    print(f"wrote {out}")
    misses = check_derived(records)
    for line in misses:
        print(("" if args.strict_derived else "warning: ") + line,
              file=sys.stderr)
    if misses and args.strict_derived:
        failed.append("derived-targets")
    if args.compare:
        regressions = compare(Path(args.compare), records)
        for line in regressions:
            print(line, file=sys.stderr)
        if regressions:
            failed.append(f"compare:{args.compare}")
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
