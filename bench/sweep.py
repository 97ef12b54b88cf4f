"""Find the highest arrival rate an open-loop cell sustains, on the chip.

    python bench/sweep.py --workload <cell> --seed <n> --seconds 40 \
        --rates 0.3 0.4 0.5 0.6

Runs ``bench/run.py`` once per session arrival rate (each a process of
its own, the cell's files unchanged) and prints, per rate, the turns
due and completed in the window, the median time to first token and
whether the run was correct.  A rate is sustained when the run was
correct, the window completed at least ``--keep`` of the turns due in
it and its median time to first token stayed within ``--ttft-growth``
times the lowest rate's.  The last line is the highest sustained rate
and four fifths of it, the rate an open-loop cell below capacity is
given.  Rates above the first one not sustained are not run.
"""

from __future__ import annotations

import argparse
import json
import re
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--log-dir", default=None,
                    help="write each rate's standard error here")
    ap.add_argument("--keep", type=float, default=0.8)
    ap.add_argument("--ttft-growth", type=float, default=2.0)
    args = ap.parse_args(argv)
    rows = []
    for rate in sorted(args.rates):
        p = subprocess.run(
            [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
             args.workload, "--seed", str(args.seed), "--seconds",
             str(args.seconds), "--trace", "0", "--rate", str(rate)],
            cwd=ROOT, capture_output=True, text=True)
        if args.log_dir:
            d = Path(args.log_dir)
            d.mkdir(parents=True, exist_ok=True)
            (d / f"sweep_{rate}.err").write_text(p.stderr)
        checks = {}
        for line in p.stderr.splitlines():
            c = re.match(r"check (\S+): (\S+) \(limit (\S+)\)", line)
            if c and float(c.group(2)) > float(c.group(3)):
                checks[c.group(1)] = float(c.group(2))
        m = re.search(r"window: (\d+) turns due, (\d+) completed",
                      p.stderr)
        out = {}
        if p.returncode == 0 and p.stdout.strip():
            out = json.loads(p.stdout.strip().splitlines()[-1])
        row = {"rate": rate, "rc": p.returncode,
               "due": int(m.group(1)) if m else None,
               "completed": int(m.group(2)) if m else None,
               "ttft_p50_ms": out.get("metrics", {}).get(
                   "ttft_p50_ms", {}).get("value"),
               "correct": out.get("correct"), "failed_checks": checks}
        base = rows[0]["ttft_p50_ms"] if rows else row["ttft_p50_ms"]
        row["sustained"] = bool(
            row["rc"] == 0 and row["correct"] and row["due"]
            and row["ttft_p50_ms"] is not None
            and row["completed"] >= args.keep * row["due"]
            and row["ttft_p50_ms"] <= args.ttft_growth * base)
        rows.append(row)
        print(json.dumps(row), flush=True)
        if not row["sustained"]:
            break                     # the rates above it are not tried
    knee = None
    for r in rows:
        if not r["sustained"]:
            break
        knee = r["rate"]
    print(json.dumps({"knee": knee,
                      "cell_rate": None if knee is None
                      else round(0.8 * knee, 3)}), flush=True)
    return 0 if knee is not None else 1


if __name__ == "__main__":
    raise SystemExit(main())
