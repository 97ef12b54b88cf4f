"""Readings that a cell's correctness limits are set from, on the chip.

    python bench/calibrate.py --workload <cell> --seeds 1 2 3 --seconds 20

For each seed, in one process: a whole run of the cell at its own size
and load with a short window, judged twice over the same sample of
served turns: as served, and with the control (the reference computed
in float8) in the program's place, whose judgement has to come out not
correct.  One JSON line per seed with both sets of numbers.  Each limit
in ``bench/cells/<cell>.json`` lies above the program's largest reading
and below the control's smallest.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench.run import configure_jax, log  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control", type=int, default=1,
                    help="also judge with the float8 control (0: no)")
    args = ap.parse_args(argv)

    import jax

    from bench import harness

    if jax.devices()[0].platform != "tpu":
        log("refused: no TPU")
        return 1
    configure_jax()
    spec = harness.load_spec(args.workload)
    for seed in args.seeds:
        t0 = time.perf_counter()
        run = harness.Run(spec, seed, args.seconds, False, t_start=t0,
                          log=log)
        out = run.go()
        row = {"workload": args.workload, "seed": seed,
               "correct": out["correct"],
               "program": {k: c["value"] for k, c in out["checks"].items()},
               "served_tokens": sum(len(s) for _, s, _ in run.last_sample),
               "turns": len(run.last_sample)}
        if args.control:
            ctl = run.judge(out, "fp8")
            row["control_correct"] = ctl["correct"]
            row["control"] = {k: ctl["checks"][k]["value"]
                              for k in ("logit_rel_err",
                                        "greedy_argmax_mismatches")}
        row["seconds"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
        del run
        gc.collect()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
