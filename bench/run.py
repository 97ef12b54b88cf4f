"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``<cell>`` is a workload of ``BENCHMARK.json``.  The run makes the
weights and the traffic from ``--seed``, warms up every program shape
the cell uses, measures for ``--seconds``, checks what it served
against the plain reference, and prints one JSON object as the last
line of standard output.  With ``--trace 0`` its metrics are the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics,
read from a profiler trace of a few seconds in the window's middle.

The run refuses, with a non-zero exit and no result line, when JAX
finds no TPU, fewer chips than the cell asks for, or an attention path
other than the compiled Pallas kernel.  ``--rate`` overrides the mix's
session arrival rate (used to find the system's capacity; the cells
carry their own rate).  ``--control fp8`` judges the run with the plain
reference computed in float8 in the program's place: the check's
control, which has to come out not correct.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CACHE_DIR = ROOT / ".jax_cache"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def configure_jax() -> None:
    """The persistent compilation cache at a fixed path inside the
    checkout, holding every program however quick it was to compile."""
    import jax

    CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--control", choices=("fp8",), default=None)
    args = ap.parse_args(argv)

    from bench import harness

    try:
        spec = harness.load_spec(args.workload)
    except (harness.Refused, FileNotFoundError) as err:
        log(f"refused: {err}")
        return 2
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"refused: no TPU (JAX found {devices[0].platform}); nothing "
            "was run")
        return 1
    need = int(spec["workload"]["chips"])
    if len(devices) < need:
        log(f"refused: the cell needs {need} chips, JAX found "
            f"{len(devices)}")
        return 1
    configure_jax()
    log(f"device: {devices[0].device_kind} x{len(devices)}; cell "
        f"{args.workload}, seed {args.seed}, {args.seconds} s, trace "
        f"{args.trace}")
    run = harness.Run(spec, args.seed, args.seconds, bool(args.trace),
                      t_start=T_START, rate=args.rate, control=args.control,
                      log=log)
    try:
        out = run.go()
    except harness.Refused as err:
        log(f"refused: {err}")
        return 1
    harness.emit(out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
