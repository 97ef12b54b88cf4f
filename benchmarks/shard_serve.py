"""Sharded-serving throughput: the decode hot loop on a tp mesh.

Proves the DESIGN §11 scaling claims on forced-host-device CPU meshes
(the same harness the distributed tests use):

* per-step decode latency and tokens/s for tp=1 vs tp=2 through a
  branched continuous batch;
* the fork/commit cost model is mesh-invariant — one vectorized
  ``branch()`` fan-out still services its CoW plan in exactly ONE fused
  ``_copy_pages`` dispatch under ``shard_map`` (asserted, then
  reported);
* tp=2 tokens are bit-identical to tp=1 (asserted in the subprocess).

Each tp width runs in a subprocess because
``--xla_force_host_platform_device_count`` must be set before JAX
initializes — the parent process (and every other benchmark in the
``run.py`` sweep) keeps seeing the normal device set.  CPU "shards" of
one physical core measure dispatch/partitioning overhead, not speedup;
the derived column carries the dispatch counts that must stay flat.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path
from typing import List, Tuple

_WORKER = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%(tp)d"
import dataclasses, time
import jax
from repro.configs import get_config
from repro.models.model import Model
from repro.runtime.scheduler import Scheduler, SchedulerConfig
from repro.runtime.serve_loop import ServeEngine

tp = %(tp)d
cfg = dataclasses.replace(get_config("paper-agentic"), dtype="float32")
model = Model(cfg, attn_chunk=8, remat=False)
params = model.init(jax.random.PRNGKey(0))
eng = ServeEngine(model, params, num_pages=512, page_size=16,
                  max_pages_per_seq=24, tp=tp)
sched = Scheduler(eng, SchedulerConfig(max_batch=8))
rids = [sched.submit(list(range(3 + r, 11 + r)), max_new_tokens=32)
        for r in range(2)]
sched.admit()

# vectorized fan-out: 4 branches per request, ONE fused CoW dispatch each
cow0 = eng.cow_dispatches
branches = []
for rid in rids:
    branches.extend(sched.fork(sched.seq_of(rid), 4, eager_cow=True))
fork_dispatches = eng.cow_dispatches - cow0
assert fork_dispatches == len(rids), (fork_dispatches, len(rids))

tokens = [eng.decode(branches)]          # untimed: compile
cow_before = eng.cow_dispatches
t0 = time.perf_counter()
steps = 8
for _ in range(steps):
    tokens.append(eng.decode(branches))
dt = time.perf_counter() - t0
json.dump({
    "tp": tp,
    "devices": len(jax.devices()),
    "us_per_step": dt / steps * 1e6,
    "tokens_per_s": len(branches) * steps / dt,
    "fork_cow_dispatches_per_fanout": fork_dispatches / len(rids),
    "decode_cow_dispatches": eng.cow_dispatches - cow_before,
    "tokens": tokens,
}, sys.stdout)
"""


def _run_tp(tp: int) -> dict:
    repo = Path(__file__).resolve().parent.parent
    # a CPU-mesh check by design: the child never competes for a TPU
    # that this host (or the parent process) may hold
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = str(repo / "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    r = subprocess.run(
        [sys.executable, "-c", _WORKER % {"tp": tp}],
        capture_output=True, text=True, env=env, timeout=900)
    if r.returncode != 0:
        raise RuntimeError(f"tp={tp} worker failed:\n{r.stderr[-4000:]}")
    return json.loads(r.stdout)


def run() -> List[Tuple[str, float, str]]:
    rows: List[Tuple[str, float, str]] = []
    results = {tp: _run_tp(tp) for tp in (1, 2)}
    # the acceptance property: same seed => same tokens across meshes
    assert results[1]["tokens"] == results[2]["tokens"], \
        "tp=2 tokens diverged from tp=1"
    for tp, res in results.items():
        rows.append((f"tp{tp}_us_per_step", res["us_per_step"],
                     f"{res['devices']}dev"))
        rows.append((f"tp{tp}_tokens_per_s", res["tokens_per_s"],
                     "8way_branched"))
        rows.append((f"tp{tp}_fork_cow_dispatches",
                     res["fork_cow_dispatches_per_fanout"],
                     "per_4way_fanout_fused"))
    rows.append(("tp_token_identical", 1.0, "tp1_vs_tp2"))
    return rows
