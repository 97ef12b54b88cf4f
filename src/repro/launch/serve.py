"""Serving entry point: the ``repro.api`` surface end to end.

Demo mode pushes a stream of requests through the exploration driver
over one :class:`~repro.api.BranchSession`: every prompt runs a
concurrent best-of-N policy (vectorized ``branch()`` through page-budget
admission, decode branches in the shared continuous batch, score,
first-commit-wins commit; graceful unforked degradation under page
pressure), then prints the session's procfs-style ``tree()`` view::

    python -m repro.launch.serve --arch paper-agentic --branches 3

The model runs at its published widths and dtype (``--arch qwen2-1.5b``
is the full 1.5B model in bf16).  CPU runs of a large arch pass
``--reduced --dtype float32`` for a tiny same-family stand-in.  The
exit code is non-zero when any request was not served.

``--tp N`` runs the decode hot loop tensor-parallel over an N-device
serving mesh (DESIGN §11) — weights and KV pages shard, branch
bookkeeping stays host-side, and the served tokens are identical to
``--tp 1`` for the same seed.  On a CPU-only host, force devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=N``.

``--serve host:port`` starts the multi-tenant HTTP/SSE front door
(DESIGN §14) instead of the demo: one engine loop serves every tenant's
``/v1/generate`` and ``/v1/explore`` traffic until SIGINT/SIGTERM, then
drains gracefully (in-flight decodes finish; parked reservations are
evicted) and exits 0.  ``--tenants name:max_concurrent:priority,...``
registers tenant classes::

    python -m repro.launch.serve --serve 127.0.0.1:8777 \\
        --tenants vip:16:3,batch:32:1
"""

from __future__ import annotations

import argparse
import dataclasses

import jax
import numpy as np

from repro.launch.compile_cache import configure_compile_cache


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-agentic")
    ap.add_argument("--branches", type=int, default=3)
    ap.add_argument("--tokens", type=int, default=8)
    ap.add_argument("--requests", type=int, default=2)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=2.0)
    ap.add_argument("--tp", type=int, default=None,
                    help="tensor-parallel width of the serving mesh "
                         "(default: single-device)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record per-branch lifecycle spans and write a "
                         "Chrome/Perfetto trace.json here on exit "
                         "(also prints the one-screen metrics summary)")
    ap.add_argument("--serve", default=None, metavar="HOST:PORT",
                    help="run the multi-tenant HTTP/SSE front door "
                         "instead of the demo (SIGINT/SIGTERM drains "
                         "gracefully)")
    ap.add_argument("--tenants", default=None,
                    metavar="NAME:MAX_CONCURRENT:PRIORITY,...",
                    help="tenant classes for --serve (unknown tenants "
                         "get the default class)")
    ap.add_argument("--num-pages", type=int, default=1024,
                    help="KV page-pool size (default 1024)")
    ap.add_argument("--reduced", action="store_true",
                    help="serve a tiny same-family stand-in of --arch "
                         "(CPU runs); default is the published widths")
    ap.add_argument("--dtype", default=None,
                    help="parameter and activation dtype (CPU runs pass "
                         "float32); default is the config's own")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable cross-request KV prefix sharing "
                         "(on by default: identical prompt prefixes "
                         "share read-only CoW pages, so best-of-N from "
                         "N users costs one prefill)")
    args = ap.parse_args(argv)
    configure_compile_cache()

    from repro.api import BranchSession
    from repro.configs import get_config, reduced
    from repro.distributed.mesh import serving_mesh
    from repro.explore_ctx import ExplorationDriver, best_of_n
    from repro.models.model import Model
    from repro.obs import Observability
    from repro.runtime.serve_loop import ServeEngine, init_serve_params

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if args.dtype:
        cfg = dataclasses.replace(cfg, dtype=args.dtype)
    model = Model(cfg, attn_chunk=8, remat=False)
    mesh = serving_mesh(args.tp) if args.tp else None
    params = init_serve_params(model, jax.random.PRNGKey(0), mesh)
    engine = ServeEngine(model, params, num_pages=args.num_pages,
                         page_size=8, max_pages_per_seq=64, mesh=mesh,
                         prefix_cache=not args.no_prefix_cache,
                         obs=Observability(trace=args.trace is not None))
    session = BranchSession(engine, max_batch=args.max_batch, seed=1)
    if session.tp > 1:
        print(f"serving mesh: tp={session.tp} over "
              f"{len(jax.devices())} devices")
    if args.serve:
        return _serve_front_door(session, args)
    driver = ExplorationDriver(session)

    prompts = {}
    for r in range(args.requests):
        prompt = [int(t) for t in np.random.default_rng(r).integers(
            1, cfg.vocab_size, size=6)]
        exp = driver.explore(prompt, max_new_tokens=args.tokens + 1,
                             policy=best_of_n, n=args.branches,
                             tokens=args.tokens,
                             temperature=args.temperature,
                             name=f"request-{r}")
        prompts[exp] = prompt
    # an infeasible request fails only its own exploration: report it
    # per-request (as the pre-driver demo did) and serve the rest
    driver.run(raise_errors=False)

    failed = 0
    for r, (exp, prompt) in enumerate(prompts.items()):
        if exp.error is not None:
            print(f"request {r}: not served ({exp.error})")
            failed += 1
            continue
        res = exp.result
        scores = [f"{s:.1f}" for s in res.stats.get("scores", [])]
        note = " (degraded: page pressure)" if res.stats.get("degraded") \
            else ""
        print(f"request {r}: prompt {prompt} -> {res.generated} "
              f"(best of {res.stats.get('branches', 0)}, "
              f"scores {scores}){note}")
    print("session tree (procfs view):")
    print(session.format_tree(metrics=args.trace is not None))
    if args.trace:
        session.trace(args.trace)
        print(f"wrote {args.trace} — open at https://ui.perfetto.dev")
    return 1 if failed else 0


def _parse_tenants(spec):
    """``name:max_concurrent:priority,...`` → TenantConfig list."""
    from repro.server import TenantConfig

    out = []
    for part in (spec or "").split(","):
        part = part.strip()
        if not part:
            continue
        fields = part.split(":")
        name = fields[0]
        max_conc = int(fields[1]) if len(fields) > 1 else 16
        priority = int(fields[2]) if len(fields) > 2 else 1
        out.append(TenantConfig(name, max_concurrent=max_conc,
                                priority=priority))
    return out


def _serve_front_door(session, args) -> int:
    import asyncio
    import signal

    from repro.server import FrontDoor

    host, _, port = args.serve.rpartition(":")
    host = host or "127.0.0.1"
    fd = FrontDoor(session, _parse_tenants(args.tenants))

    async def run() -> None:
        server = await fd.serve(host, int(port))
        addr = server.sockets[0].getsockname()
        print(f"serving on http://{addr[0]}:{addr[1]} "
              f"(tenants: {[t.name for t in fd.tenancy.tenants()]})",
              flush=True)
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            loop.add_signal_handler(sig, stop.set)
        await stop.wait()
        print("draining...", flush=True)
        stats = await fd.shutdown(drain=True)
        print(f"drained cleanly ({stats['evicted']} parked/stale "
              "evicted)", flush=True)
        if args.trace:
            session.trace(args.trace)
            print(f"wrote {args.trace}", flush=True)

    asyncio.run(run())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
