"""Serve a model at its published widths on the TPU once, and check it.

    python chip_smoke.py                # one chip: qwen2-1.5b in bf16
    python chip_smoke.py --four-chips   # one 4-chip host: granite-8b, tp=4

The quickest proof that the served path still runs on the chip.  One
process does all the work, through the entry points a user calls:
``ServeEngine`` (``attn_impl="auto"``, which must resolve to the compiled
Pallas kernel), ``BranchSession``, ``ExplorationDriver`` and
``best_of_n``.  Weights are random, made from ``--seed``.

Phases:

1. **serve** — four requests that share a 512-token prompt head, each a
   best-of-4 exploration of 32 new tokens.  This runs a dense prefill,
   prefix adoption with a suffix prefill, a vectorized fork with its
   copy-on-write, fused decode steps, commit and sibling invalidation.
   Every request must be served, no handle may stay open, and the pool
   must be whole again once the prefix cache is dropped.
2. **logits** — a prompt is forked into two branches (one greedy, one
   sampled) whose first appends fault copy-on-write inside the fused
   step.  After a few steps, each branch's paged-Pallas decode logits
   must match the logits of a dense prefill of the same tokens, which
   runs the jnp chunked attention: an independent path.  Then the same
   with ``kv_dtype="int8"`` pools against the same bf16 prefill.

Lines before the last are informational (device, compile seconds, host
step times, peak device memory): they are not a benchmark.  The last
line is ``{"ok": true, "device": {...}}``.  The script exits non-zero
and prints no such line when JAX finds no TPU, when the attention kernel
resolves to anything but the compiled Pallas kernel, or when any request
or check fails.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
if str(REPO / "src") not in sys.path:
    sys.path.insert(0, str(REPO / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.compile_cache import configure_compile_cache  # noqa: E402

# Decode-vs-prefill logit tolerances, as max |decode - prefill| over
# max |prefill| of the row.  bf16: the two paths round activations to
# bf16 at different points (the prefill casts attention probabilities to
# bf16, the kernel keeps them in f32) over every layer.  int8: per-page,
# per-kv-head symmetric quantization of K and V adds up to half a step
# of 1/127 of each page's range on top of that.
BF16_REL_TOL = 0.05
INT8_REL_TOL = 0.10

REQUESTS = 4          # explorations served in phase 1
BRANCHES = 4          # best-of-N width
NEW_TOKENS = 32       # tokens each branch decodes
HEAD = 512            # shared prompt head (32 pages of 16)
TAIL = 8              # per-request prompt tail
CHECK_STEPS = 6       # decode steps before the logits comparison
PAGE = 16
MAX_PAGES = 64        # 1024 tokens of context per sequence
NUM_PAGES = 2048


class SmokeFailure(AssertionError):
    """A phase of the smoke run did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def info(msg: str) -> None:
    print(f"[info] {msg}", flush=True)


class CompileClock:
    """Seconds JAX spent in backend compiles (a persistent-cache hit
    counts only the time to read the entry)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_: object) -> None:
        if event == self.EVENT:
            self.seconds += secs


def make_engine(model, params, *, mesh=None, kv_dtype=None,
                prefix_cache=False):
    from repro.runtime.serve_loop import ServeEngine

    return ServeEngine(model, params, num_pages=NUM_PAGES, page_size=PAGE,
                       max_pages_per_seq=MAX_PAGES, attn_impl="auto",
                       kv_dtype=kv_dtype, mesh=mesh,
                       prefix_cache=prefix_cache)


def check_pool_whole(engine) -> None:
    engine.kv.clear_prefix_cache()
    st = engine.kv.stats()
    check(st["pages_free"] == st["pages_total"],
          f"pool not returned: {st['pages_free']} of {st['pages_total']} "
          "pages free")
    check(st["sequences_live"] == 0,
          f"{st['sequences_live']} sequences still live")


def serve_requests(engine, *, seed: int) -> dict:
    """Phase 1: best-of-N explorations over one session."""
    from repro.api import BranchSession
    from repro.explore_ctx import ExplorationDriver, best_of_n

    requests, branches, tokens = REQUESTS, BRANCHES, NEW_TOKENS
    rng = np.random.default_rng(seed)
    vocab = engine.cfg.vocab_size
    shared = [int(t) for t in rng.integers(1, vocab, HEAD)]
    prompts = [shared + [int(t) for t in rng.integers(1, vocab, TAIL)]
               for _ in range(requests)]
    session = BranchSession(engine, max_batch=requests * branches,
                            seed=seed)
    driver = ExplorationDriver(session)
    exps = [driver.explore(p, max_new_tokens=tokens + 1, policy=best_of_n,
                           n=branches, tokens=tokens, temperature=1.0,
                           name=f"request-{i}")
            for i, p in enumerate(prompts)]
    driver.run()
    for i, exp in enumerate(exps):
        check(exp.error is None, f"request {i} failed: {exp.error!r}")
        res = exp.result
        check(res is not None and res.committed,
              f"request {i} committed no branch")
        check(res.stats.get("branches") == branches,
              f"request {i} explored {res.stats.get('branches')} branches "
              f"of {branches} (degraded: {res.stats.get('degraded')})")
        check(len(res.generated) == tokens,
              f"request {i} generated {len(res.generated)} of {tokens}")
    tree = session.tree()
    check(tree["handles"]["open"] == 0,
          f"{tree['handles']['open']} handles left open")
    st = engine.stats()
    check(st["prefill_dispatches"] == requests,
          f"{st['prefill_dispatches']} prefills for {requests} requests")
    m = engine.obs.metrics.snapshot()
    hits = m["counters"].get("kv.prefix_hits", 0)
    check(hits == requests - 1,
          f"{hits} prefix-cache hits for {requests} requests sharing a head")
    session.close()
    check_pool_whole(engine)
    return {"explorations": len(exps),
            "tokens_decoded": m["counters"]["engine.tokens_decoded"],
            "cow_faults": st["cow_faults"], "prefix_hits": hits}


def check_decode_logits(engine, prefill, prompt, *, rel_tol: float,
                        key: jax.Array) -> float:
    """Phase 2: paged decode logits vs a dense prefill of the same tokens.

    Returns the worst relative error seen.
    """
    sid = engine.add_request(prompt)
    engine.decode([sid])
    kids = engine.fork(sid, 2)          # lazy: the first append faults
    for i in range(CHECK_STEPS):
        engine.decode(kids, greedy=[True, False], temperature=1.0,
                      key=jax.random.fold_in(key, i))
    got = np.asarray(engine.last_logits, np.float32)      # [2, V]
    worst = 0.0
    for row, kid in enumerate(kids):
        # the last token appended is the one these logits produced
        context = engine.tokens(kid)[:-1]
        ref, _ = prefill(engine.params,
                         jax.numpy.asarray(context, jax.numpy.int32)[None])
        ref = np.asarray(ref[0, -1], np.float32)
        check(bool(np.isfinite(got[row]).all()),
              f"branch {row}: non-finite decode logits")
        err = float(np.abs(got[row] - ref).max() / np.abs(ref).max())
        worst = max(worst, err)
        check(err <= rel_tol,
              f"branch {row}: decode logits off the dense prefill by "
              f"{err:.4g} of the logit range (tolerance {rel_tol})")
        top2 = np.sort(ref)[-2:]
        if top2[1] - top2[0] > 2 * np.abs(got[row] - ref).max():
            check(int(got[row].argmax()) == int(ref.argmax()),
                  f"branch {row}: argmax differs at a clear margin")
    engine.commit(kids[0])              # first commit wins: kid 1 goes
    engine.release(sid)
    check_pool_whole(engine)
    return worst


def run(cfg, *, seed: int, mesh=None) -> dict:
    """Every phase on one configuration; raises :class:`SmokeFailure`."""
    from repro.models.model import Model
    from repro.runtime.serve_loop import init_serve_params

    clock = CompileClock()
    out: dict = {}
    model = Model(cfg, remat=False)
    t0 = time.perf_counter()
    params = init_serve_params(model, jax.random.PRNGKey(seed), mesh)
    jax.block_until_ready(params)
    info(f"params: {sum(x.size for x in jax.tree_util.tree_leaves(params)):,}"
         f" in {cfg.dtype}, made in {time.perf_counter() - t0:.1f} s")

    engine = make_engine(model, params, mesh=mesh, prefix_cache=True)
    check(engine.attn_impl == "pallas",
          f"attention resolved to {engine.attn_impl!r}, not 'pallas'")
    c0, t0 = clock.seconds, time.perf_counter()
    out["serve"] = serve_requests(engine, seed=seed)
    out["serve"]["wall_s"] = round(time.perf_counter() - t0, 3)
    out["serve"]["compile_s"] = round(clock.seconds - c0, 3)
    dec = engine.obs.metrics.histogram("engine.decode_step_us")
    # the histogram's percentiles are bucket bounds; the mean is exact
    info(f"serve: {out['serve']}; host decode step min "
         f"{dec.min / 1e3:.3f} ms, mean {dec.sum / max(dec.count, 1) / 1e3:.3f}"
         f" ms over {dec.count} steps, compiles included (informational)")

    prefill = jax.jit(model.prefill)
    rng = np.random.default_rng(seed + 1)
    prompt = [int(t) for t in rng.integers(1, cfg.vocab_size, HEAD + TAIL)]
    key = jax.random.PRNGKey(seed + 2)
    c0, t0 = clock.seconds, time.perf_counter()
    out["logits_rel_err"] = check_decode_logits(
        engine, prefill, prompt, rel_tol=BF16_REL_TOL, key=key)
    info(f"logits ({cfg.dtype} pages): worst relative error "
         f"{out['logits_rel_err']:.4g} <= {BF16_REL_TOL}; "
         f"{time.perf_counter() - t0:.1f} s, "
         f"compile {clock.seconds - c0:.1f} s")
    del engine
    q8 = make_engine(model, params, mesh=mesh, kv_dtype="int8")
    check(q8.attn_impl == "pallas",
          f"int8 attention resolved to {q8.attn_impl!r}")
    c0, t0 = clock.seconds, time.perf_counter()
    out["int8_logits_rel_err"] = check_decode_logits(
        q8, prefill, prompt, rel_tol=INT8_REL_TOL, key=key)
    info(f"logits (int8 pages): worst relative error "
         f"{out['int8_logits_rel_err']:.4g} <= {INT8_REL_TOL}; "
         f"{time.perf_counter() - t0:.1f} s, "
         f"compile {clock.seconds - c0:.1f} s")
    out["compile_s"] = round(clock.seconds, 3)
    return out


def published(arch: str):
    """The config at its published widths, in its own dtype (bf16)."""
    from repro.configs import get_config

    cfg = get_config(arch)
    check(cfg.dtype == "bfloat16", f"{arch} dtype is {cfg.dtype}")
    return cfg


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="serve granite-8b tensor-parallel over 4 chips "
                         "(and run nothing else)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    cache = configure_compile_cache()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing "
              "was run", file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return 1
    info(f"device: {dev.device_kind} x{len(devices)}; compile cache {cache}")

    if args.four_chips:
        from repro.distributed.mesh import serving_mesh

        cfg, mesh = published("granite-8b"), serving_mesh(4)
    else:
        cfg, mesh = published("qwen2-1.5b"), None
    info(f"model: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
         f"heads={cfg.num_heads} kv_heads={cfg.num_kv_heads} "
         f"head_dim={cfg.head_dim} vocab={cfg.vocab_size} "
         f"tp={1 if mesh is None else mesh.size}")
    out = run(cfg, seed=args.seed, mesh=mesh)
    info(f"compile seconds, whole run: {out['compile_s']}")
    for i, d in enumerate(devices[:need]):
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            info(f"device {i} peak bytes in use: "
                 f"{stats['peak_bytes_in_use']:,}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
