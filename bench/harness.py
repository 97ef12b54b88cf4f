"""One run of one cell: set-up, warm-up, the measured window, the check.

Everything a cell needs is found by name from ``BENCHMARK.json``:
``bench/configs/<config>.json`` (the deployment: model sizes and the
engine's pool and batch), ``bench/families/<family>.py`` (the model
family the configuration names: its weights, plain reference and work
counts), ``bench/traffic/<traffic>.json`` (the mix, read by
:mod:`bench.generator`), ``bench/cells/<cell>.json`` (the limits of the
correctness check) and ``bench/metrics/<metric>.py`` (one reader per
per-layer metric).

The window drives ``ExplorationDriver.step()``; under it run
``BranchSession``, the ``Scheduler`` and the ``ServeEngine`` (prefill
and suffix prefill, the fused decode step, the paged-attention kernel).
The harness observes the layers through wrappers it sets on instance
attributes of the objects it built — no file of the program changes.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import json
import math
import shutil
import sys
import time
from contextlib import nullcontext
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from bench import generator, trace as T, work

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
PROGRAM_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
TRACE_SECONDS = 3.0          # the traced stretch, in the window's middle,
TRACE_MAX_EXTRA_S = 25.0     # held on until a turn has been admitted
TTFT_WAIT_S = 60.0           # after the window: first tokens still due
KERNEL = "paged_attention"
DECODE_MODULE = "jit_paged_fused_decode_step"
SPANS = ("drv.step", "session.admit", "policy.fork", "policy.commit",
         "engine.add_request", "engine.decode")


class Refused(RuntimeError):
    """The run cannot measure this cell here (no result is printed)."""


def load_spec(cell: str, root: Path = ROOT) -> dict:
    """The cell's workload entry and every file it names."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    wl = {w["name"]: w for w in spec["workloads"]}
    if cell not in wl:
        raise Refused(f"no workload {cell!r} in BENCHMARK.json")
    w = wl[cell]
    bench = root / "bench"
    conf = json.loads(
        (bench / "configs" / f"{w['config']}.json").read_text())
    if "family" not in conf:
        raise Refused(f"config {w['config']} names no family: give it "
                      '"family": "<name>" for bench/families/<name>.py')
    fam = bench / "families" / f"{conf['family']}.py"
    if not fam.is_file():
        raise Refused(f"config {w['config']} names the family "
                      f"{conf['family']!r}, and there is no {fam}")
    return {
        "workload": w,
        "benchmark": spec,
        "config": conf,
        "family": family(fam),
        "traffic": json.loads(
            (bench / "traffic" / f"{w['traffic']}.json").read_text()),
        "cell": json.loads((bench / "cells" / f"{cell}.json").read_text()),
        "metrics_dir": bench / "metrics",
    }


def _load(path: Path, prefix: str) -> ModuleType:
    mod_name = prefix + path.stem.replace(".", "_").replace("-", "_")
    s = importlib.util.spec_from_file_location(mod_name, path)
    if s is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


def metric_reader(metrics_dir: Path, name: str) -> Callable:
    """``bench/metrics/<name>.py``'s ``read(run)``."""
    return _load(metrics_dir / f"{name}.py", "bench_metric_").read


@functools.lru_cache(maxsize=None)
def family(path: Path) -> ModuleType:
    """A model family, ``bench/families/<family>.py``, loaded once per
    path (its jitted functions then compile once per process).  It
    provides, for a configuration's ``model`` block ``m``:

    ``make(m, seed)``, ``layer(m, seed, i)``, ``outer(m, seed)``
        the weights from the seed, in the configuration's dtype: all of
        them with the layers stacked (the pytree the program serves),
        layer ``i`` alone, and those outside the layers;
    ``logits(m, seed, tokens, positions, precision)``
        the plain reference's float32 logits at ``positions``, and with
        ``precision="fp8"`` the control's;
    ``token_flops(m, n_ctx)``
        model operations to decode one token over ``n_ctx`` tokens;
    ``attention_work(m, rows, kv_dtype)``
        ``(operations, bytes)`` of each paged-attention call, one per
        layer, that one decode or suffix call over ``rows`` (each
        ``(n_ctx, t)``) makes.
    """
    return _load(path, "bench_family_")


def arch_config(model: dict, log: Callable = lambda *_: None):
    """The program's registered architecture with every size the file
    states (the file is the configuration as it is run)."""
    from repro.configs import get_config

    cfg = get_config(model["arch"])
    sizes = {k: v for k, v in model.items() if k != "arch"}
    unknown = [k for k in sizes if not hasattr(cfg, k)]
    if unknown:
        raise Refused(f"config {model['arch']}: the program has no "
                      f"{', '.join(unknown)}")
    for k, v in sizes.items():
        if getattr(cfg, k) != v:
            log(f"config {model['arch']}: {k} {v!r} (the program "
                f"registers {getattr(cfg, k)!r})")
    return dataclasses.replace(cfg, **sizes)


class Recorder:
    """What the wrappers see.  The model's work is counted by the
    configuration's family while a trace records."""

    def __init__(self, fam: ModuleType, model: dict, kv_dtype: str) -> None:
        self.fam, self.model, self.kv_dtype = fam, model, kv_dtype
        self.window: Optional[tuple] = None
        self.first_t: Dict[int, float] = {}
        self.last_t: Dict[int, float] = {}
        self.itl: List[float] = []
        self.fork_us: List[float] = []
        self.step_host_ms: List[float] = []
        self.decode_s = 0.0
        self.compiles = 0          # programs built in the window
        self.setup_programs = 0    # programs built before it
        self.greedy_rows: Dict[int, list] = {}
        self.admitted = 0
        self.tracing = False
        self.attn_work: List[tuple] = []     # (flops, bytes) per layer call
        self.decode_flops = 0.0
        self.traced_admits = 0

    def in_window(self, t: float) -> bool:
        return self.window is not None and \
            self.window[0] <= t <= self.window[1]

    def count(self, rows: List[tuple], decode: bool) -> None:
        """One decode or suffix call over ``rows``, each ``(n_ctx, t)``."""
        self.attn_work += self.fam.attention_work(self.model, rows,
                                                  self.kv_dtype)
        if decode:
            self.decode_flops += sum(self.fam.token_flops(self.model, n)
                                     for n, _ in rows)

    def on_event(self, event: str, **_: Any) -> None:
        """A program compiled, or loaded from the persistent cache."""
        if event != PROGRAM_EVENT:
            return
        if self.window is None:
            self.setup_programs += 1
        elif self.in_window(time.perf_counter()):
            self.compiles += 1


def annotator(on: bool) -> Callable:
    if on:
        return lambda name, **kw: jax.profiler.TraceAnnotation(name, **kw)
    return lambda name, **kw: nullcontext()


def build(spec: dict, seed: int, *, impl: str, log: Callable):
    """Weights from the seed, the engine, the session and the driver."""
    from repro.api import BranchSession
    from repro.explore_ctx import ExplorationDriver
    from repro.models.model import Model
    from repro.runtime.serve_loop import ServeEngine

    conf = spec["config"]
    model = conf["model"]
    cfg = arch_config(model, log)
    eng = conf["engine"]
    params = spec["family"].make(model, seed)
    jax.block_until_ready(params)
    engine = ServeEngine(
        Model(cfg, remat=False), params, num_pages=eng["num_pages"],
        page_size=eng["page_size"],
        max_pages_per_seq=eng["max_pages_per_seq"], attn_impl=impl,
        kv_dtype=eng["kv_dtype"], prefix_cache=eng["prefix_cache"])
    session = BranchSession(engine, max_batch=eng["max_batch"],
                            seed=seed % (1 << 31))
    driver = ExplorationDriver(session)
    return engine, session, driver


def instrument(engine, session, driver, rec: Recorder,
               annotate: Callable) -> Callable:
    """Wrap each layer's entry on its instance; returns ``driver.step``
    timed and annotated."""
    orig_decode = engine.decode

    def decode(seq_ids, **kw):
        if rec.tracing:
            rec.count([(engine.kv.length(s), 1) for s in seq_ids],
                      decode=True)
        with annotate("engine.decode"):
            t0 = time.perf_counter()
            out = orig_decode(seq_ids, **kw)
            now = time.perf_counter()
        rec.decode_s += now - t0
        for i, s in enumerate(seq_ids):
            rows = rec.greedy_rows.get(s)
            if rows is not None:
                rows.append(np.asarray(logit_row(engine.last_logits, i)))
        inw = rec.in_window(now)
        w0 = rec.window[0] if rec.window else math.inf
        for s in seq_ids:
            last = rec.last_t.get(s)
            if last is None:
                rec.first_t[s] = now
            elif inw and last >= w0:
                rec.itl.append(now - last)
            rec.last_t[s] = now
        return out

    orig_add = engine.add_request

    def add_request(prompt):
        with annotate("engine.add_request"):
            sid = orig_add(prompt)
        if rec.in_window(time.perf_counter()):
            rec.admitted += 1
        if rec.tracing:
            rec.traced_admits += 1
        return sid

    orig_chunk = engine._chunk_prefill

    def chunk_prefill(sid, tokens, covered):
        if rec.tracing:
            rec.count([(covered, len(tokens) - covered)], decode=False)
        return orig_chunk(sid, tokens, covered)

    orig_admit = session.admit

    def admit():
        with annotate("session.admit"):
            return orig_admit()

    orig_branch = session.branch

    def branch(*a, **kw):
        with annotate("policy.fork"):
            t0 = time.perf_counter_ns()
            out = orig_branch(*a, **kw)
            t1 = time.perf_counter_ns()
        if rec.in_window(time.perf_counter()):
            rec.fork_us.append((t1 - t0) / 1e3)
        return out

    engine.decode = decode
    engine.add_request = add_request
    engine._chunk_prefill = chunk_prefill
    session.admit = admit
    session.branch = branch

    def step():
        d0 = rec.decode_s
        with annotate("drv.step"):
            t0 = time.perf_counter()
            driver.step()
            t1 = time.perf_counter()
        if rec.in_window(t1):
            rec.step_host_ms.append(((t1 - t0) - (rec.decode_s - d0)) * 1e3)
    return step


@jax.jit
def logit_row(logits: jax.Array, i: jax.Array) -> jax.Array:
    """Row ``i`` of a decode step's logits (one program per batch size)."""
    return jax.lax.dynamic_index_in_dim(logits, i, keepdims=False)


def warm_up(engine, client, traffic: dict, max_batch: int,
            seed: int) -> None:
    """Build every program the window will run: cache the system
    prompt; replay each session script's prompts once (with other
    tokens), which prefills every suffix the traffic can send; then the
    decode step at each batch size a turn's fan-out makes.  The program
    compiles its decode step per batch size and its suffix prefill per
    length, so a server that has run a while holds them all; set-up pays
    for them, and ``setup_programs`` counts them."""
    rng = generator.rng(seed, 99)
    vocab = engine.cfg.vocab_size

    def toks(n):
        return rng.integers(1, vocab, n).tolist()

    system = client.system
    engine.release(engine.add_request(system + toks(1)))  # caches it
    base = None
    for script in client.sched.scripts:
        prompt = system + toks(script["task"])
        sid = engine.add_request(prompt)
        turns = script["turns"]
        for j in range(1, len(turns)):
            prompt = prompt + toks(turns[j - 1]["new"] + turns[j]["tool"])
            nxt = engine.add_request(prompt)
            engine.release(sid)
            sid = nxt
        if base is None:
            base = sid
        else:
            engine.release(sid)
    n = int(traffic["best_of"])
    sizes = list(range(n, max_batch + 1, n))
    kids = engine.fork(base, max(sizes), eager_cow=True)
    key = jax.random.PRNGKey(0)
    for i, b in enumerate(sizes):
        g = [j < int(traffic["greedy_branches"]) for j in range(b)]
        engine.decode(kids[:b], greedy=g, temperature=1.0,
                      key=jax.random.fold_in(key, i))
        logit_row(engine.last_logits, 0)
    engine.release(base)
    # the fork's tail-page copy at the fan-out's width
    sid = engine.add_request(system + toks(3))
    engine.fork(sid, n, eager_cow=True)
    engine.release(sid)
    jax.block_until_ready((engine.k_pages, engine.v_pages))


class Run:
    """One run: ``Run(spec, seed, seconds, trace, ...).go()`` -> result."""

    def __init__(self, spec: dict, seed: int, seconds: float, trace: bool,
                 *, t_start: float, impl: str = "auto",
                 require_pallas: bool = True, rate: Optional[float] = None,
                 control: Optional[str] = None, log: Callable = print):
        self.spec = spec
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.t_start = t_start
        self.impl = impl
        self.require_pallas = require_pallas
        self.trace_dir = ROOT / ".bench_trace"
        self.rate = rate
        self.control = control
        self.log = log

    def go(self) -> dict:
        out, self.state = self._serve()
        return self.judge(out, self.control)

    def judge(self, out: dict, control: Optional[str]) -> dict:
        """``out`` with ``correct`` and the checks: the served sample, or
        with ``control`` the reference in that precision in the
        program's place, against the plain reference."""
        checks = self._check(self.state, control)
        out = dict(out, correct=all(c["value"] <= c["limit"]
                                    for c in checks.values()))
        out["checks"] = checks
        return out

    # -- serving ---------------------------------------------------------
    def _serve(self):
        from bench.clients import AgentClient

        spec = self.spec
        conf, traffic = spec["config"], dict(spec["traffic"])
        if self.rate is not None:
            traffic["session_rate_per_s"] = self.rate
        model = conf["model"]
        kv_dtype = conf["engine"]["kv_dtype"] or model["dtype"]
        self.rec = rec = Recorder(spec["family"], model, kv_dtype)
        jax.monitoring.register_event_listener(rec.on_event)
        engine, session, driver = build(spec, self.seed, impl=self.impl,
                                        log=self.log)
        if self.require_pallas and engine.attn_impl != "pallas":
            raise Refused(f"attention resolved to {engine.attn_impl!r}, "
                          "not 'pallas'")
        annotate = annotator(self.trace)
        step = instrument(engine, session, driver, rec, annotate)
        sched = generator.Schedule(traffic, self.seed, model["vocab_size"])
        client = AgentClient(traffic, sched, driver, annotate)
        warm_up(engine, client, traffic, conf["engine"]["max_batch"],
                self.seed)
        self.log(f"set-up built {rec.setup_programs} programs before "
                 "offering traffic")
        loop = Loop(step, client, engine, rec, self)

        # warm-up: the mix itself, so that the window opens on a steady
        # queue, batch and prefix cache
        t_warm = time.perf_counter() + float(traffic["warmup_s"])
        client.start(time.perf_counter())
        loop.run(lambda: time.perf_counter() >= t_warm, limit_s=900.0)
        m0 = self._counters(engine)
        t_w0 = time.perf_counter()
        t_w1 = t_w0 + self.seconds
        rec.window = (t_w0, t_w1)
        setup_s = t_w0 - self.t_start
        client.lag.clear()
        client.watch = rec.greedy_rows
        trace_at = (t_w0 + max(0.0, (self.seconds - TRACE_SECONDS) / 2)
                    if self.trace else None)
        loop.run(lambda: time.perf_counter() >= t_w1,
                 limit_s=self.seconds + 5, trace_at=trace_at)
        m1 = self._counters(engine)
        client.pump(t_w1)          # every turn due in the window is sent
        client.watch = None
        window_reqs = [r for r in client.inflight + client.done
                       if t_w0 <= r.t0 < t_w1]
        # a request due in the window that has no first token yet gets
        # it now; its time to first token counts the wait
        loop.run(lambda: all(self._first_token(r) is not None
                             or r.done_t is not None for r in window_reqs),
                 limit_s=TTFT_WAIT_S)
        t_ttft = time.perf_counter()
        client.offering = False
        for r in list(client.inflight):
            client.abandon(r)
        jax.block_until_ready((engine.k_pages, engine.v_pages))
        dev = jax.devices()[0]
        mem = dev.memory_stats() or {}
        out = self._result(client, window_reqs, t_w0, t_w1, t_ttft, setup_s,
                           m0, m1, mem, kv_dtype, dev)
        lag = client.lag
        self.log(f"generator lag: max {max(lag, default=0.0) * 1e3:.3f} ms,"
                 f" mean {float(np.mean(lag)) * 1e3 if lag else 0.0:.3f} ms "
                 f"over {len(lag)} sends")
        state = self._exact_checks(engine, session, client, window_reqs)
        return out, state

    def _counters(self, engine) -> dict:
        snap = engine.obs.metrics.snapshot()
        hist = {}
        for k, v in snap.get("histograms", {}).items():
            hist[k] = {"sum": float(v.get("sum", 0.0)),
                       "count": int(v.get("count", 0))}
        return {"counters": dict(snap.get("counters", {})), "hist": hist}

    def _first_token(self, r) -> Optional[float]:
        ts = [self.rec.first_t[s] for s in r.seqs if s in self.rec.first_t]
        return min(ts) if ts else None

    def _result(self, client, reqs, t_w0, t_w1, t_ttft, setup_s, m0, m1,
                mem, kv_dtype, dev) -> dict:
        rec = self.rec
        ttft, failed = [], 0
        for r in reqs:
            ft = self._first_token(r)
            failed += int(r.error is not None or r.degraded or ft is None)
            # no first token at all: counted at the wait's end, a floor
            ttft.append(((t_ttft if ft is None else ft) - r.t0) * 1e3)
        done_in = [r for r in client.done
                   if r.done_t is not None and t_w0 <= r.done_t <= t_w1]
        self.log(f"window: {len(reqs)} turns due, {len(done_in)} "
                 f"completed, {failed} failed; {len(rec.itl)} token gaps, "
                 f"{len(rec.fork_us)} forks, {rec.compiles} programs built "
                 f"in the window, {rec.setup_programs} before it")
        for name, xs in (("ttft_ms", ttft), ("itl_ms",
                                             [x * 1e3 for x in rec.itl])):
            if xs:
                self.log(f"{name}: " + ", ".join(
                    f"p{q} {T.percentile(xs, q):.3f}"
                    for q in (10, 25, 50, 75, 90, 95, 99))
                    + f", mean {float(np.mean(xs)):.3f} over {len(xs)}")
        run = {
            "seconds": self.seconds,
            "setup_s": setup_s,
            "ttft_ms": ttft,
            "itl_ms": [x * 1e3 for x in rec.itl],
            "fork_us": rec.fork_us,
            "admit_wait_ms": [(r.admitted_t - r.t0) * 1e3 for r in reqs
                              if r.admitted_t is not None],
            "host_ms_per_step": rec.step_host_ms,
            "requests_admitted": rec.admitted,
            "counters0": m0, "counters1": m1,
            "compiles": rec.compiles,
            "setup_programs": rec.setup_programs,
            "memory": mem,
            "model": self.spec["config"]["model"],
            "kv_dtype": kv_dtype,
        }
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices()),
                  "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
        if self.trace:
            run["trace"] = self._reduce_trace(dev)
            device["busy_s"] = run["trace"]["busy_s"]
            device["window_s"] = run["trace"]["window_s"]
        self.run_record = run
        metrics = {}
        for m in self._metric_defs():
            val = m["read"](run)
            if val is not None:
                metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}
        out = {"correct": None, "attempted": len(reqs), "failed": failed,
               "metrics": metrics, "device": device}
        if self.trace:
            out["breakdown"] = run["trace"]["breakdown"]
        return out

    def _metric_defs(self) -> List[dict]:
        spec = self.spec["benchmark"]
        cell = self.spec["workload"]["name"]
        group = "per_layer" if self.trace else "end_to_end"
        out = []
        for m in spec[group]:
            if "workloads" in m and cell not in m["workloads"]:
                continue
            read = (END_TO_END[m["name"]] if group == "end_to_end"
                    else metric_reader(self.spec["metrics_dir"], m["name"]))
            out.append({"name": m["name"], "unit": m["unit"], "read": read})
        return out

    # -- tracing ---------------------------------------------------------
    def start_trace(self, engine) -> None:
        jax.block_until_ready((engine.k_pages, engine.v_pages))
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        self.trace_dir.mkdir(parents=True, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # host spans: the harness's own
        jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)
        self.rec.tracing = True
        self.rec.attn_work, self.rec.decode_flops = [], 0.0
        self.rec.traced_admits = 0

    def stop_trace(self, engine) -> None:
        jax.block_until_ready((engine.k_pages, engine.v_pages))
        jax.profiler.stop_trace()
        self.rec.tracing = False

    def _reduce_trace(self, dev) -> dict:
        tr = T.load_xplane(str(self.trace_dir))
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        planes = T.device_planes(tr)
        if not planes:
            raise RuntimeError("the trace holds no TPU plane")
        plane = planes[0]
        steps = T.host_spans(tr, ["drv.step"])
        lo = min(s for _, s, _ in steps)
        hi = max(e for _, _, e in steps)
        pk = work.peaks(dev.device_kind)
        aw = self.rec.attn_work
        roof = sum(work.roofline_seconds(f, b, pk)[0] for f, b in aw)
        bound = work.roofline_seconds(sum(f for f, _ in aw),
                                      sum(b for _, b in aw), pk)[1]
        gaps = T.idle_gaps(plane, lo, hi)
        red = {
            "window_s": (hi - lo) / 1e9,
            "busy_s": T.busy_ns(plane, lo, hi) / 1e9,
            "kernel_s": T.kernel_ns(plane, KERNEL, lo, hi) / 1e9,
            "kernel_calls": T.kernel_calls(plane, KERNEL, lo, hi),
            "work_calls": len(aw),
            "kernel_roofline_s": roof,
            "kernel_bound": bound,
            "outside_decode_s": T.ops_inside(plane, DECODE_MODULE, lo, hi,
                                             inside=False) / 1e9,
            "decode_flops": self.rec.decode_flops,
            "admits": self.rec.traced_admits,
            "peak_flops": pk["bf16_flops"],
            "breakdown": {
                "device_ops": T.top(T.op_time_by_name(plane, lo, hi)),
                "idle_gaps": T.top(T.attribute_gaps(
                    gaps, T.host_spans(tr, SPANS))),
            },
        }
        self.log(f"trace: window {red['window_s']:.4f} s, busy "
                 f"{red['busy_s']:.4f} s; {KERNEL}: {red['kernel_calls']} "
                 f"calls ({len(aw)} recorded), {red['kernel_s']:.4f} s, "
                 f"{bound}-bound roofline {roof:.4f} s; outside the decode "
                 f"step {red['outside_decode_s']:.4f} s; {red['admits']} "
                 "admitted")
        return red

    # -- correctness -----------------------------------------------------
    def _exact_checks(self, engine, session, client, window_reqs) -> dict:
        """What is compared exactly, read while the program still runs;
        then the sample the reference will check."""
        from bench import check

        done = client.done
        mism = losers = 0
        for r in done:
            if r.error is None and r.winner_gen is not None:
                mism += int(r.final_tokens != r.prompt + r.winner_gen)
                losers += r.losers_alive
        handles = session.tree()["handles"]["open"]
        engine.kv.clear_prefix_cache()
        st = engine.kv.stats()
        sample = check.sample(done, self.seed,
                              int(self.spec["traffic"]["check_requests"]))
        self.rec.greedy_rows.clear()
        return {
            "values": {
                "commit_mismatches": mism,
                "losers_alive": losers,
                "requests_errored": sum(1 for r in done
                                        if r.error is not None),
                "requests_unanswered": sum(
                    1 for r in window_reqs
                    if self._first_token(r) is None and r.done_t is None),
                "pool_pages_leaked": st["pages_total"] - st["pages_free"],
                "handles_open": handles,
            },
            "sample": [(r.prompt, r.greedy_gen, np.stack(r.logits))
                       for r in sample],
        }

    def _check(self, state: dict, control: Optional[str]) -> dict:
        """Compare the sample with the plain reference, once the program's
        state is gone; every number beside its limit."""
        from bench import check

        gc.collect()
        lim = self.spec["cell"]["limits"]
        seqs = self.last_sample = state["sample"]
        read = {"logit_rel_err": check.NO_READING,
                "greedy_argmax_mismatches": check.NO_READING}
        if seqs:
            t0 = time.perf_counter()
            read = check.compare(self.spec["family"],
                                 self.spec["config"]["model"], self.seed,
                                 seqs, control)
            self.log(f"reference: {len(seqs)} turns, "
                     f"{sum(len(g) for _, g, _ in seqs)} served tokens, "
                     f"{time.perf_counter() - t0:.3f} s"
                     + (f", the {control} control in the program's place"
                        if control else ""))
        vals = {**read, **state["values"]}
        return {k: {"value": float(v), "limit": float(lim[k])}
                for k, v in vals.items()}


class Loop:
    """Offer the traffic and step the driver until a condition holds."""

    def __init__(self, step, client, engine, rec, run: Run):
        self.step, self.client, self.engine = step, client, engine
        self.rec, self.run_ = rec, run

    def run(self, until: Callable[[], bool], *, limit_s: float,
            trace_at: Optional[float] = None) -> None:
        client = self.client
        t_end = time.perf_counter() + limit_s
        trace_end = None
        while not until():
            now = time.perf_counter()
            if now > t_end:
                break
            if trace_at is not None and trace_end is None and now >= trace_at:
                self.run_.start_trace(self.engine)
                trace_end = time.perf_counter() + TRACE_SECONDS
            if self.rec.tracing and now >= trace_end and (
                    self.rec.traced_admits
                    or now >= trace_end + TRACE_MAX_EXTRA_S):
                self.run_.stop_trace(self.engine)
            client.pump(now)
            if not client.inflight:
                nxt = client.next_event()
                if nxt is None:
                    break
                time.sleep(max(0.0, min(nxt, t_end) - time.perf_counter()))
                continue
            self.step()
            client.collect(time.perf_counter())
        if self.rec.tracing:
            self.run_.stop_trace(self.engine)


def _pct(q: float, key: str) -> Callable:
    def read(run: dict) -> Optional[float]:
        xs = run[key]
        return T.percentile(xs, q) if xs else None
    return read


END_TO_END: Dict[str, Callable] = {
    "setup_s": lambda run: run["setup_s"],
    "ttft_p50_ms": _pct(50, "ttft_ms"),
    "itl_p95_ms": _pct(95, "itl_ms"),
}


def emit(out: dict) -> None:
    """The run's last lines: each compared number beside its limit on
    standard error, then the result as the last line of standard
    output, its ``checks`` key last."""
    checks = out.pop("checks")
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    out["checks"] = checks
    print(json.dumps(out), flush=True)
