"""Observability subsystem: metrics math, span lifecycle, Chrome export,
timed spans on the profiler's clock, and the scheduler's request times.

Covers the obs tentpole end to end: histogram bucket math, the
disabled-mode no-op guarantee (call-count probe on the clock), span
trees mirroring the branch tree, exactly-once invalidation events under
every racing closer (eager sibling kill, lazy -ESTALE discovery,
abort-after-ESTALE, scheduler-purged reap — the re-entrant close
bugfix), engine counter views keeping their ``stats()`` dict shape, and
a full 8-way ``best_of_n`` exploration whose exported Chrome trace
matches ``BranchTree.snapshot()`` lineage.
"""

import dataclasses
import glob
import json
import os
import time

import jax
import pytest

from repro.configs import get_config
from repro.core.lifecycle import BranchStatus, BranchTree
from repro.models.model import Model
from repro.obs import Observability
from repro.obs.metrics import Histogram, Metrics
from repro.obs.tracer import ENGINE_TRACK, Tracer
from repro.runtime.serve_loop import ServeEngine


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_gauge_basics():
    m = Metrics()
    c = m.counter("x.events")
    c.inc()
    c.inc(4)
    assert c.value == 5
    assert m.counter("x.events") is c          # get-or-create
    g = m.gauge("x.level")
    g.set(7)
    g.add(-2)
    assert g.value == 5
    with pytest.raises(TypeError):
        # the runtime guard branchlint BL005 front-runs, exercised
        m.gauge("x.events")  # branchlint: ignore[BL005]


def test_histogram_bucket_math():
    h = Histogram("t", lo=1.0, growth=2.0, buckets=4)   # bounds 1,2,4,8
    assert h.bounds == [1.0, 2.0, 4.0, 8.0]
    for v in (0.5, 1.0, 1.5, 3.0, 8.0, 100.0):
        h.observe(v)
    # 0.5 and 1.0 -> bucket 0 (<=1); 1.5 -> bucket 1; 3.0 -> bucket 2;
    # 8.0 -> bucket 3; 100.0 -> overflow
    assert h.counts == [2, 1, 1, 1, 1]
    assert h.count == 6
    assert h.min == 0.5 and h.max == 100.0
    snap = h.snapshot()
    assert snap["count"] == 6
    assert snap["buckets"] == {"1": 2, "2": 1, "4": 1, "8": 1, "inf": 1}


def test_histogram_percentiles():
    h = Histogram("t", lo=1.0, growth=2.0, buckets=10)
    for _ in range(99):
        h.observe(3.0)       # bucket bound 4
    h.observe(1000.0)        # bound 1024
    assert h.percentile(50) == 4.0
    assert h.percentile(99) == 4.0
    assert h.percentile(100) == 1000.0   # capped at true max
    empty = Histogram("e")
    assert empty.percentile(50) == 0.0
    assert empty.snapshot()["min"] == 0.0


def test_metrics_format_procfs_lines():
    m = Metrics()
    m.counter("kv.commits").inc(3)
    m.gauge("kv.pages_free").set(17)
    m.histogram("t.lat_us").observe(12.0)
    text = m.format()
    assert "counter kv.commits 3" in text
    assert "gauge   kv.pages_free 17" in text
    assert "hist    t.lat_us count=1" in text


# ---------------------------------------------------------------------------
# tracer core + disabled-mode no-op
# ---------------------------------------------------------------------------

def test_disabled_tracer_is_true_noop():
    calls = []

    def probe_clock():
        calls.append(1)
        return 0

    tr = Tracer(enabled=False, clock=probe_clock)
    assert tr.begin_span(1, "explore") is None
    assert tr.end_span(1) is False
    tr.instant(1, "fork")
    assert calls == []                 # the clock was never consulted
    assert tr.spans == [] and tr.instants == []


def test_end_span_reentrancy_guard():
    tr = Tracer(enabled=True)
    tr.begin_span(5, "explore")
    assert tr.end_span(5, status="committed") is True
    # the double close IS the subject under test here
    assert tr.end_span(5) is False  # branchlint: ignore[BL004]
    assert len(tr.spans) == 1
    assert tr.spans[0].status == "committed"


def test_chrome_trace_schema_valid_and_loadable(tmp_path):
    tr = Tracer(enabled=True)
    tr.begin_span(0, "explore", group=0)
    tr.begin_span(1, "explore", parent=0)
    tr.instant(1, "fork")
    tr.end_span(1, status="committed")
    path = tmp_path / "trace.json"
    tr.export_chrome_trace(path)
    loaded = json.loads(path.read_text())   # valid JSON on disk
    evs = loaded["traceEvents"]
    assert all({"ph", "name", "pid"} <= set(e) for e in evs)
    for e in evs:
        if e["ph"] == "X":
            assert e["ts"] >= 0 and e["dur"] >= 0
    # the still-open root span was flushed, not dropped
    root = [e for e in evs if e["ph"] == "X" and e["tid"] == 0]
    assert root and root[0]["args"]["status"] == "open"
    # child inherited the root's process and recorded its parent
    child = [e for e in evs if e["ph"] == "X" and e["tid"] == 1][0]
    # the deliberately-open root span is the subject under test
    assert child["pid"] == 0 and child["args"]["parent"] == 0  # branchlint: ignore[BL004]


# ---------------------------------------------------------------------------
# lifecycle instrumentation (span tree mirrors branch tree)
# ---------------------------------------------------------------------------

def traced_tree(**kw):
    obs = Observability(trace=True)
    return BranchTree(tracer=obs.tracer, **kw), obs.tracer


def test_span_nesting_mirrors_branch_nesting():
    tree, tr = traced_tree()
    root = tree.create_root()
    a, b = tree.fork(root, 2)
    (a1,) = tree.fork(a, 1)
    lineage = tr.lineage()
    assert lineage == {root: None, a: root, b: root, a1: a}
    # commit the grandchild, then the child: spans close leaf-first with
    # the winning statuses, and b is invalidated by a's commit
    tree.commit(a1)
    tree.commit(a)
    by_track = {s.track: s for s in tr.spans}
    assert by_track[a1].status == "committed"
    assert by_track[a].status == "committed"
    assert by_track[b].status == "invalidated"
    assert root not in by_track          # root still open (live)
    assert tr.has_open(root)


def test_invalidation_events_fire_exactly_once_per_killed_sibling():
    tree, tr = traced_tree()
    root = tree.create_root()
    kids = tree.fork(root, 4)
    tree.commit(kids[0])
    # losers observe -ESTALE lazily AND clean up with abort afterwards —
    # both re-close attempts must be no-ops
    for k in kids[1:]:
        assert tree.status(k) is BranchStatus.STALE
        tree.abort(k)
    inv = [i for i in tr.instants if i.name == "invalidated"]
    assert sorted(i.track for i in inv) == sorted(kids[1:])
    assert len(inv) == 3                 # exactly once each
    commits = [i for i in tr.instants if i.name == "commit"]
    assert [c.track for c in commits] == [kids[0]]


def test_reap_closes_purged_open_spans_as_invalidated():
    """The bugfix: an external abort reaps descendants whose open
    explore-spans were never closed (their -ESTALE was never observed);
    reap must close them as invalidated — no leak, no double-close."""
    tree, tr = traced_tree()
    root = tree.create_root()
    a, b = tree.fork(root, 2)
    tree.fork(a, 2)                      # grandchildren, still open
    tree.invalidate(root, status=BranchStatus.ABORTED)   # external purge
    assert tree.reap(root) == 5
    assert tr.open_spans == []           # nothing leaked
    by_track = {s.track: s for s in tr.spans}
    assert len(by_track) == 5            # nothing double-closed
    assert by_track[root].status == "aborted"
    # every descendant closed as invalidated exactly once
    assert all(by_track[t].status in ("invalidated", "aborted")
               for t in by_track)
    inv = [i.track for i in tr.instants if i.name == "invalidated"]
    assert len(inv) == len(set(inv))


def test_lazy_stale_discovery_closes_span_once():
    tree, tr = traced_tree()
    root = tree.create_root()
    a, b = tree.fork(root, 2)
    tree.commit(a)                       # b eagerly invalidated
    closes_before = len(tr.spans)
    assert tree.status(b) is BranchStatus.STALE   # lazy re-check: no-op
    assert len(tr.spans) == closes_before


# ---------------------------------------------------------------------------
# engine / scheduler / session integration
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine_setup():
    cfg = dataclasses.replace(get_config("paper-agentic"), dtype="float32")
    model = Model(cfg, attn_chunk=8, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def fresh_engine(engine_setup, **kw):
    cfg, model, params = engine_setup
    kw.setdefault("num_pages", 128)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_pages_per_seq", 16)
    return ServeEngine(model, params, **kw)


def test_engine_counters_are_registry_views(engine_setup):
    eng = fresh_engine(engine_setup)
    assert eng.cow_dispatches == 0       # fresh engine, fresh hub
    root = eng.add_request([7, 8, 9])
    kids = eng.fork(root, 3)
    eng.decode(kids)
    # attribute views and the registry agree
    snap = eng.obs.metrics.snapshot()
    assert eng.cow_faults == snap["counters"]["engine.cow_faults"] > 0
    assert eng.cow_dispatches == snap["counters"]["engine.cow_dispatches"]
    # stats() keeps its dict shape (tier-1 compatibility surface)
    st = eng.stats()
    for key in ("cow_dispatches", "cow_faults", "cow_inline_steps",
                "verify_dispatches", "pages_free", "pages_total"):
        assert key in st
    # per-step telemetry landed
    assert snap["histograms"]["engine.decode_step_us"]["count"] == 1
    assert snap["histograms"]["engine.batch_occupancy"]["p50"] >= 3
    assert snap["counters"]["engine.tokens_decoded"] == 3
    assert snap["gauges"]["engine.kv_pool_bytes"] > 0
    assert snap["counters"]["kv.branches_forked"] == 3


def test_kv_footprints_and_pool_gauges(engine_setup):
    eng = fresh_engine(engine_setup)
    root = eng.add_request([1, 2, 3, 4, 5])
    fp = eng.kv.footprints()
    assert fp[root] == len(eng.kv.block_table(root))
    kids = eng.fork(root, 2)
    fp = eng.kv.footprints()
    assert set(kids) <= set(fp)
    g = eng.obs.metrics.snapshot()["gauges"]
    assert g["kv.pages_free"] == eng.kv.free_pages
    assert g["kv.pages_shared"] == eng.kv.stats()["pages_shared"]
    eng.commit(kids[0])
    g = eng.obs.metrics.snapshot()["gauges"]
    assert g["kv.pages_free"] == eng.kv.free_pages
    assert g["kv.pages_shared"] == eng.kv.stats()["pages_shared"]


def test_session_stat_metrics_and_format_tree(engine_setup):
    from repro.api import BranchSession

    eng = fresh_engine(engine_setup)
    session = BranchSession(eng, max_batch=8, seed=0)
    root = session.open([3, 1, 4], max_new_tokens=4)
    for _ in range(4):
        session.step()
    view = session.stat(metrics=True)    # the README quickstart call
    assert "metrics" in view and "branches" in view
    assert view["metrics"]["counters"]["sched.admitted"] == 1
    assert "footprints" in view
    per_hd = session.stat(root, metrics=True)
    assert per_hd["hd"] == root and "metrics" in per_hd
    text = session.format_tree(metrics=True)
    assert "metrics:" in text and "counter sched.admitted 1" in text
    assert "metrics:" not in session.format_tree()
    wait = view["metrics"]["histograms"]["sched.admission_wait_us"]
    assert wait["count"] == 1
    session.finish(root)                 # release the handle (BL002)


def test_best_of_n_trace_matches_snapshot_lineage(engine_setup, tmp_path):
    """Acceptance: an 8-way best_of_n exploration exports a Chrome trace
    whose span tree matches BranchTree.snapshot() — one track per
    branch, commit/invalidate events present."""
    from repro.api import BranchSession
    from repro.explore_ctx import ExplorationDriver, best_of_n

    eng = fresh_engine(engine_setup, num_pages=256,
                       obs=Observability(trace=True))
    session = BranchSession(eng, max_batch=16, seed=3)
    driver = ExplorationDriver(session)
    exp = driver.explore([7, 3, 9, 2], max_new_tokens=9, policy=best_of_n,
                         n=8, tokens=4, temperature=1.5)
    snapshot = None
    for _ in range(500):
        if not driver.step():
            break
        snap = eng.kv.tree.snapshot()
        if snap and len(snap[0].get("children", [])) == 8:
            snapshot = snap              # the full 9-node tree, mid-run
    driver.run()
    assert exp.result is not None and snapshot is not None

    path = tmp_path / "trace.json"
    trace = session.trace(path)
    loaded = json.loads(path.read_text())
    assert loaded == trace
    spans = [e for e in trace["traceEvents"] if e["ph"] == "X"
             and e["name"] == "explore"]
    inst = [e for e in trace["traceEvents"] if e["ph"] == "i"]

    def lineage_of(node, parent=None, out=None):
        out[node["id"]] = parent
        for c in node["children"]:
            lineage_of(c, node["id"], out)
        return out

    want = lineage_of(snapshot[0], None, {})
    got = {e["tid"]: e["args"].get("parent") for e in spans}
    assert got == want                   # one track per branch, exact tree
    assert len({e["tid"] for e in spans}) == 9
    # the winner committed, every losing sibling shows an invalidate
    committed = {e["tid"] for e in inst if e["name"] == "commit"}
    assert len(committed) == 1
    invalidated = {e["tid"] for e in inst if e["name"] == "invalidated"}
    kids = set(want) - {snapshot[0]["id"]}
    assert kids - committed <= invalidated
    # engine decode telemetry rode the reserved engine track
    assert any(e["tid"] == ENGINE_TRACK and e["name"] == "decode_step"
               for e in inst)


def test_untraced_engine_records_nothing(engine_setup):
    eng = fresh_engine(engine_setup)
    root = eng.add_request([5, 6])
    eng.fork(root, 2)
    assert eng.obs.tracer.spans == []
    assert eng.obs.tracer.instants == []


# ---------------------------------------------------------------------------
# timed spans: a registry histogram plus a profiler annotation
# ---------------------------------------------------------------------------

def test_span_observes_once_on_normal_exit():
    obs = Observability()
    with obs.span("t.phase", req=3, rows=2) as sp:
        pass
    h = obs.metrics.snapshot()["histograms"]["t.phase_us"]
    assert h["count"] == 1
    assert sp.us is not None and sp.us >= 0
    assert h["sum"] == pytest.approx(sp.us, abs=1e-3)


def test_span_observes_nothing_when_the_body_raises():
    obs = Observability()
    with obs.span("t.phase"):
        pass
    with pytest.raises(KeyError, match="boom"):
        with obs.span("t.phase") as sp:
            raise KeyError("boom")
    assert sp.us is None
    assert obs.metrics.histogram("t.phase_us").count == 1
    # the annotation closed: a later span still nests and observes
    with obs.span("t.phase"):
        pass
    assert obs.metrics.histogram("t.phase_us").count == 2


def host_spans(trace_dir, names):
    """``{name: [(start_ns, end_ns), ...]}`` of the named spans on the
    trace's ``/host:CPU`` plane."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert paths, "the profiler wrote no trace"
    data = jax.profiler.ProfileData.from_file(paths[0])
    out = {}
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in names:
                    out.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    return out


def test_driver_step_spans_land_on_the_profiler_host_plane(engine_setup,
                                                            tmp_path):
    from repro.api import BranchSession
    from repro.explore_ctx import ExplorationDriver

    eng = fresh_engine(engine_setup)
    session = BranchSession(eng, max_batch=8, seed=0)
    driver = ExplorationDriver(session)
    hd = session.open([3, 1, 4, 1], max_new_tokens=4)
    driver.step()                        # builds the decode step untraced
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        driver.step()
    finally:
        jax.profiler.stop_trace()
    session.finish(hd)
    phases = ("engine.decode_prep", "engine.decode_wait",
              "engine.decode_post")
    names = ("driver.step", "engine.decode_step") + phases
    got = host_spans(str(tmp_path), names)
    assert {n: len(got.get(n, ())) for n in names} == dict.fromkeys(names, 1)
    (d0, d1), = got["driver.step"]
    (s0, s1), = got["engine.decode_step"]
    assert d0 <= s0 <= s1 <= d1
    spans = [got[n][0] for n in phases]
    for a, b in spans:
        assert s0 <= a <= b <= s1       # each phase inside the step
    for (_, b), (a, _) in zip(spans, spans[1:]):
        assert b <= a                   # prep, then wait, then post


def test_decode_phases_sum_to_the_decode_step(engine_setup):
    eng = fresh_engine(engine_setup)
    root = eng.add_request([2, 7, 1, 8])
    kids = eng.fork(root, 2)
    for _ in range(3):
        eng.decode(kids)
    h = eng.obs.metrics.snapshot()["histograms"]
    step = h["engine.decode_step_us"]
    parts = [h[f"engine.decode_{p}_us"] for p in ("prep", "wait", "post")]
    assert step["count"] == 3 and all(p["count"] == 3 for p in parts)
    total = sum(p["sum"] for p in parts)
    assert total <= step["sum"] + 1e-3
    assert total >= 0.9 * step["sum"]


# ---------------------------------------------------------------------------
# the scheduler's request times and the prefix cache's prompt share
# ---------------------------------------------------------------------------

def test_admission_wait_counts_only_queueing(engine_setup):
    from repro.runtime.scheduler import Scheduler, SchedulerConfig

    eng = fresh_engine(engine_setup, num_pages=5)
    sched = Scheduler(eng, SchedulerConfig(max_batch=4))
    prefill = eng.add_request

    def slow_prefill(prompt):
        time.sleep(0.05)
        return prefill(prompt)

    eng.add_request = slow_prefill
    # each request reserves 3 of the 5 pages: r2 waits for r1 to retire
    r1 = sched.submit(list(range(1, 9)), max_new_tokens=2)
    r2 = sched.submit(list(range(11, 19)), max_new_tokens=2)
    sched.run(max_steps=20)
    assert len(sched.result(r1)) == len(sched.result(r2)) == 10
    h = eng.obs.metrics.histogram("sched.admission_wait_us")
    assert h.count == 2
    # r1 left the queue at once: its wait holds none of its prefill
    assert h.min < 50_000
    # r2, held back by the page budget, waited out r1's whole run
    assert h.max > h.min + 50_000


def test_first_token_is_timed_once_per_request(engine_setup):
    from repro.api import BranchSession
    from repro.explore_ctx import ExplorationDriver, best_of_n

    eng = fresh_engine(engine_setup, num_pages=256)
    session = BranchSession(eng, max_batch=16, seed=4)
    driver = ExplorationDriver(session)
    exp = driver.explore([5, 6, 7, 8], max_new_tokens=8, policy=best_of_n,
                         n=4, tokens=3, temperature=1.0)
    hd = session.open([1, 2, 3], max_new_tokens=3)
    driver.run()
    for _ in range(10):
        session.step()
    assert exp.result is not None
    assert session.finish(hd) is not None
    m = eng.obs.metrics
    first = m.histogram("sched.first_token_us")
    assert first.count == 2             # the exploration's turn + the plain
    assert m.counter("sched.admitted").value == 2
    # a first token comes after its request left the queue
    assert first.min >= m.histogram("sched.admission_wait_us").min


def test_prompt_token_counters_see_prefix_hits(engine_setup):
    eng = fresh_engine(engine_setup, prefix_cache=True)
    total = eng.obs.metrics.counter("engine.prompt_tokens")
    cached = eng.obs.metrics.counter("engine.prompt_tokens_cached")
    prompt = list(range(1, 11))
    a = eng.add_request(prompt)               # a miss: 9 tokens prefilled
    assert (total.value, cached.value) == (9, 0)
    _, covered = eng.kv.match_prefix(prompt + [42])
    assert 0 < covered < 11                   # whole pages of the prompt
    b = eng.add_request(prompt + [42, 43])    # a hit on those pages
    assert (total.value, cached.value) == (9 + 11, covered)
    c = eng.add_request([99, 98, 97, 96, 95])  # another miss
    assert (total.value, cached.value) == (9 + 11 + 4, covered)
    for sid in (a, b, c):
        eng.release(sid)
