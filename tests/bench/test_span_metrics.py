"""The readers of the program's own spans and counters: each reads a
window mean from the change between the harness's two snapshots, reads
nothing where the program lacks its instruments, and finds them in a
whole run of a cell at a small size on the CPU."""

import time

import pytest

from bench import harness

METRICS = harness.ROOT / "bench" / "metrics"


def snap(hist, counters=()):
    return {"hist": {k: {"sum": s, "count": n} for k, (s, n) in hist.items()},
            "counters": dict(counters)}


# the window's two snapshots: every histogram as (sum, count)
BEFORE = snap({"driver.step_us": (1_000_000.0, 10),
               "engine.decode_wait_us": (700_000.0, 10),
               "engine.decode_prep_us": (40_000.0, 10),
               "engine.decode_post_us": (5_000.0, 10),
               "sched.admission_wait_us": (900.0, 3),
               "sched.first_token_us": (600_000.0, 3),
               "session.fork_us": (7_000.0, 3),
               "session.commit_us": (900.0, 3)},
              {"engine.prompt_tokens": 30_000,
               "engine.prompt_tokens_cached": 29_000})
AFTER = snap({"driver.step_us": (3_800_000.0, 30),
              "engine.decode_wait_us": (2_500_000.0, 30),
              "engine.decode_prep_us": (120_000.0, 30),
              "engine.decode_post_us": (25_000.0, 30),
              "sched.admission_wait_us": (1_500.0, 5),
              "sched.first_token_us": (1_000_000.0, 5),
              "session.fork_us": (11_000.0, 5),
              "session.commit_us": (1_700.0, 5)},
             {"engine.prompt_tokens": 50_000,
              "engine.prompt_tokens_cached": 48_000})

WANT = {
    # (2.8 s of steps - 1.8 s of waits) over 20 steps
    "step_host_ms": 50.0,
    # (80 ms of prep + 20 ms of post) over 20 decode calls
    "decode_host_ms": 5.0,
    "queue_wait_ms": 0.3,
    "server_ttft_ms": 200.0,
    "fork_us": 2_000.0,
    "commit_us": 400.0,
    "cached_prompt_share": 95.0,
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_takes_the_window_mean(name):
    read = harness.metric_reader(METRICS, name)
    got = read({"counters0": BEFORE, "counters1": AFTER})
    assert got == pytest.approx(WANT[name])
    # an instrument first registered inside the window counts from zero
    empty = snap({})
    got = read({"counters0": empty, "counters1": AFTER})
    assert got is not None


# what a program without the spans and counters snapshots: the older
# histograms only, the admission wait among them (then timed through
# the prefill)
PARENT = snap({"engine.decode_step_us": (1_000_000.0, 10),
               "engine.batch_occupancy": (80.0, 10),
               "engine.fork_us": (2_000.0, 8),
               "sched.admission_wait_us": (300_000.0, 3)},
              {"engine.decode_steps": 10, "kv.prefix_hits": 3})


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_reads_nothing_without_its_instruments(name):
    read = harness.metric_reader(METRICS, name)
    assert read({"counters0": PARENT, "counters1": PARENT}) is None
    # and nothing over a window in which the instruments did not move
    assert read({"counters0": AFTER, "counters1": AFTER}) is None


@pytest.fixture(scope="module")
def small_run():
    from test_control import SEED, small_spec

    r = harness.Run(small_spec("qwen2-agent-fanout"), SEED + 1, 2.0, False,
                    t_start=time.perf_counter(), impl="fused_ref",
                    require_pallas=False, log=lambda *_: None)
    r.go()
    return r.run_record


def test_a_run_of_the_cell_reports_every_reader(small_run):
    got = {n: harness.metric_reader(METRICS, n)(small_run) for n in WANT}
    assert all(v is not None for v in got.values()), got
    # the window's turns fork four ways and commit one
    assert got["fork_us"] > 0 and got["commit_us"] > 0
    # the queue is part of the server's time to first token, which a
    # turn's prefill and at least one decode step follow
    assert 0 <= got["queue_wait_ms"] <= got["server_ttft_ms"]
    # the host share of a step and of a decode call
    assert 0 < got["decode_host_ms"] < got["step_host_ms"]
    # every turn re-sends the system prompt the cache holds
    assert 50.0 < got["cached_prompt_share"] <= 100.0
