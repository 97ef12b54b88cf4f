"""The comparison that decides ``correct`` fails when it should.

Each test drives a whole run of a cell at a small size on the CPU — the
harness's look for a chip skipped, everything else as on the chip —
and judges what the window served.  A sound run is correct and builds
no program in the window.  With the timed path broken underneath (a
served token altered where the decode step produces it; a decode step
that returns the KV pools it was given, unchanged) ``correct`` comes
out false, and so it does with the control, the reference computed in
float8, judged in the program's place.
"""

import time

import numpy as np
import pytest

from bench import harness

SEED = (1 << 32) + 4242
CELL = "qwen2-agent-fanout"


def small_spec(cell: str) -> dict:
    spec = harness.load_spec(cell)
    m = spec["config"]["model"]
    small = dict(m, num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                 head_dim=16, d_ff=128, vocab_size=512)
    spec["config"] = dict(spec["config"], model=small)
    spec["config"]["engine"] = dict(spec["config"]["engine"], num_pages=600,
                                    max_pages_per_seq=64, max_batch=16)
    spec["traffic"] = dict(
        spec["traffic"], system_tokens=64, sessions=6,
        session_rate_per_s=2.0, warmup_s=2.0,
        task_tokens={"median": 12, "sigma": 0.5, "min": 4, "max": 40},
        tool_tokens={"median": 16, "sigma": 0.7, "min": 2, "max": 48},
        new_tokens={"median": 8, "sigma": 0.5, "min": 2, "max": 20},
        think_s={"median": 0.05, "sigma": 0.5, "min": 0.01, "max": 0.2})
    return spec


def run(spec, **kw) -> dict:
    r = harness.Run(spec, SEED, 2.0, False, t_start=time.perf_counter(),
                    impl="fused_ref", require_pallas=False,
                    log=lambda *_: None, **kw)
    out = r.go()
    out["_run"] = r
    return out


@pytest.fixture(scope="module")
def clean():
    return run(small_spec(CELL))


def test_a_sound_run_is_correct(clean):
    assert clean["correct"], clean["checks"]
    assert clean["attempted"] > 0
    assert clean["_run"].run_record["compiles"] == 0


def _broken_step(monkeypatch, fault):
    from repro.runtime import serve_loop

    step = serve_loop.paged_fused_decode_step

    def broken(cfg, params, k_pages, v_pages, *args, **kw):
        logits, kp, vp = step(cfg, params, k_pages, v_pages, *args, **kw)
        if fault == "token":
            # the greedy pick moves to the next vocabulary entry
            return np.roll(np.asarray(logits), 1, axis=-1), kp, vp
        return logits, k_pages, v_pages       # the state left unchanged

    monkeypatch.setattr(serve_loop, "paged_fused_decode_step", broken)


@pytest.mark.parametrize("fault", ["token", "state"])
def test_a_broken_decode_step_is_not_correct(monkeypatch, fault):
    _broken_step(monkeypatch, fault)
    out = run(small_spec(CELL))
    assert not out["correct"]
    err = out["checks"]["logit_rel_err"]
    assert err["value"] > err["limit"]


def test_the_float8_control_is_not_correct(clean):
    r = clean["_run"]
    ctl = r.judge(dict(clean), "fp8")
    assert not ctl["correct"]
    prog = clean["checks"]["logit_rel_err"]["value"]
    assert ctl["checks"]["logit_rel_err"]["value"] >= 3 * prog
