"""Decode fast path: fused one-dispatch steps, int8 KV, fused verify.

Engine-level guarantees of DESIGN §12:

* ``attn_impl="fused_ref"`` is token-identical to the legacy ``"ref"``
  two-dispatch path — including across fork/CoW, where the fused step
  services every fault inline (``cow_dispatches`` stays 0);
* interpret-mode Pallas inside the fused step agrees too, so the kernel
  that ships to TPU is exercised by CPU CI;
* ``kv_dtype="int8"`` survives a full fork -> decode -> commit cycle
  with logits within int8 rounding of the fp pools;
* ``spec_verify`` equals a sequential greedy verifier branch, one
  dispatch for k draft tokens.
"""

import dataclasses
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.select import INTERPRET_ENV, resolve_impl
from repro.models.model import Model
from repro.runtime import serve_loop
from repro.runtime.serve_loop import ServeEngine, _pad_pow2

REPO = Path(__file__).resolve().parents[1]


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


def exercise(eng, prompt=(5, 17, 3, 42, 7, 11, 2, 9, 30, 4, 8, 1, 22)):
    """A lifecycle workout: decode, fork (lazy CoW), decode children,
    commit one, keep decoding.  Returns every token produced in order.

    The 13-token prompt leaves a partially-filled tail page, so the
    fork's first child append CoW-faults — on the fast path that fault
    must ride the decode dispatch itself.
    """
    out = []
    sid = eng.add_request(list(prompt))
    out += eng.decode([sid])
    kids = eng.fork(sid, 3)
    out += eng.decode(kids)           # CoW faults on the shared tail
    out += eng.decode(kids)
    out += eng.decode(kids)
    eng.commit(kids[1])
    out += eng.decode([sid])
    return out, sid


def test_fused_ref_token_identical_to_legacy(engine_setup):
    legacy = fresh_engine(engine_setup, attn_impl="ref")
    fused = fresh_engine(engine_setup, attn_impl="fused_ref")
    t_legacy, _ = exercise(legacy)
    t_fused, _ = exercise(fused)
    assert t_legacy == t_fused
    # the legacy path paid separate CoW dispatches; the fused path none
    assert legacy.cow_dispatches > 0
    assert fused.cow_dispatches == 0
    assert fused.cow_faults == legacy.cow_faults   # same faults serviced
    assert fused.cow_inline_steps > 0


def test_interpret_kernel_token_identical(engine_setup):
    """The actual Pallas kernel body (interpreted) inside the engine."""
    legacy = fresh_engine(engine_setup, attn_impl="ref")
    kern = fresh_engine(engine_setup, attn_impl="interpret")
    t_legacy, _ = exercise(legacy)
    t_kern, _ = exercise(kern)
    assert t_legacy == t_kern
    assert kern.cow_dispatches == 0


# int8 KV moves this model's logits (scale ~3) by a few hundredths
INT8_LOGIT_ATOL = 0.1


def _teacher_forced(eng, script):
    """Make ``eng.decode`` emit ``script``'s next step instead of its own
    greedy pick, so two engines decode the very same contexts.  The
    forced token replaces the pending one, whose KV is not written yet.
    """
    real = eng.decode

    def decode(seq_ids, **kw):
        real(seq_ids, **kw)
        want = next(script)
        for s, t in zip(seq_ids, want):
            eng.token_domain.truncate(s, len(eng.token_domain.get(s)) - 1)
            eng.token_domain.append(s, t)
        return want

    eng.decode = decode


def test_int8_kv_full_cycle_greedy_parity(engine_setup, monkeypatch):
    """int8 pools through fork -> decode -> commit track the fp pools.

    The int8 engine is teacher-forced on the fp engine's tokens, so each
    step compares the logits of one context: they must agree within
    ``INT8_LOGIT_ATOL``, and the greedy tokens must agree wherever the fp
    top-2 margin exceeds twice that (closer calls are int8 near-ties).
    """
    logits = []
    for name in ("paged_decode_step", "paged_fused_decode_step"):
        def record(*a, _step=getattr(serve_loop, name), **k):
            out = _step(*a, **k)
            logits.append(np.asarray(out[0][:, 0], np.float32))
            return out
        monkeypatch.setattr(serve_loop, name, record)

    legacy = fresh_engine(engine_setup, attn_impl="ref")
    q8 = fresh_engine(engine_setup, kv_dtype="int8")
    # auto resolves to fused_ref on plain CPU, interpret under the CI
    # env flag — anything but the oracle-only "ref" path
    assert q8.quantized and q8.attn_impl != "ref" and q8.fast_path
    steps = []
    real = legacy.decode

    def recorded(ids, **kw):
        steps.append(real(ids, **kw))
        return steps[-1]

    legacy.decode = recorded
    t_legacy, sid_l = exercise(legacy)
    # keep decoding the committed winner: scales follow the pages
    for _ in range(4):
        legacy.decode([sid_l])
    fp_logits, logits[:] = list(logits), []
    _teacher_forced(q8, iter(steps))
    t_q8, sid_q = exercise(q8)
    for _ in range(4):
        q8.decode([sid_q])
    assert t_q8 == t_legacy and len(logits) == len(fp_logits)
    for lf, lq in zip(fp_logits, logits):
        np.testing.assert_allclose(lq, lf, rtol=0, atol=INT8_LOGIT_ATOL)
        top2 = np.sort(lf, axis=-1)[:, -2:]
        clear = top2[:, 1] - top2[:, 0] > 2 * INT8_LOGIT_ATOL
        assert (lq.argmax(-1) == lf.argmax(-1))[clear].all()


def test_int8_scales_copied_on_eager_fork(engine_setup):
    """Eager fork CoW must move scales with pages (one fused dispatch)."""
    eng = fresh_engine(engine_setup, kv_dtype="int8")
    sid = eng.add_request(list(range(1, 14)))
    eng.decode([sid])        # length 13: the tail page is now partial
    before = eng.cow_dispatches
    kids = eng.fork(sid, 2, eager_cow=True)
    assert eng.cow_dispatches == before + 1
    # children's private tail pages dequant identically to the parent's
    t0 = eng.decode([kids[0]])
    t1 = eng.decode([kids[1]])
    assert t0 == t1                  # same context -> same greedy token


def test_spec_verify_matches_sequential_verifier(engine_setup):
    """One fused verify dispatch == a greedy verifier branch's k steps."""
    for impl in ("ref", "fused_ref", "interpret"):
        eng = fresh_engine(engine_setup, attn_impl=impl)
        sid = eng.add_request([9, 8, 7, 6, 5])
        eng.decode([sid])
        # the sequential oracle: fork a branch and decode greedily
        (branch,) = eng.fork(sid, 1)
        seq_tokens = [eng.decode([branch])[0] for _ in range(4)]
        # drafts scored against the frozen origin in one dispatch
        drafts = [seq_tokens,                       # the true greedy path
                  [seq_tokens[0], 0, 1, 2],        # diverges at step 1
                  [0, 1, 2, 3]]                    # diverges immediately
        rows = eng.spec_verify(sid, drafts)
        assert eng.verify_dispatches == 1
        # row 0 teacher-forces the greedy path -> reproduces it exactly
        assert rows[0] == seq_tokens
        # every row's position 0 is the target's next token (it depends
        # only on the shared pending token)
        assert all(r[0] == seq_tokens[0] for r in rows)
        # after a draft diverges, the row keeps tracking the *target
        # given the draft*, which is what lcp acceptance needs; the
        # prefix up to the divergence still matches
        assert rows[1][:2] == seq_tokens[:2]


def test_spec_verify_validates_drafts(engine_setup):
    eng = fresh_engine(engine_setup, attn_impl="fused_ref")
    sid = eng.add_request([1, 2, 3])
    with pytest.raises(ValueError):
        eng.spec_verify(sid, [])
    with pytest.raises(ValueError):
        eng.spec_verify(sid, [[1, 2], [1]])


def test_int8_requires_fused_path(engine_setup):
    with pytest.raises(ValueError, match="fused"):
        fresh_engine(engine_setup, attn_impl="ref", kv_dtype="int8")
    with pytest.raises(ValueError):
        fresh_engine(engine_setup, kv_dtype="int4")


def test_pad_pow2_empty_returns_empty():
    """Regression: an empty CoW op list used to IndexError on src[-1]."""
    s, d = _pad_pow2([], [])
    assert s.shape == (0,) and d.shape == (0,)
    assert s.dtype == jnp.int32 and d.dtype == jnp.int32
    # non-empty lists still pad to the enclosing power of two
    s, d = _pad_pow2([3, 4, 5], [7, 8, 9])
    assert s.shape == (4,) and list(np.asarray(s)) == [3, 4, 5, 5]


def test_resolve_impl_env(monkeypatch):
    monkeypatch.delenv(INTERPRET_ENV, raising=False)
    assert resolve_impl("auto") == "ref"          # CPU backend in CI
    assert resolve_impl("auto", cpu_fallback="fused_ref") == "fused_ref"
    monkeypatch.setenv(INTERPRET_ENV, "1")
    assert resolve_impl("auto") == "interpret"
    assert resolve_impl("ref") == "ref"           # explicit impl wins
    monkeypatch.setenv(INTERPRET_ENV, "0")
    assert resolve_impl("auto") == "ref"


def test_tp2_fused_token_parity_subprocess():
    """tp=2 fused decode + verify == single-device, token for token."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count=2"
        import dataclasses, jax
        from repro.configs import get_config
        from repro.models.model import Model
        from repro.runtime.serve_loop import ServeEngine

        cfg = dataclasses.replace(get_config("paper-agentic"),
                                  dtype="float32")
        model = Model(cfg, attn_chunk=8, remat=False)
        params = model.init(jax.random.PRNGKey(0))

        def run(**kw):
            eng = ServeEngine(model, params, num_pages=64, page_size=4,
                              max_pages_per_seq=16,
                              attn_impl="fused_ref", **kw)
            sid = eng.add_request(list(range(1, 14)))
            out = eng.decode([sid])
            kids = eng.fork(sid, 2)
            out += eng.decode(kids)
            out += eng.decode(kids)
            ver = eng.spec_verify(kids[0], [[5, 6, 7], [1, 2, 3]])
            assert eng.cow_dispatches == 0
            return out, ver

        t1, v1 = run()
        t2, v2 = run(tp=2)
        assert t1 == t2, (t1, t2)
        assert v1 == v2, (v1, v2)
        print("SUBPROC_OK")
    """)
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=900)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-4000:]}"
    assert "SUBPROC_OK" in r.stdout
