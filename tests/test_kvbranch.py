"""KVBranchManager: CoW page tables, refcounts, fork/commit/abort."""

import os
import random
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core import KVBranchManager, SeqStatus, StaleBranchError
from repro.core.errors import (BranchError, BranchStateError, Errno,
                               FrozenOriginError)


@pytest.fixture
def kv():
    return KVBranchManager(num_pages=64, page_size=4)


def fill(kv, sid, n):
    return [kv.prepare_append(sid)[0] for _ in range(n)]


def test_new_seq_allocates_ceil_pages(kv):
    sid = kv.new_seq(length=9)  # 9 tokens, page=4 -> 3 pages
    assert len(kv.block_table(sid)) == 3
    assert kv.free_pages == 64 - 3


def test_append_fills_pages_in_order(kv):
    sid = kv.new_seq()
    slots = fill(kv, sid, 6)
    assert [s.offset for s in slots] == [0, 1, 2, 3, 0, 1]
    assert len(kv.block_table(sid)) == 2


def test_fork_shares_pages_zero_copy(kv):
    sid = kv.new_seq(length=8)
    free_before = kv.free_pages
    c1, c2 = kv.fork(sid, n=2)
    assert kv.free_pages == free_before  # no page allocated by fork
    assert kv.block_table(c1) == kv.block_table(sid)
    for p in kv.block_table(sid):
        assert kv.refcount(p) == 3  # parent + 2 children


def test_parent_frozen_while_children_live(kv):
    sid = kv.new_seq(length=4)
    kv.fork(sid, n=2)
    with pytest.raises(FrozenOriginError):
        kv.prepare_append(sid)


def test_cow_on_shared_tail_page(kv):
    sid = kv.new_seq()
    fill(kv, sid, 6)  # page0 full, page1 has 2 tokens
    tail = kv.block_table(sid)[-1]
    c1, c2 = kv.fork(sid, n=2)
    # first append on c1 must CoW the shared tail page
    (slot,) = kv.prepare_append(c1)
    assert slot.cow, "expected a CoW page copy"
    assert slot.cow[0].src_page == tail
    assert kv.block_table(c1)[-1] == slot.cow[0].dst_page != tail
    assert slot.offset == 2
    # sibling and parent tables untouched
    assert kv.block_table(c2)[-1] == tail
    assert kv.block_table(sid)[-1] == tail
    # full pages stay shared (prefix sharing)
    assert kv.refcount(kv.block_table(sid)[0]) == 3


def test_page_aligned_fork_appends_without_cow(kv):
    sid = kv.new_seq()
    fill(kv, sid, 4)  # exactly one full page
    (c,) = kv.fork(sid)
    (slot,) = kv.prepare_append(c)
    assert not slot.cow  # new page, no copy needed
    assert slot.offset == 0


def test_commit_promotes_table_and_invalidates_siblings(kv):
    sid = kv.new_seq()
    fill(kv, sid, 4)
    c1, c2 = kv.fork(sid, n=2)
    fill(kv, c1, 3)
    parent = kv.commit(c1)
    assert parent == sid
    assert kv.length(sid) == 7
    assert not kv.is_live(c2)
    with pytest.raises(StaleBranchError):
        kv.prepare_append(c2)
    # parent resumes active and appendable
    assert kv.is_live(sid)
    kv.prepare_append(sid)


def test_commit_recycles_sibling_pages(kv):
    sid = kv.new_seq()
    fill(kv, sid, 4)
    c1, c2, c3 = kv.fork(sid, n=3)
    fill(kv, c1, 5)  # c1 allocates 2 pages (CoW? no: tail full -> fresh)
    fill(kv, c2, 9)
    fill(kv, c3, 1)
    used_before = kv.num_pages - kv.free_pages
    kv.commit(c1)
    used_after = kv.num_pages - kv.free_pages
    assert used_after < used_before  # losers' private pages recycled
    # exactly the winner chain remains: parent table pages all refcount 1
    for p in kv.block_table(sid):
        assert kv.refcount(p) == 1


def test_abort_frees_private_pages_keeps_shared(kv):
    sid = kv.new_seq()
    fill(kv, sid, 4)
    c1, c2 = kv.fork(sid, n=2)
    fill(kv, c1, 5)
    kv.abort(c1)
    assert not kv.is_live(c1)
    assert kv.is_live(c2)
    assert kv.refcount(kv.block_table(sid)[0]) == 2  # parent + c2
    # parent still frozen (c2 alive)
    with pytest.raises(FrozenOriginError):
        kv.prepare_append(sid)
    kv.abort(c2)
    # all children resolved -> parent resumes
    kv.prepare_append(sid)


def test_nested_fork_commit(kv):
    sid = kv.new_seq()
    fill(kv, sid, 4)
    (c,) = kv.fork(sid)
    fill(kv, c, 2)
    g1, g2 = kv.fork(c, n=2)
    fill(kv, g1, 1)
    kv.commit(g1)  # commits into c only
    assert kv.length(c) == 7
    assert kv.length(sid) == 4
    assert not kv.is_live(g2)
    kv.commit(c)
    assert kv.length(sid) == 7


def test_commit_with_live_children_rejected(kv):
    sid = kv.new_seq(length=4)
    (c,) = kv.fork(sid)
    kv.fork(c, n=2)
    with pytest.raises(BranchStateError):
        kv.commit(c)


def test_pool_exhaustion_is_enospc(kv):
    sid = kv.new_seq(length=64 * 4)  # exactly the pool
    with pytest.raises(MemoryError):
        kv.prepare_append(sid)  # needs a 65th page


def test_dense_block_tables_padding(kv):
    s1 = kv.new_seq(length=5)
    s2 = kv.new_seq(length=1)
    bt, lens = kv.dense_block_tables([s1, s2], max_pages=4)
    assert bt.shape == (2, 4)
    assert lens.tolist() == [5, 1]
    assert bt[0, :2].tolist() == kv.block_table(s1)
    assert (bt[1, 1:] == 0).all()


def test_release_frees_everything(kv):
    sid = kv.new_seq(length=16)
    kv.release(sid)
    assert kv.free_pages == 64
    assert not kv.is_live(sid)


def test_stats(kv):
    sid = kv.new_seq(length=8)
    kv.fork(sid, n=2)
    st = kv.stats()
    assert st["pages_shared"] == 2
    assert st["sequences_live"] == 3


# ---------------------------------------------------------------------------
# double-release hardening: _decref validates BEFORE mutating, raises
# BranchError(EINVAL), and the guard survives ``python -O``
# ---------------------------------------------------------------------------

def test_double_release_raises_einval_allocator_untouched(kv):
    sid = kv.new_seq(length=8)
    pages = kv.block_table(sid)
    kv.release(sid)
    free_before = kv.free_pages
    with pytest.raises(BranchError) as ei:
        kv._decref(pages)
    assert ei.value.errno is Errno.EINVAL
    # validate-before-mutate: nothing re-entered the free list, no
    # refcount went negative
    assert kv.free_pages == free_before
    assert all(kv.refcount(p) == 0 for p in pages)
    # the pool still hands out every page exactly once
    seen = kv.block_table(kv.new_seq(length=64 * 4))
    assert len(seen) == len(set(seen)) == 64


def test_decref_is_occurrence_aware(kv):
    # a page listed k times needs k outstanding references — one ref
    # plus a duplicate entry must NOT free it and then free it again
    sid = kv.new_seq(length=4)
    (p,) = kv.block_table(sid)
    assert kv.refcount(p) == 1
    with pytest.raises(BranchError) as ei:
        kv._decref([p, p])
    assert ei.value.errno is Errno.EINVAL
    assert kv.refcount(p) == 1
    assert kv.free_pages == 63
    kv.prepare_append(sid)  # the sequence is still fully usable


def test_truncate_then_release_shared_pages_stay_consistent(kv):
    # the historical corruption: truncate dropped a shared page's ref,
    # then releasing the fork origin freed it again, double-inserting it
    # into the free list
    sid = kv.new_seq(length=16)               # 4 pages
    (child,) = kv.fork(sid)
    kv.truncate(child, 4)                     # drops 3 shared refs
    shared = kv.block_table(sid)
    assert [kv.refcount(p) for p in shared] == [2, 1, 1, 1]
    kv.release(child)
    kv.release(sid)
    assert kv.free_pages == 64
    # every page is free exactly once: drain the pool and check dupes
    drained = kv.block_table(kv.new_seq(length=64 * 4))
    assert len(set(drained)) == 64


def test_double_release_guard_survives_python_O(tmp_path):
    # ``python -O`` strips assert statements; the guard must be a real
    # raise.  Run the double release in an optimized subprocess.
    import repro
    src = str(Path(repro.__file__).resolve().parents[1])
    code = "\n".join([
        "from repro.core import KVBranchManager",
        "from repro.core.errors import BranchError, Errno",
        "kv = KVBranchManager(num_pages=8, page_size=4)",
        "sid = kv.new_seq(length=4)",
        "pages = kv.block_table(sid)",
        "kv.release(sid)",
        "try:",
        "    kv._decref(pages)",
        "except BranchError as e:",
        "    if e.errno is not Errno.EINVAL:",
        "        raise SystemExit(f'wrong errno: {e.errno!r}')",
        "    print('GUARDED', kv.free_pages)",
        "else:",
        "    raise SystemExit('double release silently succeeded under -O')",
    ])
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-O", "-c", code],
                          capture_output=True, text=True, env=env,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert "GUARDED 8" in proc.stdout


# ---------------------------------------------------------------------------
# randomized interleavings: refcounts always equal live-table +
# prefix-registry references, and the free list never double-lists
# ---------------------------------------------------------------------------

def _check_refcount_invariants(kv):
    from collections import Counter
    refs = Counter()
    for s, table in kv._tables.items():
        if kv.is_live(s):
            refs.update(table)
    refs.update(kv._prefix_pages.values())
    for p in range(kv.num_pages):
        assert kv.refcount(p) == refs[p], (
            f"page {p}: refcount {kv.refcount(p)} != {refs[p]} references")
    free = list(kv._free)
    assert len(free) == len(set(free)), "free list double-lists a page"
    assert set(free) == {p for p in range(kv.num_pages)
                         if kv.refcount(p) == 0}, (
        "free list out of sync with zero-refcount pages")


def test_random_op_interleavings_preserve_invariants():
    rng = random.Random(0xC0FFEE)
    kv = KVBranchManager(num_pages=48, page_size=4)
    for step in range(600):
        live = [s for s in list(kv._tables)
                if kv.is_live(s) and not kv.is_tiered(s)]
        tiered = [s for s in list(kv._tiered_pages) if kv.is_live(s)]
        ops = ["new", "adopt"]
        if live:
            ops += ["append", "fork", "release", "truncate", "commit",
                    "abort", "demote", "register"]
        if tiered:
            ops += ["promote", "release_tiered"]
        op = rng.choice(ops)
        try:
            if op == "new":
                kv.new_seq(length=rng.randrange(0, 13))
            elif op == "adopt":
                toks = [rng.randrange(1, 9) for _ in range(8)]
                pages, covered = kv.match_prefix(toks)
                kv.new_seq(length=max(covered, rng.randrange(0, 13)),
                           prefix_pages=pages)
            elif op == "append":
                kv.prepare_append(rng.choice(live), rng.randrange(1, 6))
            elif op == "fork":
                kv.fork(rng.choice(live), n=rng.randrange(1, 3))
            elif op == "release":
                kv.release(rng.choice(live))
            elif op == "truncate":
                s = rng.choice(live)
                kv.truncate(s, rng.randrange(0, kv.length(s) + 1))
            elif op == "commit":
                kv.commit(rng.choice(live))
            elif op == "abort":
                kv.abort(rng.choice(live))
            elif op == "demote":
                kv.demote(rng.choice(live))
            elif op == "register":
                s = rng.choice(live)
                toks = [rng.randrange(1, 9) for _ in range(kv.length(s))]
                kv.register_prefix(s, toks)
            elif op == "promote":
                kv.promote(rng.choice(tiered))
            elif op == "release_tiered":
                kv.release(rng.choice(tiered))
        except (BranchError, MemoryError, ValueError):
            pass  # rejected ops must leave state consistent too
        _check_refcount_invariants(kv)


def test_clear_prefix_cache_returns_the_pool():
    """Released sequences plus a cleared prefix cache leave every page
    free; a live sharer keeps its pages through the clear."""
    kv = KVBranchManager(num_pages=16, page_size=4)
    prompt = list(range(1, 11))                 # 2 full pages + a tail
    a = kv.new_seq(length=len(prompt))
    assert kv.register_prefix(a, prompt) == 3
    pages, covered = kv.match_prefix(prompt)
    b = kv.new_seq(length=covered, prefix_pages=pages)
    kv.release(a)
    assert kv.clear_prefix_cache() == 3
    assert kv.match_prefix(prompt) == ([], 0)
    assert kv.free_pages == 16 - len(kv.block_table(b))
    _check_refcount_invariants(kv)
    kv.release(b)
    assert kv.free_pages == 16
    _check_refcount_invariants(kv)
