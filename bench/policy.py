"""The benchmark's copy of the best-of-n exploration policy.

The same shape as the program's ``best_of_n`` (fork n, decode each, commit
the best by mean token id), kept here so that what a request does cannot
change under the benchmark.  Two differences: the first
``greedy_branches`` branches decode greedily, so their tokens can be
checked against the reference, and the policy leaves on its request
record what the check needs (the greedy branch's tokens and, in the
window, the list the harness fills with its logits; the winner's tokens;
whether the losers were invalidated).
"""

from __future__ import annotations

import time
from typing import Any, Generator

import numpy as np

from repro.core.errors import BranchError
from repro.explore_ctx.driver import Decode, Fork


def best_of(ctx: Any, *, n: int, tokens: int, temperature: float,
            greedy_branches: int, rec: Any, annotate: Any) -> Generator:
    rec.admitted_t = time.perf_counter()
    try:
        kids = yield Fork(ctx, n)
    except BranchError:
        # permanent page pressure: the request is served unforked and
        # counts as failed
        rec.degraded = True
        yield Decode([ctx], tokens, greedy=True)
        return None
    rec.seqs = [k.seq for k in kids]
    if rec.watch is not None and greedy_branches:
        # the harness keeps the greedy branch's logits, row by row
        rec.logits = rec.watch[kids[0].seq] = []
    greedy = [i < greedy_branches for i in range(n)]
    yield Decode(kids, tokens, greedy=greedy, temperature=temperature)
    gens = [k.generated() for k in kids]
    scores = [float(np.mean(g)) if g else float("-inf") for g in gens]
    w = int(np.argmax(scores))
    if greedy_branches:
        rec.greedy_gen = gens[0]
    rec.winner_gen = gens[w]
    with annotate("policy.commit"):
        kids[w].commit()
    rec.losers_alive = sum(1 for i, k in enumerate(kids)
                           if i != w and k.alive)
    return w
