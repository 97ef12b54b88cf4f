"""The one traffic generator: a schedule of agent sessions from parameters
and a seed.

A traffic mix is a data file, ``bench/traffic/<name>.json``; this module
turns it and ``--seed`` into a :class:`Schedule`, a pure function of the
two.

An agent session opens with a task over the shared system prompt, then
runs its turns one after another: each turn is a best-of-n exploration
whose winner commits; the agent then runs a tool for a think time and
sends the next turn, its prompt the last prompt, the winner's tokens and
the tool's result.  Sessions arrive in an open loop.

The sessions' scripts (task length, turns, each turn's tool result,
decode length and think time), the order in which they arrive and the
arrival gaps are fixed by the mix alone: sizes and gaps are quantiles
of their distributions, put in an order drawn once from the mix.  The
seed draws every token.  So every seed offers the same work at the
same times: a window holds about half a cycle, and an order drawn from
the seed would change which scripts and bursts fall inside it.

Keys of a mix (sizes are token counts, times seconds; a distribution is
``{"median", "sigma", "min", "max"}`` of a log-normal, cut at its ends):

``sessions``          scripts in one cycle of the schedule.
``session_rate_per_s`` mean session arrivals per second.
``arrival_cv``        coefficient of variation of the arrival gaps
                      (log-normal; 1 is as variable as Poisson, more is
                      burstier).
``system_tokens``     the system prompt every session shares.
``task_tokens``, ``turns``, ``tool_tokens``, ``new_tokens``, ``think_s``:
                      distributions of a session's opening task, its
                      number of turns, the tool result that opens each
                      later turn, and per turn the tokens each branch
                      decodes and the tool's time after it.
``best_of``, ``greedy_branches``, ``temperature``: the fan-out of a turn.
``warmup_s``          seconds of the mix offered before the window.
``check_requests``    finished turns compared with the reference.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np

MASK32 = 0xFFFFFFFF
SALT_ORDER, SALT_GAPS, SALT_SYSTEM, SALT_TASK, SALT_TOOL, SALT_SCRIPT = \
    1, 2, 3, 4, 5, 6


def _seed_words(seed: int) -> List[int]:
    seed = int(seed)
    return [seed & MASK32, (seed >> 32) & MASK32]


def rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng(_seed_words(seed) + [int(s) for s in salt])


def quantiles(dist: Dict[str, float], n: int) -> List[float]:
    """``n`` evenly spaced quantiles of a log-normal, cut at its ends."""
    z = NormalDist()
    mu, sigma = math.log(float(dist["median"])), float(dist["sigma"])
    lo, hi = float(dist["min"]), float(dist["max"])
    return [min(hi, max(lo, math.exp(mu + sigma * z.inv_cdf((i + 0.5) / n))))
            for i in range(n)]


def counts(dist: Dict[str, float], n: int) -> List[int]:
    return [int(round(q)) for q in quantiles(dist, n)]


def scripts(traffic: dict) -> List[dict]:
    """The cycle's session scripts, from the mix alone (no seed)."""
    m = int(traffic["sessions"])
    fixed = rng(0, SALT_SCRIPT)
    turns = fixed.permutation(counts(traffic["turns"], m)).tolist()
    tasks = fixed.permutation(counts(traffic["task_tokens"], m)).tolist()
    k = sum(turns)
    new = fixed.permutation(counts(traffic["new_tokens"], k)).tolist()
    think = fixed.permutation(quantiles(traffic["think_s"], k)).tolist()
    # the first turn opens with the task; every later one with a tool
    tool = [0] + fixed.permutation(
        counts(traffic["tool_tokens"], k - m)).tolist()
    out, j, jt = [], 0, 1
    for s in range(m):
        ts = []
        for t in range(turns[s]):
            ts.append({"tool": tool[jt] if t else 0, "new": new[j],
                       "think_s": think[j]})
            jt += bool(t)
            j += 1
        out.append({"task": tasks[s], "turns": ts})
    return out


def suffix_lengths(traffic: dict, page: int) -> List[int]:
    """Every uncached suffix a turn prefills: the prompt past the last
    whole page that an earlier prompt of its session (or the system
    prompt) left in the prefix cache.  The committed tokens are not
    cached, so a turn prefills them again with its tool result."""
    system = int(traffic["system_tokens"])
    out = set()
    for s in scripts(traffic):
        cached = (system // page) * page
        n = system + s["task"]
        out.add(n - 1 - cached)
        for i, t in enumerate(s["turns"][1:]):
            cached = ((n - 1) // page) * page
            n += s["turns"][i]["new"] + t["tool"]
            out.add(n - 1 - cached)
    return sorted(out)


class Schedule:
    """Session ``i``'s script, arrival gap and tokens, for any ``i``.

    Scripts repeat with period ``sessions``; tokens never do.
    """

    def __init__(self, traffic: dict, seed: int, vocab: int):
        self.traffic = traffic
        self.seed = int(seed)
        self.vocab = int(vocab)
        self.scripts = scripts(traffic)
        m = len(self.scripts)
        self.order = rng(0, SALT_ORDER).permutation(m).tolist()
        cv = float(traffic["arrival_cv"])
        sigma = math.sqrt(math.log(1.0 + cv * cv))
        q = quantiles({"median": 1.0, "sigma": sigma, "min": 0.0,
                       "max": math.inf}, m)
        scale = 1.0 / (float(traffic["session_rate_per_s"]) * np.mean(q))
        self.gaps = rng(0, SALT_GAPS).permutation(
            [g * scale for g in q]).tolist()

    def tokens(self, count: int, *salt: int) -> List[int]:
        return rng(self.seed, *salt).integers(1, self.vocab, count).tolist()

    def system(self) -> List[int]:
        return self.tokens(int(self.traffic["system_tokens"]), SALT_SYSTEM)

    def session(self, i: int) -> dict:
        """Session ``i``: its script, its task's tokens and its gap after
        session ``i - 1``."""
        k = i % len(self.scripts)
        s = self.scripts[self.order[k]]
        return {"index": i, "script": self.order[k], "gap_s": self.gaps[k],
                "task": self.tokens(s["task"], SALT_TASK, i),
                "turns": s["turns"]}

    def tool(self, i: int, turn: int, count: int) -> List[int]:
        """The tool result that opens turn ``turn`` of session ``i``."""
        return self.tokens(count, SALT_TOOL, i, turn)
