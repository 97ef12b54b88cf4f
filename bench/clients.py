"""The client that offers an agent mix to the system, turn by turn.

It reads only the :class:`~bench.generator.Schedule` and talks to the
system through its public entry point: each turn is an exploration
launched with ``ExplorationDriver.explore`` running the benchmark's
``best_of`` policy.

Sessions arrive in an open loop, at the schedule's gaps, the first when
the client starts.  A session's turns run one after another: when a
turn commits, the agent runs its tool for the turn's think time and
then sends the next turn, whose prompt is the last prompt, the winner's
tokens and the tool's result.
A turn is timed from when it was due: its session's arrival, or its
tool's end.
"""

from __future__ import annotations

import heapq
from typing import Any, Callable, Dict, List, Optional

from bench.generator import Schedule
from bench.policy import best_of


class Req:
    """One turn as the client sees it."""

    def __init__(self, index: int, t0: float, prompt: List[int]):
        self.index = index
        self.t0 = t0                   # when it was due
        self.prompt = prompt
        self.session: Optional[dict] = None
        self.admitted_t: Optional[float] = None
        self.done_t: Optional[float] = None
        self.seqs: List[int] = []
        self.exp: Any = None
        self.watch: Optional[Dict[int, list]] = None
        self.logits: Optional[list] = None   # the greedy branch's rows
        self.greedy_gen: Optional[List[int]] = None
        self.winner_gen: Optional[List[int]] = None
        self.final_tokens: Optional[List[int]] = None
        self.error: Optional[BaseException] = None
        self.degraded = False
        self.losers_alive = 0


class AgentClient:
    """Agent sessions of best-of-n turns over a shared system prompt."""

    def __init__(self, traffic: dict, sched: Schedule, driver: Any,
                 annotate: Callable):
        self.t = traffic
        self.sched = sched
        self.driver = driver
        self.annotate = annotate
        self.system = sched.system()
        self.next_session = 0
        self.next_due: Optional[float] = None
        self.waiting: List[tuple] = []     # (due, n, session): tool running
        self.n_turns = 0
        self.inflight: List[Req] = []
        self.done: List[Req] = []
        self.lag: List[float] = []
        self.offering = True
        self.watch: Optional[Dict[int, list]] = None

    def start(self, now: float) -> None:
        self.next_due = now            # the first session arrives at once

    def _launch(self, st: dict, due: float, now: float) -> None:
        turn = st["script"]["turns"][st["turn"]]
        self.lag.append(now - due)
        req = Req(self.n_turns, due, st["context"])
        self.n_turns += 1
        req.session = st
        req.watch = self.watch
        req.exp = self.driver.explore(
            req.prompt, max_new_tokens=turn["new"] + 1, policy=best_of,
            n=int(self.t["best_of"]), tokens=turn["new"],
            temperature=float(self.t["temperature"]),
            greedy_branches=int(self.t["greedy_branches"]), rec=req,
            annotate=self.annotate, name=f"turn-{req.index}")
        self.inflight.append(req)

    def pump(self, now: float) -> None:
        """Send every turn that is due by ``now``."""
        if not self.offering:
            return
        while self.next_due <= now:
            s = self.sched.session(self.next_session)
            self.next_session += 1
            st = {"script": s, "turn": 0, "context": self.system + s["task"]}
            self._launch(st, self.next_due, now)
            self.next_due += self.sched.session(self.next_session)["gap_s"]
        while self.waiting and self.waiting[0][0] <= now:
            due, _, st = heapq.heappop(self.waiting)
            self._launch(st, due, now)

    def next_event(self) -> Optional[float]:
        if not self.offering:
            return None
        return min([self.next_due] + [w[0] for w in self.waiting[:1]])

    def collect(self, now: float) -> None:
        """Take in the turns that completed in the last step; a session
        with turns left runs its tool, then sends the next."""
        still = []
        for req in self.inflight:
            exp = req.exp
            if not exp.done:
                still.append(req)
                continue
            req.done_t = now
            req.error = exp.error
            req.final_tokens = exp.final_tokens
            req.exp = None
            self.done.append(req)
            st = req.session
            turns = st["script"]["turns"]
            nxt = st["turn"] + 1
            if (nxt < len(turns) and req.error is None
                    and req.winner_gen is not None):
                tool = self.sched.tool(st["script"]["index"], nxt,
                                       turns[nxt]["tool"])
                st = dict(st, turn=nxt,
                          context=req.prompt + req.winner_gen + tool)
                due = now + turns[nxt - 1]["think_s"]
                heapq.heappush(self.waiting, (due, req.index, st))
        self.inflight = still

    def abandon(self, req: Req) -> None:
        """Retire an unfinished turn now, releasing all it holds (one
        still queued for admission holds no pages: its handle closes)."""
        hd, session = req.exp.hd, self.driver.session
        if hd is not None:
            if session.admitted(hd):
                session.finish(hd)
            else:
                session.close(hd)
        req.exp = None
        self.inflight.remove(req)
