"""Drives the serving stack from the client's side.

Requests go to ``router.submit(SearchRequest)``; each answer is
timestamped by ``QueryFuture.add_done_callback``, which runs in the
serving thread that resolves it, so it only records and hands off.  One
client thread sends everything.
"""

from __future__ import annotations

import contextlib
import queue
import time
from typing import Dict, List, Optional

import jax
import numpy as np
from repro.core.futures import BackpressureError
from repro.serve.client import SearchRequest

from arrivals import due_times
from devtrace import WINDOW_SPAN

# how long after the window closes the client waits for late answers
LATE_WAIT_S = 60.0


class Ledger:
    """What happened to every request of one phase."""

    def __init__(self):
        self.query_idx: List[int] = []
        self.due: List[float] = []
        self.sent: List[float] = []
        self.done: List[float] = []
        self.answer: List[Optional[object]] = []   # SearchResponse or None
        self.error: List[Optional[str]] = []
        self.t_open = self.t_close = self.t_given_up = 0.0
        self._done_q: "queue.SimpleQueue[int]" = queue.SimpleQueue()

    def __len__(self) -> int:
        return len(self.due)

    def submit(self, router, query_idx: int, query: np.ndarray, mix: Dict,
               due: float) -> int:
        i = len(self.due)
        self.query_idx.append(query_idx)
        self.due.append(due)
        self.done.append(float("nan"))
        self.answer.append(None)
        self.error.append(None)
        req = SearchRequest(query=query, k=mix.get("k"),
                            top_n=mix.get("top_n"), tag=i)
        self.sent.append(time.perf_counter())
        try:
            fut = router.submit(req)
        except BackpressureError as exc:
            self._finish(i, None, f"refused: {exc}")
            return i
        fut.add_done_callback(lambda f, i=i: self._resolved(i, f))
        return i

    def _resolved(self, i: int, fut) -> None:
        exc = fut.exception() if not fut.cancelled() else None
        if fut.cancelled() or exc is not None:
            self._finish(i, None, repr(exc) if exc else "cancelled")
        else:
            self._finish(i, fut.result(), None)

    def _finish(self, i: int, answer, error: Optional[str]) -> None:
        t = time.perf_counter()
        self.answer[i] = answer
        self.error[i] = error
        self.done[i] = t
        self._done_q.put(i)

    def wait_all(self, until: float) -> None:
        """Wait until every request has resolved, or ``until``."""
        while any(np.isnan(self.done)) and time.perf_counter() < until:
            try:
                self._done_q.get(timeout=0.05)
            except queue.Empty:
                pass

    def n_open(self) -> int:
        return int(np.sum(np.isnan(self.done)))

    def latencies_s(self) -> np.ndarray:
        """Per request, from the time it was due (in a closed loop, its
        send); one never answered counts until the client stopped
        waiting."""
        start = np.asarray(self.due)
        done = np.asarray(self.done)
        ok = np.array([a is not None for a in self.answer], bool)
        return np.where(ok & ~np.isnan(done), done, self.t_given_up) - start


def run_phase(router, pool: np.ndarray, first_query: int,
              mix: Dict, seconds: float, seed: int,
              span: bool = False) -> Ledger:
    """Send the mix's traffic for ``seconds``, then wait for its answers.
    Queries are taken from the pool in order from ``first_query``.  With
    ``span`` the sending is wrapped in the profiler's ``bench.window`` host
    span, which a trace's reduction clips to."""
    led = Ledger()
    nxt = first_query

    def send(due: float) -> None:
        nonlocal nxt
        led.submit(router, nxt, pool[nxt % len(pool)], mix, due)
        nxt += 1

    with (jax.profiler.TraceAnnotation(WINDOW_SPAN) if span
          else contextlib.nullcontext()):
        led.t_open = time.perf_counter()
        _send_for(led, send, mix, led.t_open + seconds, seed)
        led.t_close = time.perf_counter()
    led.wait_all(led.t_close + LATE_WAIT_S)
    led.t_given_up = time.perf_counter()
    return led


def _send_for(led: Ledger, send, mix: Dict, t_end: float, seed: int) -> None:
    if mix["loop"] == "closed":
        for _ in range(int(mix["outstanding"])):
            send(time.perf_counter())
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            try:
                led._done_q.get(timeout=min(0.05, t_end - now))
            except queue.Empty:
                continue
            if time.perf_counter() < t_end:
                send(time.perf_counter())
    elif mix["loop"] == "open":
        for due in led.t_open + due_times(mix, t_end - led.t_open, seed):
            delay = due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            send(due)
        delay = t_end - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")


def shape_sweep(router, pool: np.ndarray, first_query: int,
                mix: Dict, max_burst: int) -> int:
    """Warm-up: bursts of 1..max_burst requests at once, each answered
    before the next, so every batch size the stack can form has run.
    Returns the next unused pool index."""
    nxt = first_query
    for b in range(1, max_burst + 1):
        led = Ledger()
        for _ in range(b):
            led.submit(router, nxt, pool[nxt % len(pool)], mix,
                       time.perf_counter())
            nxt += 1
        led.wait_all(time.perf_counter() + LATE_WAIT_S)
    return nxt
