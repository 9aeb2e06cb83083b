"""Closed- and open-loop drivers that keep every request of the window.

A driver returns a :class:`Window`: for request ``k`` its ids, when it was
due (open loop) or issued (closed loop), when its answer came, and the
answer or the error. Answers are compared with the reference only after
the window has closed; latencies are exact, not bucketed.

The bookkeeping lives in preallocated arrays and flat lists, with no
object per request that the garbage collector tracks, so that the window's
own records do not lengthen the client process's collections as the
window goes on.
"""

from __future__ import annotations

import math
import threading
import time

import numpy as np

#: seconds past the window's close that an answer may still come in
ANSWER_WAIT_S = 60.0
#: an open-loop request sent this long after it was due counts as late
LATE_S = 0.001


class Window:
    """The requests of one window (or of a warm-up)."""

    def __init__(self, ids: list, multi: bool):
        n = len(ids)
        self.ids = ids        # per request: an array of ids, or one id
        self.multi = multi
        self.t_ref = np.full(n, np.nan)
        self.t_done = np.full(n, np.nan)
        self.answers: list = [None] * n
        self.errors: dict[int, str] = {}
        self.sent = 0
        self.late = 0
        self.start = self.end = math.nan

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def request_ids(self, k: int) -> list:
        return self.ids[k].tolist() if self.multi else [self.ids[k]]

    def answer(self, k: int) -> list | None:
        a = self.answers[k]
        return a if self.multi or a is None else [a]

    def failed(self, k: int) -> bool:
        return k in self.errors or self.answers[k] is None

    def latencies_s(self) -> np.ndarray:
        """Per sent request; a failure lies beyond every limit."""
        lat = self.t_done[:self.sent] - self.t_ref[:self.sent]
        for k in range(self.sent):
            if self.failed(k):
                lat[k] = math.inf
        return lat

    def close(self, start: float, deadline: float) -> None:
        self.start = start
        done = self.t_done[:self.sent]
        last = float(np.nanmax(done)) if np.isfinite(done).any() else deadline
        self.end = max(last, deadline)


def closed_loop(clients, per_client: list, seconds: float | None
                ) -> Window:
    """Each of ``clients`` sends its requests (multigets; each an array of
    ids in ``per_client``) one after another, waiting for each answer,
    until ``seconds`` have passed (or, with None, until it has sent them
    all). Requests sent before the deadline are answered and kept; the
    window ends with the last answer."""
    if len(clients) != len(per_client):
        raise ValueError("one request list per client")
    # request k of client c is slot c + k * n_clients
    n_clients = len(clients)
    longest = max(len(reqs) for reqs in per_client)
    slots = [None] * (longest * n_clients)
    for c, reqs in enumerate(per_client):
        for k, arr in enumerate(reqs):
            slots[c + k * n_clients] = arr
    win = Window(slots, multi=True)
    gate = threading.Barrier(n_clients + 1)
    times: dict = {}
    errors: list = []

    def worker(c: int) -> None:
        client = clients[c]
        gate.wait()
        deadline = times["deadline"]
        for k in range(len(per_client[c])):
            slot = c + k * n_clients
            ids = slots[slot].tolist()
            t0 = time.perf_counter()
            if t0 >= deadline:
                return
            win.t_ref[slot] = t0
            try:
                win.answers[slot] = client.multiget(ids)
            except Exception as exc:  # a failed request is counted
                win.errors[slot] = repr(exc)
            win.t_done[slot] = time.perf_counter()
        if seconds is not None:
            errors.append(RuntimeError(
                f"client {c} ran out of requests before the deadline: "
                "raise requests_per_client"))

    threads = [threading.Thread(target=worker, args=(c,), daemon=True)
               for c in range(n_clients)]
    for t in threads:
        t.start()
    times["start"] = time.perf_counter()
    times["deadline"] = times["start"] + (math.inf if seconds is None
                                          else seconds)
    gate.wait()
    for t in threads:
        t.join(None if seconds is None else seconds + ANSWER_WAIT_S + 30)
    if any(t.is_alive() for t in threads):
        raise RuntimeError("a closed-loop client never returned")
    if errors:
        raise errors[0]
    # keep the slots that were sent, in slot order
    sent = [s for s in range(len(slots)) if not math.isnan(win.t_ref[s])]
    out = Window([slots[s] for s in sent], multi=True)
    out.t_ref[:] = win.t_ref[sent]
    out.t_done[:] = win.t_done[sent]
    out.answers = [win.answers[s] for s in sent]
    out.errors = {i: win.errors[s] for i, s in enumerate(sent)
                  if s in win.errors}
    out.sent = len(sent)
    out.close(times["start"], times["start"] if seconds is None
              else times["deadline"])
    return out


def open_loop(client, ids: list, at, seconds: float) -> Window:
    """Send ``get(ids[k])`` at offset ``at[k]`` from the window's start,
    whether or not earlier answers came, until ``seconds`` have passed;
    never early. Latency counts from when a request was due, so a stalled
    sender shows as latency, and ``late`` counts the requests it sent more
    than :data:`LATE_S` behind."""
    win = Window(ids, multi=False)
    lock = threading.Lock()
    all_done = threading.Event()
    state = {"pending": 0, "closing": False}

    def stamp(k: int, fut) -> None:
        win.t_done[k] = time.perf_counter()
        exc = fut.exception()
        if exc is not None:
            win.errors[k] = repr(exc)
        else:
            win.answers[k] = fut.result()
        with lock:
            state["pending"] -= 1
            if state["closing"] and state["pending"] == 0:
                all_done.set()

    start = time.perf_counter()
    for k in range(len(ids)):
        off = at[k]
        if off >= seconds:
            break
        due = start + off
        now = time.perf_counter()
        if due > now:
            time.sleep(due - now)
        elif now - due > LATE_S:
            win.late += 1
        win.t_ref[k] = due
        with lock:
            state["pending"] += 1
        win.sent = k + 1
        try:
            fut = client.get_async(ids[k])
        except Exception as exc:
            win.errors[k] = repr(exc)
            win.t_done[k] = time.perf_counter()
            with lock:
                state["pending"] -= 1
        else:
            fut.add_done_callback(lambda f, k=k: stamp(k, f))
    with lock:
        state["closing"] = True
        if state["pending"] == 0:
            all_done.set()
    all_done.wait(ANSWER_WAIT_S)
    for j in range(win.sent):
        if win.answers[j] is None and j not in win.errors:
            win.errors[j] = "no answer"
    win.close(start, start + seconds)
    return win
