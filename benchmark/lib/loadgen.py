"""Load runner: one thread, the benchmark's own clock.

Requests go in through the program's public front door
(``EngineHandle._server.submit``, the same queue ``serve.api.LLM`` uses) and
come back as ``GenerationResult`` objects in ``rm.results``. The runner polls
that table instead of waiting on the server's per-submission events: those
fire only when a whole ``generate_*`` call returns, which under continuous
load is never, and a closed-loop client that waited on one would send its
next request in a wave with all the others.

Copied in spirit from ``flexflow_tpu/serve/loadgen.LoadRunner`` (PERF.md,
Open questions, lists the original). What differs: lengths come from a fixed
cycle instead of random draws, a closed loop is kept loaded until every
request that touched the window has finished, and records carry the times
the window accounting needs.
"""

from __future__ import annotations

import time
from typing import Callable, List, Optional

from . import traffic as T

POLL_S = 0.001


class ServerDied(RuntimeError):
    pass


class _Door:
    """Submit and collect through the handle's background server."""

    def __init__(self, handle, clock=time.perf_counter):
        if getattr(handle, "_server", None) is None:
            handle.start_server()
        self.srv = handle._server
        self.rm = handle.rm
        self.clock = clock

    def submit(self, prompt, n_out: int, due: Optional[float] = None):
        guids, _ev = self.srv.submit([prompt], n_out, 0)
        now = self.clock()
        return {"guid": guids[0], "submit": now,
                "due": now if due is None else due,
                "n_in": len(prompt), "want_out": n_out}

    def collect(self, entry) -> Optional[dict]:
        res = self.rm.results.get(entry["guid"])
        if res is None:
            return None
        rec = dict(entry)
        rec.update(
            status=res.status, n_out=len(res.output_tokens),
            first=entry["submit"] + res.ttft_s,
            finish=entry["submit"] + res.latency_s,
            ttft_attributed=res.ttft_s > 0.0,
            queue_wait_s=res.queue_wait_s, prefill_s=res.prefill_s)
        return rec

    def check_alive(self):
        if self.srv._error is not None:
            raise ServerDied("serving loop died") from self.srv._error
        if not self.srv._thread.is_alive():
            raise ServerDied("serving thread exited")

    def cancel(self, entries):
        """Cancel what is still in flight (requests sent after the window
        closed, which no metric reads) and wait until each has resolved."""
        for e in entries:
            self.rm.cancel(e["guid"])
        deadline = self.clock() + 60.0
        left = list(entries)
        while left and self.clock() < deadline:
            left = [e for e in left if e["guid"] not in self.rm.results]
            time.sleep(POLL_S)
        return not left


def run_closed(handle, traffic: dict, slots: int, seed: int, seconds: float,
               vocab: int, on_window: Optional[Callable] = None,
               timeout_s: float = 300.0, clock=time.perf_counter):
    """Closed loop with a fixed number of clients.

    The first wave is staggered (client c's first request asks for
    (c+1)/clients of its output length) so the clients are out of phase
    from the start, as they are in a steady state. The window opens once
    every first-wave request has finished and ``warmup_s`` has passed; load
    continues through it and after it until every request sent before the
    window closed has finished. Returns (records, w0, w1, info)."""
    clients = slots if traffic["clients"] == "slots" else int(traffic["clients"])
    cycle = T.Cycle(traffic, seed, vocab)
    door = _Door(handle, clock)
    inflight, records = {}, []
    first_wave = set()
    t_start = clock()
    for c in range(clients):
        prompt, n_out = cycle.next()
        n_out = max(2, round(n_out * (c + 1) / clients))
        inflight[c] = door.submit(prompt, n_out)
        first_wave.add(inflight[c]["guid"])
    w0 = w1 = None
    warmup_s = float(traffic["warmup_s"])
    while True:
        now = clock()
        for c, e in list(inflight.items()):
            rec = door.collect(e)
            if rec is None:
                continue
            records.append(rec)
            first_wave.discard(e["guid"])
            inflight[c] = door.submit(*cycle.next())
        if w0 is None:
            if not first_wave and now - t_start >= warmup_s:
                w0, w1 = now, now + seconds
                if on_window is not None:
                    on_window(w0, w1)
        elif now >= w1 and all(e["submit"] >= w1 for e in inflight.values()):
            break
        door.check_alive()
        if now - t_start > timeout_s + seconds:
            raise TimeoutError(
                f"closed loop not finished {now - t_start:.0f}s after start")
        time.sleep(POLL_S)
    drained = door.cancel(list(inflight.values()))
    info = {"clients": clients, "warmup_s": w0 - t_start,
            "drain_s": clock() - w1, "drained": drained,
            "requests_total": len(records)}
    return records, w0, w1, info


def run_open(handle, traffic: dict, seed: int, seconds: float, vocab: int,
             on_window: Optional[Callable] = None, timeout_s: float = 300.0,
             clock=time.perf_counter):
    """Open loop: the same number of requests in every run, each timed
    from when it was due. Returns (records, w0, w1, info); ``records`` holds
    every request, and each carries ``in_window`` (due inside the window)."""
    offsets, n_pre, n_win = T.open_schedule(traffic, seed, seconds)
    cycle = T.Cycle(traffic, seed, vocab)
    door = _Door(handle, clock)
    lead = 0.25
    w0 = clock() + lead - offsets[0]
    w1 = w0 + seconds
    if on_window is not None:
        on_window(w0, w1)
    inflight: List[dict] = []
    records: List[dict] = []
    nxt = 0
    while nxt < len(offsets) or inflight:
        now = clock()
        while nxt < len(offsets) and w0 + offsets[nxt] <= now:
            prompt, n_out = cycle.next()
            e = door.submit(prompt, n_out, due=w0 + offsets[nxt])
            e["in_window"] = n_pre <= nxt < n_pre + n_win
            inflight.append(e)
            nxt += 1
        still = []
        for e in inflight:
            rec = door.collect(e)
            if rec is None:
                still.append(e)
            else:
                records.append(rec)
        inflight = still
        door.check_alive()
        if now - w1 > timeout_s:
            raise TimeoutError(f"open loop: {len(inflight)} requests "
                               f"unfinished {now - w1:.0f}s after the window")
        time.sleep(POLL_S)
    info = {"n_pre": n_pre, "n_window": n_win, "n_total": len(offsets),
            "drain_s": clock() - w1}
    return records, w0, w1, info
