"""Compile seconds and persistent-cache traffic from JAX's own monitoring
events (after ``chip_smoke.CompileMeter``; the original is listed in PERF.md,
Open questions). Every stage event is stamped with the benchmark's clock when
it ends, so that a compilation inside the measured window can be seen."""

from __future__ import annotations

import time


class CompileMeter:
    def __init__(self, clock=time.perf_counter):
        import jax.monitoring as mon

        self.clock = clock
        self.stages = []        # (ended_at, seconds, event, fun_name)
        self.hits = 0
        self.misses = 0
        mon.register_event_listener(self._on_event)
        mon.register_event_time_span_listener(self._on_span)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_span(self, event, start, end, **kw):
        if event.startswith("/jax/core/compile/"):
            self.stages.append((self.clock(), end - start,
                                event.rsplit("/", 1)[-1],
                                str(kw.get("fun_name", "?"))))

    def mark(self):
        return (len(self.stages), self.hits, self.misses)

    def since(self, mark) -> dict:
        """Seconds per compile stage since ``mark``. Tracing, lowering and
        the backend stage (compile, or load from the cache) of one program
        do not overlap, so their sums add up."""
        by = {}
        for _, s, ev, _fn in self.stages[mark[0]:]:
            by[ev] = by.get(ev, 0.0) + s
        return {"stage_seconds": {k: round(v, 2) for k, v in sorted(by.items())},
                "programs": sum(1 for x in self.stages[mark[0]:]
                                if x[2] == "backend_compile_duration"),
                "cache_hits": self.hits - mark[1],
                "cache_misses": self.misses - mark[2]}

    def inside(self, t0: float, t1: float):
        """Backend compilations (or cache loads) that ended in [t0, t1]."""
        return [(round(s, 3), fn) for at, s, ev, fn in self.stages
                if ev == "backend_compile_duration" and t0 <= at <= t1]
