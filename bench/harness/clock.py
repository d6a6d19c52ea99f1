"""Compile time and compile counts, from JAX's own monitoring events."""

from __future__ import annotations

import time

EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
          "/jax/core/compile/jaxpr_to_mlir_module_duration",
          "/jax/core/compile/backend_compile_duration")
BACKEND = EVENTS[2]


class CompileClock:
    """While ``active``: the intervals of JAX's tracing, lowering and
    backend-compile (or compile-cache load) events, and how many of each
    ran.  ``seconds()`` is the length of their union, so nested
    jits count once."""

    def __init__(self):
        import jax
        self.spans, self.counts, self.active = [], {}, False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.active and event in EVENTS:
            end = time.perf_counter()
            self.spans.append((end - duration, end))
            self.counts[event] = self.counts.get(event, 0) + 1

    def start(self) -> None:
        self.spans, self.counts, self.active = [], {}, True

    def stop(self) -> None:
        self.active = False

    @property
    def compiles(self) -> int:
        """Backend compiles (or persistent-cache loads) while active."""
        return self.counts.get(BACKEND, 0)

    @property
    def lowerings(self) -> int:
        return self.counts.get(EVENTS[1], 0)

    @property
    def traces(self) -> int:
        return self.counts.get(EVENTS[0], 0)

    def seconds(self) -> float:
        busy, edge = 0.0, float("-inf")
        for a, b in sorted(self.spans):
            a = max(a, edge)
            if b > a:
                busy, edge = busy + b - a, b
        return busy


class GcClock:
    """While ``active``: the pauses of Python's garbage collector, so a
    window that loses time can be told whether the collector took it."""

    def __init__(self):
        import gc
        self.pauses, self.active, self._t = [], False, None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if not self.active:
            return
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))
            self._t = None

    def start(self) -> None:
        self.pauses, self.active = [], True

    def stop(self) -> None:
        self.active = False

    def summary(self) -> str:
        total = sum(p for _, p in self.pauses)
        longest = max((p for _, p in self.pauses), default=0.0)
        gen2 = sum(1 for g, _ in self.pauses if g == 2)
        return (f"{len(self.pauses)} collections ({gen2} of generation 2), "
                f"{total:.3f} s in all, longest {longest:.3f} s")
