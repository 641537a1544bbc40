"""One process-wide tracer for the served tick: spans and counters.

A server process runs one engine, and ``jax.profiler`` is process-wide
too, so there is one tracer, ``TRACER``. It is always on, costs about a
microsecond a span, and never touches a device array.

* ``with TRACER.span(name, rid=None):`` records the span's id, its
  parent's id (the innermost span open when it opened; 0 for none), its
  name, its start and end on ``time.perf_counter_ns()`` and a tag (the
  request id on request spans). It also opens a
  ``jax.profiler.TraceAnnotation`` of the same name, so a running profile
  holds the span on its host plane, on the clock of the device trace.
* Closed spans go into a ring of ``CAPACITY`` records; ``dropped`` counts
  the ones pushed out. Each name also keeps ``<name>.n`` (spans closed)
  and ``<name>.ns`` (their total duration). ``spans`` is the number of
  span ids handed out, so a snapshot of the counters marks which spans
  came after it.
* ``TRACER.count(name, n=1)`` adds to a plain counter.
* A ``jax.monitoring`` listener, registered when this module is imported,
  counts backend compiles: ``compiles`` and ``compile_ns`` in all,
  ``compiles.<program>`` per program (e.g. ``compiles.jit(step_ragged)``)
  and ``compile_cache_hits``, the programs loaded from the persistent
  cache (JAX reports a load as a compile too). Each compile is also a
  ``serve.compile`` span under the span open at the time, tagged with the
  program's name.
"""
from __future__ import annotations

import time
from collections import deque
from typing import NamedTuple, Optional

import jax

CAPACITY = 1 << 18
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class Span(NamedTuple):
    id: int
    parent: int
    name: str
    start_ns: int
    end_ns: int
    tag: object = None     # the request id, or a compiled program's name


class _Open:
    """One span while it is open (the context manager ``span`` returns)."""
    __slots__ = ("tracer", "name", "tag", "id", "parent", "start", "ann")

    def __init__(self, tracer: "Tracer", name: str, tag):
        self.tracer, self.name, self.tag = tracer, name, tag

    def __enter__(self):
        t = self.tracer
        t._ids += 1
        self.id = t._ids
        self.parent = t._stack[-1] if t._stack else 0
        t._stack.append(self.id)
        self.ann = jax.profiler.TraceAnnotation(self.name)
        self.ann.__enter__()
        self.start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        self.ann.__exit__(*exc)
        t = self.tracer
        t._stack.pop()
        t._close(Span(self.id, self.parent, self.name, self.start, end,
                      self.tag))
        return False


class Tracer:
    def __init__(self, capacity: int = CAPACITY):
        self.capacity = capacity
        self.reset()

    def reset(self) -> None:
        """Forget every span and counter (between tests)."""
        self._ring: deque = deque(maxlen=self.capacity)
        self._stack: list = []
        self._ids = 0
        self._counts = {"dropped": 0, "compiles": 0, "compile_ns": 0,
                        "compile_cache_hits": 0}
        self._totals: dict = {}

    def span(self, name: str, rid=None) -> _Open:
        return _Open(self, name, rid)

    def record(self, name: str, start_ns: int, end_ns: int, tag=None,
               parent: Optional[int] = None) -> None:
        """A span that is already over: under the innermost open span, or
        under ``parent`` (0 makes it a root)."""
        self._ids += 1
        if parent is None:
            parent = self._stack[-1] if self._stack else 0
        self._close(Span(self._ids, parent, name, start_ns, end_ns, tag))

    def _close(self, s: Span) -> None:
        if len(self._ring) == self.capacity:
            self._counts["dropped"] += 1
        self._ring.append(s)
        tot = self._totals.get(s.name)
        if tot is None:
            tot = self._totals[s.name] = [0, 0]
        tot[0] += 1
        tot[1] += s.end_ns - s.start_ns

    def count(self, name: str, n: int = 1) -> None:
        self._counts[name] = self._counts.get(name, 0) + n

    def spans(self) -> list:
        """The closed spans the ring holds, oldest first."""
        return list(self._ring)

    def counters(self) -> dict:
        out = dict(self._counts, spans=self._ids)
        for name, (n, ns) in self._totals.items():
            out[name + ".n"] = n
            out[name + ".ns"] = ns
        return out


TRACER = Tracer()


def _on_duration(event: str, duration: float, **kw) -> None:
    if event != COMPILE_EVENT:
        return
    end = time.perf_counter_ns()
    ns = int(duration * 1e9)
    program = str(kw.get("fun_name", "unknown"))
    TRACER.count("compiles")
    TRACER.count("compile_ns", ns)
    TRACER.count("compiles." + program)
    TRACER.record("serve.compile", end - ns, end, program)


def _on_event(event: str, **_) -> None:
    if event == CACHE_HIT_EVENT:
        TRACER.count("compile_cache_hits")


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)
