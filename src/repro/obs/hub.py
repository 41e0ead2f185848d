"""``MetricsHub`` -- the shared registry the three planes report through.

One hub holds every metric of a run, keyed on ``(kind, name, labels)``,
plus span-style trace events ordered by a hub-assigned monotone ``seq``
counter (the deterministic clock; wall durations are profiling-only
side data).  Export is deterministically sorted JSONL: fixed seed ==
byte-identical telemetry.

Ambient activation
------------------
Instrumented subsystems (DES executor, ``BatchAnnealer``,
``SearchScheduler``) resolve their hub via :func:`get_hub` at run time,
so the control plane can instrument everything it constructs with one
``with hub.activate():`` block and zero parameter plumbing.  The default
ambient hub is :data:`NULL_HUB`, a disabled hub whose accessors hand out
inert singletons and retain **zero** state -- the disabled path is a
couple of attribute checks, so hot loops keep their benchmarked numbers.

The jax profiler
----------------
While a jax profiler session captures (``TraceAnnotation.is_enabled()``),
every span -- ``NULL_HUB``'s included -- also opens a
``jax.profiler.TraceAnnotation`` of its name, which puts it in the trace's
host plane on the device trace's clock, and closes into the bounded
process-wide profile log that :func:`profiled_spans` reads.  The log keeps
its own open-span stack, so parent links hold across hubs.  With no
capture running, ``NULL_HUB.span`` still hands out ``NULL_SPAN``; jax is
looked up only once something else has imported it.
"""

from __future__ import annotations

import collections
import json
import sys
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

from . import clock
from .metrics import (
    DEFAULT_BUCKETS,
    KIND_OF,
    Counter,
    Gauge,
    Histogram,
    Series,
)

#: Registry key: (kind, name, sorted label items).
Key = Tuple[str, str, Tuple[Tuple[str, object], ...]]


def _key(kind: str, name: str, labels: Dict[str, object]) -> Key:
    return (kind, name, tuple(sorted(labels.items())))


def _sort_key(key: Key):
    # Label *values* may mix int and str across metrics sharing a label
    # name; stringify so the export order is total (and deterministic).
    kind, name, labels = key
    return (kind, name, tuple((lk, str(lv)) for lk, lv in labels))


class _NullMetric:
    """Inert sink a disabled hub hands out -- every mutator is a no-op."""

    __slots__ = ()

    def inc(self, n: int = 1) -> None:
        pass

    def set(self, value: float) -> None:
        pass

    def append(self, t: float, value: float) -> None:
        pass

    def observe(self, value: float) -> None:
        pass


NULL_METRIC = _NullMetric()


class _NullSpan:
    """Inert context manager a disabled hub hands out for spans."""

    __slots__ = ()

    #: Whether ``set`` keeps what it is given (callers skip costly meta).
    recording = False

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **meta) -> "_NullSpan":
        return self


NULL_SPAN = _NullSpan()


class ProfiledSpan(NamedTuple):
    """One closed span of the profile log.  ``seq`` and ``parent`` number
    the log's own spans (the enclosing open span's ``seq``, across hubs)."""

    seq: int
    parent: Optional[int]
    name: str
    labels: Dict[str, object]
    meta: Dict[str, object]
    wall_s: float


#: Closed spans the profile log keeps; the oldest are dropped beyond it.
PROFILE_LOG_SPANS = 65_536


class _ProfileLog:
    """Spans taken while the jax profiler captures, in closing order.  Like
    a hub's own open-span stack, its stack assumes spans nest on one thread."""

    def __init__(self) -> None:
        self.closed: "collections.deque[ProfiledSpan]" = collections.deque(
            maxlen=PROFILE_LOG_SPANS
        )
        self.stack: List[int] = []
        self.seq = 0
        #: ``jax.profiler.TraceAnnotation``, once jax has been imported.
        self.annotation = None

    def capturing(self) -> bool:
        """True while a jax profiler session captures.  jax is never
        imported from here: without it no session can exist."""
        annotation = self.annotation
        if annotation is None:
            if "jax" not in sys.modules:
                return False
            from jax.profiler import TraceAnnotation

            annotation = self.annotation = TraceAnnotation
        return annotation.is_enabled()


_PROFILE_LOG = _ProfileLog()


def profiled_spans() -> Tuple[ProfiledSpan, ...]:
    """The profile log's closed spans, oldest first (children close, and so
    appear, before their parents)."""
    return tuple(_PROFILE_LOG.closed)


class _ProfileSpan:
    """A span opened while the profiler captures: a ``TraceAnnotation`` of
    the same name, timed into the profile log.  ``NULL_HUB`` hands these
    out directly; an enabled hub's :class:`Span` opens one beside itself."""

    __slots__ = ("name", "labels", "meta", "seq", "parent", "_ann", "_t0")

    recording = True

    def __init__(self, name: str, labels: Dict[str, object], meta=None) -> None:
        self.name = name
        self.labels = labels
        self.meta: Dict[str, object] = {} if meta is None else meta

    def __enter__(self) -> "_ProfileSpan":
        log = _PROFILE_LOG
        self.seq = log.seq
        log.seq += 1
        self.parent = log.stack[-1] if log.stack else None
        log.stack.append(self.seq)
        self._ann = log.annotation(self.name)
        self._ann.__enter__()
        self._t0 = clock.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        wall_s = clock.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        log = _PROFILE_LOG
        log.stack.pop()
        log.closed.append(
            ProfiledSpan(self.seq, self.parent, self.name, self.labels, self.meta, wall_s)
        )
        return False

    def set(self, **meta) -> "_ProfileSpan":
        self.meta.update(meta)
        return self


class Span:
    """One trace event: hub-assigned ``seq`` + parent link + typed meta.

    ``seq`` and ``parent`` (the enclosing span's seq, via the hub's
    open-span stack) are the deterministic clock; ``wall_s`` is measured
    through ``obs.clock`` for profiling and excluded from export unless
    ``include_wall=True``.
    """

    __slots__ = (
        "name", "labels", "seq", "parent", "meta", "wall_s", "_hub", "_t0", "_prof",
    )

    recording = True

    def __init__(self, hub: "MetricsHub", name: str, labels: Dict[str, object]):
        self._hub = hub
        self.name = name
        self.labels = labels
        self.seq: Optional[int] = None
        self.parent: Optional[int] = None
        self.meta: Dict[str, object] = {}
        self.wall_s: float = 0.0
        self._t0 = 0.0
        self._prof: Optional[_ProfileSpan] = None

    def __enter__(self) -> "Span":
        hub = self._hub
        self.seq = hub._seq
        hub._seq += 1
        self.parent = hub._stack[-1].seq if hub._stack else None
        hub._stack.append(self)
        hub._spans.append(self)
        if _PROFILE_LOG.capturing():
            # Shares ``meta``, so the log sees what ``set`` attaches.
            self._prof = _ProfileSpan(self.name, self.labels, self.meta).__enter__()
        self._t0 = clock.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.wall_s = clock.perf_counter() - self._t0
        if self._prof is not None:
            self._prof.__exit__(*exc)
            self._prof = None
        self._hub._stack.pop()
        return False

    def set(self, **meta) -> "Span":
        """Attach deterministic metadata (counts, sizes -- never wall time)."""
        self.meta.update(meta)
        return self


class MetricsHub:
    """Typed metric registry + trace-span collector with JSONL export.

    Accessors are create-or-get on ``(kind, name, labels)``; a disabled
    hub (``enabled=False``) returns shared inert singletons and retains
    zero state, which is what makes ambient instrumentation free when no
    observer asked for telemetry.
    """

    def __init__(self, enabled: bool = True) -> None:
        self.enabled = enabled
        self._metrics: Dict[Key, object] = {}
        self._spans: List[Span] = []
        self._stack: List[Span] = []
        self._seq = 0

    # -- registry -----------------------------------------------------------
    def counter(self, name: str, **labels):
        if not self.enabled:
            return NULL_METRIC
        key = _key("counter", name, labels)
        m = self._metrics.get(key)
        if m is None:
            m = self._metrics[key] = Counter()
        return m

    def gauge(self, name: str, **labels):
        if not self.enabled:
            return NULL_METRIC
        key = _key("gauge", name, labels)
        m = self._metrics.get(key)
        if m is None:
            m = self._metrics[key] = Gauge()
        return m

    def series(self, name: str, **labels):
        if not self.enabled:
            return NULL_METRIC
        key = _key("series", name, labels)
        m = self._metrics.get(key)
        if m is None:
            m = self._metrics[key] = Series()
        return m

    def histogram(self, name: str, buckets=DEFAULT_BUCKETS, **labels):
        if not self.enabled:
            return NULL_METRIC
        key = _key("histogram", name, labels)
        m = self._metrics.get(key)
        if m is None:
            m = self._metrics[key] = Histogram(buckets)
        return m

    def attach(self, name: str, metric, **labels):
        """Register an externally-created metric under this hub's registry.

        The DES executor always builds its latency/queue-depth histograms
        (``DesReport`` percentiles come from them); when a hub is active
        they are attached so the export shows the identical objects.
        Re-attaching the same key replaces the previous metric (the most
        recent run wins -- scenario timelines capture per-interval data
        through dedicated series instead).
        """
        if not self.enabled:
            return metric
        self._metrics[_key(KIND_OF[type(metric)], name, labels)] = metric
        return metric

    def find(self, kind: str, name: str) -> List[Tuple[Dict[str, object], object]]:
        """All ``(labels, metric)`` for one (kind, name), in export order."""
        out = []
        for key in sorted(self._metrics, key=_sort_key):
            k, n, labels = key
            if k == kind and n == name:
                out.append((dict(labels), self._metrics[key]))
        return out

    # -- spans --------------------------------------------------------------
    def span(self, name: str, **labels):
        if not self.enabled:
            return _ProfileSpan(name, labels) if _PROFILE_LOG.capturing() else NULL_SPAN
        return Span(self, name, labels)

    # -- export -------------------------------------------------------------
    def records(self, include_wall: bool = False) -> List[Dict[str, object]]:
        """Deterministically ordered plain dicts: sorted metrics, then
        spans in ``seq`` order.  Wall durations only with ``include_wall``."""
        out: List[Dict[str, object]] = []
        for key in sorted(self._metrics, key=_sort_key):
            kind, name, labels = key
            rec: Dict[str, object] = {"kind": kind, "name": name, "labels": dict(labels)}
            rec.update(self._metrics[key].record())
            out.append(rec)
        for sp in self._spans:
            rec = {
                "kind": "span",
                "name": sp.name,
                "labels": dict(sp.labels),
                "seq": sp.seq,
                "parent": sp.parent,
                "meta": dict(sp.meta),
            }
            if include_wall:
                rec["wall_s"] = sp.wall_s
            out.append(rec)
        return out

    def to_jsonl(self, include_wall: bool = False) -> str:
        lines = [
            json.dumps(rec, sort_keys=True, separators=(",", ":"))
            for rec in self.records(include_wall)
        ]
        return "".join(line + "\n" for line in lines)

    def export(self, path: str, include_wall: bool = False) -> str:
        with open(path, "w") as fh:
            fh.write(self.to_jsonl(include_wall))
        return path

    # -- ambient activation -------------------------------------------------
    def activate(self) -> "_Activation":
        """Make this hub the ambient :func:`get_hub` target for a block."""
        return _Activation(self)


class _Activation:
    __slots__ = ("_hub", "_prev")

    def __init__(self, hub: MetricsHub) -> None:
        self._hub = hub
        self._prev: Optional[MetricsHub] = None

    def __enter__(self) -> MetricsHub:
        global _CURRENT
        self._prev = _CURRENT
        _CURRENT = self._hub
        return self._hub

    def __exit__(self, *exc) -> bool:
        global _CURRENT
        _CURRENT = self._prev
        return False


#: The disabled ambient default: zero-state, inert accessors.
NULL_HUB = MetricsHub(enabled=False)

_CURRENT: MetricsHub = NULL_HUB


def get_hub() -> MetricsHub:
    """The ambient hub (``NULL_HUB`` unless an ``activate()`` is open)."""
    return _CURRENT
