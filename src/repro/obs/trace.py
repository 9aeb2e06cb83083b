"""End-to-end request tracing: trace ids, span stacks, slow-request log.

A *trace* is one logical request (one ``client.get`` or
``client.multiget``), identified by a 64-bit trace id (16 hex chars on the
wire and in dumps) minted at the outermost span. *Spans* are named timed
sections inside it — ``client.get`` → ``client.coalesce`` → ``rpc.multiget``
(socket) → ``server.multiget`` → ``service.coalesce`` (queue wait) →
``service.batch`` → ``store.multiget`` → ``store.decode`` →
``kernel.decode_batch`` and its steps — linked by parent span ids, across
threads and, via the :mod:`repro.net.protocol` trace header, across
processes.

The tracer is **off by default**. :meth:`Tracer.enable` and
:meth:`Tracer.disable` switch the process-wide :data:`TRACER`; a process an
operator starts turns it on with ``REPRO_TRACE=1`` in its environment, read
once when this module is imported. Off, a span site costs one attribute
test and allocates nothing, clients mint no roots and frames carry no trace
header. On, a span is booked into preallocated arrays of ints (name id,
trace id, span id, parent id, start and end ``perf_counter_ns``, thread,
batch size) — no dict per span, nothing the garbage collector tracks — and
adds its count, its seconds and its self seconds (its duration less what
its nested children on the same thread cover) to three registry counters
labelled by span name: ``repro_span_total``, ``repro_span_seconds_total``
and ``repro_span_self_seconds_total``.

Two propagation mechanisms:

* **thread-local ambient context** — :meth:`Tracer.span` opens a child of
  the current context and makes itself ambient for the body, so nested
  calls (store inside service inside server) need no plumbing. With no
  ambient context and ``root=False`` it is a no-op.
* **explicit contexts** — queue hops and wire hops carry a
  :class:`TraceContext`; :meth:`Tracer.activate` installs it on the
  receiving thread and :meth:`Tracer.record` books spans with explicit
  times (a queue wait measured enqueue → dequeue).

Timestamps are ``perf_counter_ns``: CLOCK_MONOTONIC, shared by every
process on one host, so a client's spans and a server's spans line up.
:meth:`Tracer.annotate_with` installs a hook (``repro.kernels.ops`` sets
one to ``jax.profiler.TraceAnnotation``) that makes each span also a
profiler annotation of the same name, which puts the spans of the process
holding the chip beside the device's operations in its trace;
:func:`clock_offset` maps buffered span times onto that trace's clock.

The span buffer is a ring: ``capacity`` spans, set at :meth:`enable`.
:meth:`Tracer.trace_dump` groups it by trace id and returns the *slowest*
``n`` recent requests — the on-server slow-request log behind the
``trace_dump`` RPC and ``GET /traces``.

Stdlib only (``time.time()`` is banned from serving modules by
``tools/check_hotpath``).
"""

from __future__ import annotations

import itertools
import os
import random
import threading
import time
from array import array
from bisect import bisect_left
from typing import NamedTuple

from repro.obs.metrics import REGISTRY, MetricsRegistry, _Instrument

#: spans the ring holds unless :meth:`Tracer.enable` is given another size
DEFAULT_CAPACITY = 4096
#: ints per buffered span: name id, trace id, span id, parent id, start ns,
#: end ns, thread, batch
_SLOT = 8
#: ints per open span on a thread's stack: name id, trace id, span id,
#: parent id, previous trace id, start ns, children's ns, batch
_LEVEL = 8


class TraceContext(NamedTuple):
    """What crosses a thread/queue/wire hop: which trace, which span."""

    trace_id: str  # 16 lowercase hex chars
    span_id: int   # u64, unique within the minting process


def _mint() -> int:
    return random.getrandbits(64) or 1


def new_trace_id() -> str:
    return format(_mint(), "016x")


def _trace_int(trace_id: str) -> int:
    try:
        return int(trace_id, 16) & 0xFFFFFFFFFFFFFFFF or 1
    except ValueError:  # a foreign header's id that is not hex
        return hash(trace_id) & 0xFFFFFFFFFFFFFFFF or 1


class _Noop:
    """The span a disabled tracer hands out: enters and exits doing
    nothing."""

    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, et, ev, tb) -> bool:
        return False


_NOOP = _Noop()


class _Closer:
    """The span an enabled tracer hands out: one per tracer; the open
    span it closes is the top of the exiting thread's stack."""

    __slots__ = ("_tracer",)

    def __init__(self, tracer: "Tracer"):
        self._tracer = tracer

    def __enter__(self) -> None:
        return None

    def __exit__(self, et, ev, tb) -> bool:
        self._tracer._pop()
        return False


class _ThreadState:
    """One thread's ambient context, its stack of open spans and its share
    of the span counters (written by this thread alone, so exact with no
    lock; folded into the tracer's when the thread ends)."""

    __slots__ = ("trace", "sid", "ctx", "depth", "stack", "annotations",
                 "thread", "totals", "tracer")

    def __init__(self, tracer: "Tracer") -> None:
        self.tracer = tracer
        self.trace = 0      # ambient trace id, 0 for none
        self.sid = 0        # ambient span id, 0 for none
        self.ctx = None     # the ambient TraceContext, built when asked for
        self.depth = 0
        self.stack = array("Q", [0]) * (_LEVEL * 16)
        self.annotations: list = []
        self.thread = threading.get_native_id()
        #: per name id: spans, nanoseconds, self nanoseconds
        self.totals = array("Q", [0]) * (3 * 64)
        with tracer._lock:
            tracer._thread_totals.append(self.totals)

    def __del__(self) -> None:  # the thread has ended
        self.tracer._retire(self.totals)


class _SpanCounter(_Instrument):
    """A registry counter over one field of every thread's span totals."""

    kind = "counter"

    def __init__(self, tracer: "Tracer", name: str, span: str, index: int,
                 scale: float | None):
        super().__init__(name, {"span": span})
        self._tracer = tracer
        self._index = index
        self._scale = scale

    @property
    def value(self):
        i = self._index
        with self._tracer._lock:
            total = sum(t[i] for t in self._tracer._thread_totals
                        if len(t) > i)
        return total if self._scale is None else total * self._scale

    def state(self) -> dict:
        return {"value": self.value}


class Tracer:
    """Per-process span recorder with ambient (thread-local) context."""

    def __init__(self, registry: MetricsRegistry | None = None):
        #: the switch every span site tests
        self.on = False
        self._registry = REGISTRY if registry is None else registry
        # reentrant: an ended thread's state may be collected, and its
        # totals retired, while this thread holds the lock
        self._lock = threading.RLock()
        #: the ended threads' summed span totals, then each live thread's
        self._retired = array("Q")
        self._thread_totals: list[array] = [self._retired]
        self._local = threading.local()
        self._ids = itertools.count(1)  # next() is atomic under the GIL
        self._seq = itertools.count()
        self._buf = array("Q")
        self._names: list[str] = [""]  # name id -> name; 0 marks a free slot
        self._name_ids: dict[str, int] = {}
        self._closer = _Closer(self)
        self._annotate = None

    @property
    def _tls(self) -> _ThreadState:
        """This thread's state (one thread-local lookup per call)."""
        st = getattr(self._local, "st", None)
        if st is None:
            st = self._local.st = _ThreadState(self)
        return st

    def _retire(self, totals: array) -> None:
        with self._lock:
            self._thread_totals = [t for t in self._thread_totals
                                   if t is not totals]
            retired = self._retired
            if len(retired) < len(totals):
                retired.extend(array("Q", [0]) * (len(totals) - len(retired)))
            for i, v in enumerate(totals):
                retired[i] += v

    # ------------------------------------------------------------- switch
    def enable(self, capacity: int = DEFAULT_CAPACITY) -> None:
        """Turn tracing on with a ring of ``capacity`` spans (a new size
        starts an empty ring)."""
        capacity = int(capacity)
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        if capacity * _SLOT != len(self._buf):
            self._buf = array("Q", [0]) * (capacity * _SLOT)
        self.on = True

    def disable(self) -> None:
        """Turn tracing off; the ring keeps what it holds."""
        self.on = False

    @property
    def capacity(self) -> int:
        return len(self._buf) // _SLOT

    def annotate_with(self, factory) -> None:
        """Open ``factory(name)`` (a context manager, or None) around every
        span while tracing is on; None removes the hook."""
        self._annotate = factory

    # ------------------------------------------------------------- context
    def current(self) -> TraceContext | None:
        if not self.on:
            return None
        st = self._tls
        if not st.sid:
            return None
        ctx = st.ctx
        if ctx is None:
            ctx = st.ctx = TraceContext(format(st.trace, "016x"), st.sid)
        return ctx

    def activate(self, ctx: TraceContext | None) -> TraceContext | None:
        """Install ``ctx`` as this thread's ambient context; returns the
        previous one for :meth:`restore` (always pair them)."""
        st = self._tls
        prev = st.ctx
        if prev is None and st.sid:
            prev = TraceContext(format(st.trace, "016x"), st.sid)
        if ctx is None:
            st.trace = st.sid = 0
        else:
            st.trace, st.sid = _trace_int(ctx.trace_id), int(ctx.span_id)
        st.ctx = ctx
        return prev

    def restore(self, prev: TraceContext | None) -> None:
        self.activate(prev)

    def new_context(
        self, parent: TraceContext | None = None, *, inherit: bool = True
    ) -> tuple[TraceContext, int]:
        """Allocate a span context: child of ``parent`` (default: the
        ambient context) or a fresh trace root. Returns ``(ctx,
        parent_span_id)`` — parent id 0 marks a root span."""
        if parent is None and inherit:
            parent = self.current()
        if parent is None:
            return TraceContext(new_trace_id(), next(self._ids)), 0
        return (TraceContext(parent.trace_id, next(self._ids)),
                parent.span_id)

    # ------------------------------------------------------------ recording
    def _name_id(self, name: str) -> int:
        nid = self._name_ids.get(name)
        if nid is None:
            with self._lock:
                nid = self._name_ids.get(name)
                if nid is None:
                    self._names.append(name)
                    nid = len(self._names) - 1
                    for j, (metric, scale) in enumerate((
                            ("repro_span_total", None),
                            ("repro_span_seconds_total", 1e-9),
                            ("repro_span_self_seconds_total", 1e-9))):
                        self._registry.register(_SpanCounter(
                            self, metric, name, 3 * nid + j, scale))
                    self._name_ids[name] = nid
        return nid

    def _book(self, st: _ThreadState, nid: int, trace: int, sid: int,
              parent: int, t0: int, t1: int, batch: int,
              self_ns: int) -> None:
        buf = self._buf
        k = next(self._seq) % (len(buf) // _SLOT) * _SLOT
        buf[k] = 0  # a reader skips the slot until its name is back
        buf[k + 1] = trace
        buf[k + 2] = sid
        buf[k + 3] = parent
        buf[k + 4] = t0
        buf[k + 5] = t1
        buf[k + 6] = st.thread
        buf[k + 7] = batch
        buf[k] = nid
        totals = st.totals
        i = 3 * nid
        if i >= len(totals):
            totals.extend(array("Q", [0]) * (i + 3 * 64 - len(totals)))
        totals[i] += 1
        totals[i + 1] += t1 - t0
        totals[i + 2] += self_ns

    def record(self, name: str, ctx: TraceContext, parent_id: int,
               start_ns: int, end_ns: int, *, batch: int = 0) -> None:
        """Book one finished span with explicit ``perf_counter_ns`` times
        (it has no children, so its self time is its duration)."""
        if not self.on:
            return
        self._book(self._tls, self._name_id(name), _trace_int(ctx.trace_id),
                   int(ctx.span_id), int(parent_id), start_ns, end_ns,
                   batch, end_ns - start_ns)

    def record_child(self, name: str, parent: TraceContext, start_ns: int,
                     end_ns: int, *, batch: int = 0) -> None:
        """Book a child span of ``parent`` whose times are known only after
        the fact (queue hops)."""
        if not self.on:
            return
        self._book(self._tls, self._name_id(name),
                   _trace_int(parent.trace_id),
                   next(self._ids), int(parent.span_id), start_ns, end_ns,
                   batch, end_ns - start_ns)

    def span(self, name: str, *, root: bool = False, batch: int = 0):
        """Timed section as a child of the ambient context.

        Off, or with no ambient context and ``root=False``, a no-op;
        ``root=True`` mints a new trace when none is active. The span's
        context is ambient for the body, so nested spans chain parentage.
        ``batch`` is the one annotation a span carries.
        """
        if not self.on:
            return _NOOP
        st = self._tls
        if not st.sid and not root:
            return _NOOP
        nid = self._name_id(name)
        b = st.depth * _LEVEL
        stack = st.stack
        if b + _LEVEL > len(stack):
            stack.extend(array("Q", [0]) * len(stack))
        trace = st.trace or _mint()
        sid = next(self._ids)
        stack[b] = nid
        stack[b + 1] = trace
        stack[b + 2] = sid
        stack[b + 3] = st.sid
        stack[b + 4] = st.trace
        stack[b + 6] = 0
        stack[b + 7] = batch
        st.depth += 1
        st.trace = trace
        st.sid = sid
        st.ctx = None
        hook = self._annotate
        ann = None if hook is None else hook(name)
        st.annotations.append(ann)
        if ann is not None:
            ann.__enter__()
        # the start is taken inside the annotation and the end before it
        # closes, so a buffered span lies within its profiler event
        stack[b + 5] = time.perf_counter_ns()
        return self._closer

    def _pop(self) -> None:
        t1 = time.perf_counter_ns()
        st = self._tls
        ann = st.annotations.pop()
        if ann is not None:
            ann.__exit__(None, None, None)
        st.depth -= 1
        b = st.depth * _LEVEL
        stack = st.stack
        parent = stack[b + 3]
        t0 = stack[b + 5]
        children = stack[b + 6]
        st.trace = stack[b + 4]
        st.sid = parent
        st.ctx = None
        if b:
            stack[b - _LEVEL + 6] += t1 - t0
        self._book(st, stack[b], stack[b + 1], stack[b + 2], parent, t0, t1,
                   stack[b + 7], t1 - t0 - children)

    # -------------------------------------------------------------- reading
    def spans(self) -> list[tuple]:
        """Every buffered span as ``(name, trace_id, span_id, parent_id,
        start_ns, end_ns, thread, batch)``, in start order."""
        buf, names = self._buf[:], self._names
        out = [(names[buf[k]], format(buf[k + 1], "016x"), *buf[k + 2:k + 8])
               for k in range(0, len(buf), _SLOT) if buf[k]]
        out.sort(key=lambda s: s[4])
        return out

    def trace_dump(self, n: int = 16) -> list[dict]:
        """The ``n`` slowest recent traces (slowest first), each with its
        spans in start order — the per-server slow-request log. Span
        times are microseconds of CLOCK_MONOTONIC."""
        by_trace: dict[str, list[dict]] = {}
        for name, trace, sid, parent, t0, t1, _thread, batch in self.spans():
            by_trace.setdefault(trace, []).append({
                "name": name,
                "trace_id": trace,
                "span_id": sid,
                "parent_id": parent,
                "start_us": t0 / 1e3,
                "duration_us": (t1 - t0) / 1e3,
                "annotations": {"batch": batch} if batch else {},
            })
        traces = []
        for trace_id, spans in by_trace.items():
            spans.sort(key=lambda s: s["start_us"])
            roots = [s for s in spans if s["parent_id"] == 0]
            duration = max((s["duration_us"] for s in (roots or spans)))
            traces.append({
                "trace_id": trace_id,
                "duration_us": duration,
                "root": (roots or spans)[0]["name"],
                "n_spans": len(spans),
                "spans": spans,
            })
        traces.sort(key=lambda t: -t["duration_us"])
        return traces[: int(n)]

    def clear(self) -> None:
        """Empty the ring (the span counters keep counting)."""
        self._buf = array("Q", [0]) * len(self._buf)


def clock_offset(spans, events, tolerance: int = 1_000_000
                 ) -> tuple[int, list[int]]:
    """Map buffered spans onto a profiler trace's clock.

    ``spans`` are ``(name, start_ns)`` pairs as the tracer buffered them
    (``perf_counter_ns``); ``events`` are ``(name, start_ns)`` pairs as the
    profiler recorded the same spans' annotations, on its own clock. The
    two clocks tick together, so one offset maps the first onto the second.
    A first guess is the difference most span-event pairs of one name agree
    on (to ``tolerance / 50``); then each span is matched to the nearest
    event of its name, pairs further than ``tolerance`` from the guess are
    left out, and the median of the rest is the next guess. Returns
    ``(offset, residuals)``: the median of ``event start - span start`` over
    the matched pairs, and each matched pair's distance from it, ascending
    (``(0, [])`` when nothing matches).
    """
    by_name: dict[str, list[int]] = {}
    for name, start in events:
        by_name.setdefault(name, []).append(start)
    for starts in by_name.values():
        starts.sort()
    known = [(name, start) for name, start in spans if name in by_name]
    if not known:
        return 0, []
    width = max(tolerance // 50, 1)
    votes: dict[int, int] = {}
    for name, start in known[:: max(1, len(known) // 64)]:
        for e in by_name[name]:
            key = (e - start) // width
            votes[key] = votes.get(key, 0) + 1
    offset = (max(votes, key=votes.get) * 2 + 1) * width // 2
    # match each span to the nearest event of its name, twice: the second
    # pass starts from the first pass's median, finer than a vote's width
    for _ in range(2):
        diffs = []
        for name, start in known:
            starts = by_name[name]
            i = bisect_left(starts, start + offset)
            d = min((e - start for e in starts[max(i - 1, 0):i + 1]),
                    key=lambda x: abs(x - offset))
            if abs(d - offset) <= tolerance:
                diffs.append(d)
        if not diffs:
            return 0, []
        diffs.sort()
        offset = diffs[len(diffs) // 2]
    return offset, sorted(abs(d - offset) for d in diffs)


#: the process-wide tracer every serving module records into
TRACER = Tracer()
if os.environ.get("REPRO_TRACE", "") not in ("", "0"):
    TRACER.enable()


def trace_dump(n: int = 16) -> list[dict]:
    """Module-level shortcut onto the process tracer's slow-request log."""
    return TRACER.trace_dump(n)
