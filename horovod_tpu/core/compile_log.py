"""The program's record of its own set-up: every program jax compiles in
this process, by function, and the framework call that caused it.

``setup_s`` was the one end-to-end metric whose layers were timed from
outside (a host clock round ``lower().compile()``); a slow step's first
question, "which function recompiled, and was it a cache miss", had no
answer inside the program. jax publishes what is needed through
``jax.monitoring``: per program, in order and on the compiling thread,
``jaxpr_trace_duration`` (``fun_name='train_step'``; an inner jitted
function's own event fires nested in the outer one's time),
``jaxpr_to_mlir_module_duration`` and ``backend_compile_duration``
(``fun_name='jit(train_step)'``), and inside the last, without a name,
the persistent cache's ``cache_hits`` / ``cache_misses`` and its
retrieval and saved seconds. A stage is announced when it ends, with
its start and its end (``record_event_time_span``): the one listener of
the three stages reads that, and learns the nesting from the times.

:data:`LOG` folds those into one record a program (``name``, ``cause``,
``trace_s``, ``lower_s``, ``backend_s``, ``cache``, ``retrieval_s``,
``saved_s``, ``start``, ``end``), keeps the framework's own host spans
(``hvd.init``, ``hvd.broadcast_parameters``, ``hvd.jax.jit:<fn>``) on a
per-thread stack so that a record's ``cause`` is the innermost span open
when its first event fired, and notes a recompile (a ``hvd.jax.jit``
call past its first during which the backend compiled a program) with
the program and the dispatch index. One listener set a process, installed
by ``hvd.init`` (:func:`install`), never removed; no switch. The
listeners run only when jax compiles something. Records are read
through ``hvd.telemetry()["compile_log"]``; the sums live in the
registry as ``jax.compile.*`` beside ``jax.compiles``.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import List, Optional

from horovod_tpu.core import telemetry as tele

LOGGER = logging.getLogger("horovod_tpu.compile_log")

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"
CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
CACHE_SAVED = "/jax/compilation_cache/compile_time_saved_sec"

#: The span ``hvd.jax.jit`` opens round ``lower`` and ``__call__``.
JIT_SPAN = "hvd.jax.jit:"

RECORD_FIELDS = ("name", "cause", "trace_s", "lower_s", "backend_s",
                 "cache", "retrieval_s", "saved_s", "start", "end")


def _bare(fun_name: str) -> str:
    """``train_step`` of jax's ``jit(train_step)``: tracing names the
    function, lowering and compiling the wrapped one."""
    _, paren, rest = fun_name.partition("(")
    return rest[:-1] if paren and rest.endswith(")") else fun_name


class _Record:
    """One program."""

    __slots__ = RECORD_FIELDS

    def __init__(self, name: str, cause: str, trace_s: float, start: float):
        self.name, self.cause, self.trace_s = name, cause, trace_s
        self.lower_s = self.backend_s = 0.0
        self.cache, self.retrieval_s, self.saved_s = "off", 0.0, 0.0
        self.start = self.end = start

    def as_dict(self) -> dict:
        return {k: getattr(self, k) for k in RECORD_FIELDS}


class _ThreadState(threading.local):
    """What one thread's events have left open. jax traces, lowers and
    compiles a program on the thread that asked for it, so nothing here
    is shared."""

    def __init__(self):
        self.spans: List[str] = []   # open spans, innermost last
        # Traced and not yet lowered: name -> (cause, start, end) of its
        # newest trace, in the order they ended, none nested in another.
        # Most never are lowered (``jax.eval_shape``, an inner function
        # whose outer one has no event), so only MAX_TRACED are kept.
        self.traced: dict = {}
        # Lowered and not yet compiled: the AOT path compiles later, in
        # ``Lowered.compile()``, and some are never compiled.
        self.lowered: deque = deque(maxlen=16)
        # The cache's word on the compile under way: [cache, retrieval_s,
        # saved_s]. It comes before the compile's own event, which ends it.
        self.cache: Optional[list] = None
        # The newest compiled here, as (LOG.compiled after it, record): a
        # recompile's note is made from them, and a record past
        # MAX_RECORDS is nowhere else.
        self.compiled: deque = deque(maxlen=4)


class _Span:
    """``with LOG.span(name):`` — a host span on this thread's stack,
    kept in the log when it closes."""

    __slots__ = ("log", "name", "annotation", "parent", "start")

    def __init__(self, log: "CompileLog", name: str):
        import jax

        self.log, self.name = log, name
        # On the device trace's clock too: a capture over a start-up or a
        # recompile shows the span round jax's own compile events.
        self.annotation = jax.profiler.TraceAnnotation(name)

    def __enter__(self) -> "_Span":
        stack = self.log.open_spans()
        self.parent = stack[-1] if stack else ""
        stack.append(self.name)
        self.annotation.__enter__()
        self.start = time.time()
        return self

    def __exit__(self, *exc):
        end = time.time()
        self.annotation.__exit__(*exc)
        self.log.open_spans().pop()
        self.log.keep_span(self.name, self.start, end, self.parent)
        return False


class CompileLog:
    """Records, spans and recompiles, in memory and bounded: the first
    :attr:`MAX_RECORDS` programs whole, then counts only (the
    ``jax.compile.*`` series go on)."""

    MAX_RECORDS = 256
    MAX_TRACED = 256
    MAX_SPANS = 256
    MAX_RECOMPILES = 64

    def __init__(self):
        self._lock = threading.Lock()
        self._state = _ThreadState()
        self._records: List[_Record] = []
        self._spans: List[dict] = []
        self._recompiles: deque = deque(maxlen=self.MAX_RECOMPILES)
        #: Programs lowered in this process, kept as records or not.
        self.programs = 0
        #: Programs the backend compiled (``jax.compiles``): what
        #: ``_InstrumentedJit.__call__`` and the watchdog compare.
        self.compiled = 0
        self.recompiles = 0
        self._on_time_span = {TRACE: self._traced, LOWER: self._lowered,
                              BACKEND: self._compiled}
        self._on_duration = {CACHE_RETRIEVAL: 1, CACHE_SAVED: 2}
        self._on_event = {CACHE_HIT: "hit", CACHE_MISS: "miss"}

    # -- spans ---------------------------------------------------------------

    def open_spans(self) -> List[str]:
        """This thread's stack of open span names, innermost last."""
        return self._state.spans

    def span(self, name: str) -> _Span:
        """A host span round a rare call (``hvd.init``, the broadcast,
        ``lower``), also a ``jax.profiler.TraceAnnotation``. The per-step
        path pushes its name itself (``_InstrumentedJit.__call__``)."""
        return _Span(self, name)

    def keep_span(self, name: str, start: float, end: float, parent: str):
        with self._lock:
            if len(self._spans) < self.MAX_SPANS:
                self._spans.append({"name": name, "start": start,
                                    "end": end, "parent": parent})

    def compiled_in_call(self, fn_name: str, seconds: float, since: int,
                         dispatch: int):
        """A ``hvd.jax.jit`` call that just returned, ``seconds`` long,
        with :attr:`compiled` above ``since``: its span is kept (a call
        that compiled nothing leaves none: it is the per-step path), and
        past the wrapper's first call (``dispatch`` > 0) it is a
        recompile if the program was this thread's."""
        end = time.time()
        self.keep_span(JIT_SPAN + fn_name, end - seconds, end,
                       self._innermost())
        # The call may also have compiled a program that lays out an
        # argument: the function's own, else the longest.
        mine = [r for n, r in self._state.compiled if n > since]
        if dispatch > 0 and mine:
            rec = max(mine, key=lambda r: (r.name == fn_name, r.backend_s))
            tele.REGISTRY.counter("jax.recompiles").inc()
            with self._lock:
                self.recompiles += 1
                self._recompiles.append(
                    {"name": rec.name, "dispatch": dispatch,
                     "backend_s": rec.backend_s, "cache": rec.cache})

    def last_recompile(self) -> Optional[dict]:
        with self._lock:
            return dict(self._recompiles[-1]) if self._recompiles else None

    # -- jax.monitoring listeners --------------------------------------------
    # A fault in the bookkeeping must never fail a user's compile.

    def on_time_span(self, event: str, start: float, end: float, **kwargs):
        handler = self._on_time_span.get(event)
        if handler is not None:
            try:
                handler(kwargs.get("fun_name", ""), start, end)
            except Exception:  # pragma: no cover - defensive
                LOGGER.debug("compile log listener failed", exc_info=True)

    def on_duration(self, event: str, seconds: float, **kwargs):
        field = self._on_duration.get(event)
        if field is not None:
            self._cache(field, float(seconds))
            tele.REGISTRY.counter(
                "jax.compile.cache_retrieval_s" if event == CACHE_RETRIEVAL
                else "jax.compile.saved_s").inc(seconds)

    def on_event(self, event: str, **kwargs):
        cache = self._on_event.get(event)
        if cache is not None:
            self._cache(0, cache)
            tele.REGISTRY.counter(
                "jax.compile.cache_hits" if cache == "hit"
                else "jax.compile.cache_misses").inc()

    def _cache(self, field: int, value):
        st = self._state
        if st.cache is None:
            st.cache = ["off", 0.0, 0.0]
        st.cache[field] = value

    def _innermost(self) -> str:
        stack = self._state.spans
        return stack[-1] if stack else ""

    @staticmethod
    def _drop_since(traced: dict, start: float):
        """Forget the traces that began at ``start`` or later: they ended
        inside the stage that began then and are its time."""
        while traced:
            newest = next(reversed(traced))
            if traced[newest][1] < start:
                break
            del traced[newest]

    def _traced(self, name: str, start: float, end: float):
        # An inner function's trace ended first, inside this one. The
        # spans open now were open when the trace began: one opened
        # inside it has closed inside it.
        traced = self._state.traced
        self._drop_since(traced, start)
        traced.pop(name, None)
        traced[name] = (self._innermost(), start, end)
        if len(traced) > self.MAX_TRACED:
            del traced[next(iter(traced))]

    def _begin(self, name: str, start: float) -> _Record:
        """The record of the program whose lowering (or compiling, had the
        listeners come later) began at ``start``: with the newest trace of
        that name on this thread, or with none (the jaxpr was cached)."""
        traced = self._state.traced
        # A lowering rule's own jitted helpers, traced meanwhile.
        self._drop_since(traced, start)
        cause, start, end = traced.pop(
            name, (self._innermost(), start, start))
        with self._lock:
            rec = _Record(name, cause, end - start, start)
            self.programs += 1
            if len(self._records) < self.MAX_RECORDS:
                self._records.append(rec)
        tele.REGISTRY.counter("jax.compile.trace_s").inc(rec.trace_s)
        return rec

    def _lowered(self, name: str, start: float, end: float):
        st = self._state
        rec = self._begin(_bare(name), start)
        rec.lower_s, rec.end = end - start, end
        st.lowered.append(rec)
        st.cache = None
        tele.REGISTRY.counter("jax.compile.lower_s").inc(rec.lower_s)

    def _compiled(self, name: str, start: float, end: float):
        st, name = self._state, _bare(name)
        for rec in reversed(st.lowered):
            if rec.name == name:
                st.lowered.remove(rec)
                break
        else:
            rec = self._begin(name, start)
        rec.backend_s, rec.end = end - start, end
        if st.cache is not None:
            (rec.cache, rec.retrieval_s, rec.saved_s), st.cache = st.cache, None
        with self._lock:
            self.compiled += 1
            st.compiled.append((self.compiled, rec))
        tele.REGISTRY.counter("jax.compiles").inc()
        tele.REGISTRY.counter("jax.compile.backend_s").inc(rec.backend_s)

    # -- views ---------------------------------------------------------------

    def snapshot(self) -> dict:
        """What ``hvd.telemetry()["compile_log"]`` holds."""
        with self._lock:
            return {"programs": self.programs,
                    "records": [r.as_dict() for r in self._records],
                    "spans": [dict(s) for s in self._spans],
                    "recompiles": [dict(r) for r in self._recompiles]}

    def report_lines(self) -> List[str]:
        """The "compiles" table of ``hvd.telemetry_report()``: the ten
        longest records and the totals of those kept."""
        records = self.snapshot()["records"]
        if not records:
            return []

        def total(r):
            return r["trace_s"] + r["lower_s"] + r["backend_s"]

        def row(name, cause, r, cache):
            return (f"  {name[:24]:24s} {cause[:36]:36s} "
                    f"{r['trace_s']:8.3f} {r['lower_s']:8.3f} "
                    f"{r['backend_s']:8.3f} {cache:>5s}")

        out = [f"compiles ({self.programs} programs, "
               f"{len(records)} recorded):",
               f"  {'name':24s} {'cause':36s} {'trace_s':>8s} "
               f"{'lower_s':>8s} {'backend_s':>8s} {'cache':>5s}"]
        for r in sorted(records, key=total, reverse=True)[:10]:
            out.append(row(r["name"], r["cause"] or "-", r, r["cache"]))
        sums = {k: sum(r[k] for r in records)
                for k in ("trace_s", "lower_s", "backend_s")}
        misses = sum(r["cache"] == "miss" for r in records)
        out.append(row("total", f"{misses} cache misses", sums, ""))
        return out


LOG = CompileLog()

_install_lock = threading.Lock()
_installed = False


def install():
    """Register :data:`LOG`'s listeners with ``jax.monitoring``, once a
    process (``hvd.init`` calls this every time; a second call registers
    nothing)."""
    global _installed
    with _install_lock:
        if _installed:
            return
        _installed = True
    import jax.monitoring as monitoring

    monitoring.register_event_time_span_listener(LOG.on_time_span)
    monitoring.register_event_duration_secs_listener(LOG.on_duration)
    monitoring.register_event_listener(LOG.on_event)
