"""Spans inside the engine, recorded while a JAX profiler session runs.

Tracing is on exactly while the profiler is active (``TraceMe.is_enabled``
of JAX's profiler): inside ``jax.profiler.trace(...)``, between
``start_trace`` and ``stop_trace``, or while the profiler server captures
a live service.  There is no other switch.  Off, a span site costs that
one check: it creates no object, takes no lock and reads no clock.

On, each span

- opens a ``TraceMe`` under its name, so that it lands in the profiler's
  own trace, on the host plane and the device trace's time base;
- records its name, its start and end on ``time.time_ns()`` (the clock of
  ``JobEvent``), the thread's CPU time over it (wall minus CPU is time off
  the CPU, waiting for the interpreter lock or for I/O), its parent span,
  the invocation it belongs to and a few attributes, into the span log of
  the run that the current thread works for.

A run binds a fresh :class:`SpanLog` to its loop thread (:func:`run_log`)
and hands the spans out as ``RunResult.spans``.  A thread pool passes no
context on, so worker and transfer threads are given theirs explicitly
(:func:`invocation`, :func:`attach`, :func:`bound`).  A span on a thread
with no log bound reaches the profiler's trace only.

An invocation's spans share its key ``(namespace, run key, attempt)``,
where the run key is the invocation path, or ``path#specN`` for a
speculative twin.  The root of an invocation is its ``JobEvent`` (launch
to harvest); ``sf:work`` names it by that key and has no parent span.

Span names (one place each, where the work happens):

``sf:tick``        one iteration of the engine loop, before its wait;
                   children ``sf:fire``, ``sf:schedule``, ``sf:harvest``
``sf:schedule``    the tick's placement pass and stage-in; ``waiting``
                   (the queue's length), ``probed`` (jobs the policy was
                   asked about), ``placed``, ``skipped`` (the pass did not
                   run: nothing it reads had changed)
``sf:work``        the worker function of one invocation
``sf:stage_in``    waiting for (or running) the invocation's transfers
``sf:body``        the step's own function
``sf:encode``      ``serialize``; ``bytes``, and ``lazy`` for the bytes of
                   a resident entry, made when first asked for
``sf:decode``      ``deserialize``; ``bytes``
``sf:cas_put``     ``ObjectStore.put``: hash and index; ``bytes``, ``dedup``
``sf:hold``        ``ObjectStore.put_value`` of device arrays: waiting until
                   they are ready; ``bytes``, ``key``
``sf:transfer``    ``DataManager.transfer_sync``; ``kind``, ``bytes``
``sf:journal``     one journal record, write, flush and fsync; ``kind``
``sf:deploy``      a site's deploy or undeploy; ``model``, ``event``
``sf:compile``     one backend compile (from ``jax.monitoring``), parented
                   to the span that was open when it ran; ``fun``
"""
from __future__ import annotations

import itertools
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

#: the ``jax.monitoring`` duration event that ``sf:compile`` reads
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

Key = Tuple[str, str, int]       # (namespace, run key, attempt)


def _probe():
    """``(is_enabled, TraceMe)`` of JAX's profiler; where it cannot be
    imported, a probe that always reads off."""
    try:
        from jax._src.lib import _profiler
        return _profiler.TraceMe.is_enabled, _profiler.TraceMe
    except Exception:                       # noqa: BLE001 — no profiler
        return (lambda: False), None


enabled, _TraceMe = _probe()


@dataclass(slots=True)
class Span:
    name: str
    start_ns: int
    end_ns: int = 0
    cpu_ns: Optional[int] = None    # thread CPU time; None for sf:compile
    id: int = 0
    parent: Optional[int] = None
    key: Optional[Key] = None
    attrs: Dict[str, Any] = field(default_factory=dict)


class SpanLog:
    """The spans of one run, in the order they ended."""

    def __init__(self):
        self._lock = threading.Lock()
        self._spans: List[Span] = []          # lock: _lock

    def add(self, span: Span):
        with self._lock:
            self._spans.append(span)

    def spans(self) -> List[Span]:
        with self._lock:
            return list(self._spans)


# the current thread's context: (log, parent span id, invocation key)
_tls = threading.local()
_ids = itertools.count(1)


def _context():
    return getattr(_tls, "ctx", None) or (None, None, None)


class _Off:
    """The span, scope and binding that tracing off hands out: one
    shared object that does nothing."""
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False

    def set(self, key: str, value: Any):
        pass


class _OffSpan(_Off):
    __slots__ = ()

    def __enter__(self):
        return self


_OFF = _Off()
_OFF_SPAN = _OffSpan()


class _Open:
    """One span while it is open on the current thread."""
    __slots__ = ("span", "_log", "_prev", "_me")

    def __init__(self, name: str):
        self.span = Span(name, 0)

    def __enter__(self):
        self._prev = getattr(_tls, "ctx", None)
        log, parent, key = self._prev or (None, None, None)
        s = self.span
        s.id, s.parent, s.key = next(_ids), parent, key
        self._log = log
        self._me = _TraceMe(s.name)
        self._me.__enter__()
        _tls.ctx = (log, s.id, key)
        s.start_ns = time.time_ns()
        s.cpu_ns = time.thread_time_ns()
        return self

    def __exit__(self, *exc):
        s = self.span
        s.cpu_ns = time.thread_time_ns() - s.cpu_ns
        s.end_ns = time.time_ns()
        _tls.ctx = self._prev
        self._me.__exit__(*exc)
        if self._log is not None:
            self._log.add(s)
        return False

    def set(self, key: str, value: Any):
        self.span.attrs[key] = value


def span(name: str):
    """A context manager timing one span; ``set(key, value)`` on what it
    returns adds an attribute."""
    if not enabled():
        return _OFF_SPAN
    return _Open(name)


# ------------------------------------------------------------ contexts
class _Bind:
    __slots__ = ("ctx", "_prev")

    def __init__(self, ctx):
        self.ctx = ctx

    def __enter__(self):
        self._prev = getattr(_tls, "ctx", None)
        _tls.ctx = self.ctx
        return self.ctx[0]

    def __exit__(self, *exc):
        _tls.ctx = self._prev
        return False


def run_log():
    """Bind a fresh :class:`SpanLog` to this thread for one run.  The
    context manager gives the log, or None when tracing is off."""
    if not enabled():
        return _OFF
    _listen_for_compiles()
    return _Bind((SpanLog(), None, None))


def invocation(namespace: str, run_key: str, attempt: int):
    """The context for an invocation's worker, carrying this thread's log
    and the invocation's key, with no parent span (the ``JobEvent`` is
    the root).  None when tracing is off or no log is bound."""
    if not enabled():
        return None
    log = _context()[0]
    return None if log is None else (log, None, (namespace, run_key, attempt))


def attach(ctx):
    """Bind a context taken on another thread (``invocation``) to this
    one for the duration of a ``with``."""
    return _OFF if ctx is None else _Bind(ctx)


def bound(fn: Callable) -> Callable:
    """``fn`` carrying the current thread's context into the thread that
    will call it; ``fn`` itself when there is none to carry."""
    if not enabled():
        return fn
    ctx = getattr(_tls, "ctx", None)
    if ctx is None:
        return fn

    def call(*args, **kwargs):
        with _Bind(ctx):
            return fn(*args, **kwargs)
    return call


# ------------------------------------------------------------ compiles
_listening = False
_listen_lock = threading.Lock()


def _on_duration(event: str, seconds: float, **kwargs):
    if event != COMPILE_EVENT or not enabled():
        return
    log, parent, key = _context()
    if log is None:
        return
    end = time.time_ns()
    log.add(Span("sf:compile", end - int(seconds * 1e9), end, id=next(_ids),
                 parent=parent, key=key,
                 attrs={"fun": kwargs.get("fun_name")}))


def _listen_for_compiles():
    """Register the compile listener once, the first time a run traces."""
    global _listening
    with _listen_lock:
        if _listening:
            return
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True
