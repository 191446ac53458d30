"""Program spans and counters of the emulator's host path, recorded only
while a JAX profiler session is active.

``span(name, **counts)`` marks one stretch of host work. Outside a
profiler session it returns a shared no-op, so an untraced run pays one
flag check per span. Inside one it opens a
``jax.profiler.TraceAnnotation(name)`` (the span lands on the trace's
host plane, on the device events' clock) and appends one :class:`Span`
to a bounded in-memory log; :func:`records` returns the log and
:func:`clear` empties it. The first span of a new session empties the
log too, so it holds one session's records at most. Counts ride the
log record only; the annotation is bare.

Spans nest per thread. The outermost span of a thread starts a new
call id; the spans under it share that id and link to their parent's
span id. Work that leaves the thread (executor workers, the stream
prefetch thread) carries its caller's :func:`context` and re-enters it
with :func:`attach`, so one top-level call keeps one id across
threads.

Span names used by the emulator (``repro.core.emulator``,
``executor``, ``campaign``) and its techniques:

* ``emu.call`` — one top-level call (``run_many``, ``run_stream_many``,
  ``Campaign.run``); counts ``requests``, the non-NOP requests of the
  records it returns, which the benchmark's readers check against the
  sum over its dispatches.
* ``emu.plan`` — grouping, slot budgets and executable lookup and
  priming (``prepare_tasks`` / ``prepare_stream_tasks``).
* ``emu.pack`` — a group's host-side padding and stacking, or a stream
  group's initial state.
* ``emu.assemble`` — one stream window built on the prefetch thread.
* ``emu.prefetch_wait`` — the stream loop waiting for its next window.
* ``emu.dispatch`` — enqueueing one executable call; counts ``slots``
  (scan length), ``lanes`` (padded batch), ``requests`` (non-NOP
  requests this dispatch newly emulates), ``masked_requests`` (those in
  lanes whose weak-row filter mask is off), ``write_requests`` and
  ``rc_requests`` (the WRITE, and the RC_COPY and RC_INIT, among
  ``requests``), ``shards`` (devices the batch is split over) and
  ``floored`` (1 where the two-lane floor of the batch axis widened a
  dispatch that would otherwise have run one lane a device, else 0).
* ``emu.wait`` — gathering a dispatch's outputs (blocks on the device).
* ``emu.consume`` — folding one stream window's outputs into the
  per-stream accumulators.
* ``emu.finalize`` — turning a group's outputs into result records.
* ``tech.traces`` — ``RowClone.campaign`` building its grid's traces
  and profiling clonability (``repro.core.techniques``); counts
  ``points`` and ``fallback_rows``. It encloses no other span.
"""
from __future__ import annotations

import itertools
import threading
import time
from typing import List, NamedTuple, Optional, Tuple

import jax

from repro.utils.jax_compat import profiler_active

__all__ = ["Span", "span", "count_requests", "context", "attach",
           "active", "records", "clear", "dropped", "CAPACITY"]

# records kept per session, the rest counted; a 10 s benchmark window
# records a few hundred
CAPACITY = 1 << 16


class Span(NamedTuple):
    name: str
    id: int
    parent: Optional[int]   # enclosing span's id, None at a call's top
    call: int               # shared by every span of one top-level call
    thread: int             # threading.get_ident() of the recording thread
    start_ns: int           # time.perf_counter_ns()
    end_ns: int
    counts: dict


_LOG: List[Span] = []
_DROPPED = 0
_LOG_LOCK = threading.Lock()
_SPAN_IDS = itertools.count(1)
_CALL_IDS = itertools.count(1)
_TLS = threading.local()
_SESSION = False  # profiler_active() as last seen; False -> True clears


def _stack() -> list:
    st = getattr(_TLS, "stack", None)
    if st is None:
        st = _TLS.stack = []
    return st


def active() -> bool:
    """True while a JAX profiler session records. The first call that
    sees a new session empties the log."""
    global _SESSION
    on = profiler_active()
    if on != _SESSION:
        with _LOG_LOCK:
            if on and not _SESSION:
                _clear()
            _SESSION = on
    return on


class _Noop:
    """What :func:`span` and :func:`attach` return outside a profiler
    session: enters, exits and records nothing; falsy."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def __bool__(self):
        return False


_NOOP = _Noop()


class _Open:
    """One recording span."""
    __slots__ = ("name", "counts", "id", "parent", "call", "t0", "ann")

    def __init__(self, name: str, counts: dict):
        self.name, self.counts = name, counts

    def add(self, **counts) -> None:
        """Counts known only once the work ran (or is about to)."""
        self.counts.update(counts)

    def __enter__(self):
        st = _stack()
        if st:
            self.call, self.parent = st[-1]
        else:
            self.call, self.parent = next(_CALL_IDS), None
        self.id = next(_SPAN_IDS)
        st.append((self.call, self.id))
        self.ann = jax.profiler.TraceAnnotation(self.name)
        self.ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self.ann.__exit__(*exc)
        _stack().pop()
        _append(Span(self.name, self.id, self.parent, self.call,
                     threading.get_ident(), self.t0, t1, self.counts))
        return False


class _Attached:
    """Re-enters a captured context on another thread."""
    __slots__ = ("ctx",)

    def __init__(self, ctx):
        self.ctx = ctx

    def __enter__(self):
        _stack().append(self.ctx if self.ctx is not None
                        else (next(_CALL_IDS), None))
        return self

    def __exit__(self, *exc):
        _stack().pop()
        return False


def _append(rec: Span) -> None:
    global _DROPPED
    with _LOG_LOCK:
        if len(_LOG) < CAPACITY:
            _LOG.append(rec)
        else:
            _DROPPED += 1


def span(name: str, **counts):
    """Context manager for one span (see the module docstring). The
    object it enters as is falsy outside a session, so counts that cost
    something to compute can be guarded: ``if sp: sp.add(...)``."""
    if not active():
        return _NOOP
    return _Open(name, counts)


def count_requests(sp, results) -> None:
    """Count the non-NOP requests (``n_requests``; quarantined error
    records have none) of an entry point's returned records on its
    ``emu.call`` span ``sp``."""
    if sp:
        sp.add(requests=sum(r.get("n_requests", 0) for r in results))


def context() -> Optional[Tuple[int, Optional[int]]]:
    """The calling thread's innermost ``(call id, span id)``, for
    :func:`attach` on another thread; None outside a session or a span."""
    if not active():
        return None
    st = _stack()
    return st[-1] if st else None


def attach(ctx):
    """Context manager: spans opened inside it on this thread belong to
    ``ctx`` (from :func:`context`). With ``ctx`` None, inside a session,
    they start a call of their own."""
    if not active():
        return _NOOP
    return _Attached(ctx)


def records() -> List[Span]:
    """Every span recorded since the last :func:`clear`, in the order
    they closed."""
    with _LOG_LOCK:
        return list(_LOG)


def dropped() -> int:
    """Spans not recorded because the log held :data:`CAPACITY`."""
    return _DROPPED


def clear() -> None:
    with _LOG_LOCK:
        _clear()


def _clear() -> None:
    global _DROPPED
    _LOG.clear()
    _DROPPED = 0
