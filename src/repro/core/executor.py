"""Overlapped campaign executor: run prepared compile-key groups with
host/device overlap instead of the serial pack -> dispatch -> block loop.

Why a thread pool and not async dispatch alone: every task ends by
gathering its outputs to the host (``np.asarray``), which blocks its
thread until the device finishes, so one Python thread would serialize
pack -> dispatch -> gather. XLA releases the GIL while it runs, so two
*threads* overlap: while a worker waits on group k, another packs
(``np.stack`` / padding, pure Python+NumPy) and dispatches group k+1.
Whether that overlap pays on a given device is a measurement, not a
property of this module.

Determinism contract:

* A :class:`GroupTask` is *prepared* on the caller's thread — in
  particular :func:`repro.core.emulator._batched_fn` (the in-memory
  executable LRU) is resolved before any worker starts, so
  ``cache_stats()`` counters are exactly what the serial loop would
  produce, in the same order.
* Each task's ``finalize`` writes only its own result slots (disjoint
  indices of a shared list), so concurrent finalization needs no lock.
* Execution is bit-identical to the serial loop by construction: the
  same executable runs on the same packed arrays; only wall-clock
  interleaving changes. ``execute(tasks, serial=True)`` keeps the
  in-order loop for A/B.

The pool is module-level and lazily built (``REPRO_EXEC_WORKERS`` caps
it, default ``min(cpu_count, 8)``); :func:`set_workers` resizes it.
Worker threads only ever touch jax through executable calls and
``jnp.asarray`` staging, both thread-safe.
"""
from __future__ import annotations

import atexit
import dataclasses
import os
import threading
import time
import warnings
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Callable, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import spans

__all__ = ["GroupTask", "StreamTask", "TaskFailure", "ExecutionError",
           "execute", "submit_task", "set_workers", "workers",
           "shutdown", "is_shutdown"]


@dataclasses.dataclass
class GroupTask:
    """One compile-key group, prepared but not yet executed.

    ``fn`` is the resolved (jitted, possibly shard_mapped) batched
    executable; ``pack`` builds its argument arrays on the host and
    returns ``(args, ctx)``; ``finalize`` receives the gathered NumPy
    outputs plus ``ctx`` and writes per-trace records into the
    caller's result slots. ``pack`` and ``finalize`` run on a worker
    thread under :func:`execute`'s overlapped mode — keep them free of
    shared mutable state beyond the disjoint result slots.

    ``counts`` are the ``emu.dispatch`` span's counts and ``trace_ctx``
    the caller's :func:`repro.core.spans.context` at preparation, which
    the worker re-enters (see :mod:`repro.core.spans`).
    """
    fn: Callable[..., Any]
    pack: Callable[[], Tuple[tuple, Any]]
    finalize: Callable[[dict, Any], None]
    label: str = ""
    cost: int = 0   # relative work hint (e.g. slots * batch) for LPT order
    counts: dict = dataclasses.field(default_factory=dict)
    trace_ctx: Any = None

    # pack() re-pads and re-stacks from the immutable prepared traces and
    # finalize() overwrites the same disjoint slots, so a failed attempt
    # can safely be retried from scratch (transient-failure recovery)
    retryable = True

    def run(self) -> None:
        with spans.attach(self.trace_ctx):
            with spans.span("emu.pack"):
                args, ctx = self.pack()                  # host: pad + stack
            with spans.span("emu.dispatch", **self.counts):
                out = self.fn(*args)                     # device: the scan
            with spans.span("emu.wait"):                 # gather (blocks)
                out = {k: np.asarray(v) for k, v in out.items()}
            with spans.span("emu.finalize"):
                self.finalize(out, ctx)


@dataclasses.dataclass
class StreamTask:
    """One streaming compile-key group: a window loop instead of a
    single dispatch (see ``repro.core.emulator.prepare_stream_tasks``).

    ``pack`` builds the initial carried state plus a host context;
    ``windows(ctx)`` yields one ``(args, counts)`` pair per trace
    window (the last one freeze-lifted to drain the tail in place);
    ``fn(state, *args)`` is the resolved window
    executable returning ``(new_state, emitted)``; ``consume`` receives
    each window's gathered NumPy emission; ``finalize`` receives the
    final carried state. The loop is inherently serial per task — state
    threads window to window — but host and device still overlap WITHIN
    it: window assembly (trace generation / file parsing, ``np.stack``,
    staging) runs on a dedicated prefetch thread one window ahead while
    the current window is inside XLA (which releases the GIL for the
    whole execution — the same observation the group-level pool is
    built on). The prefetch queue is bounded, so a stream holds at most
    ``_PREFETCH`` staged windows at once — constant memory, whatever
    the trace length. The executor additionally overlaps DIFFERENT
    stream/group tasks across workers. Same determinism contract as
    :class:`GroupTask`: disjoint result slots, prepared on the
    caller's thread; prefetch changes wall-clock interleaving only,
    never the window sequence.

    A window's own ``counts`` (``requests``, ``write_requests``,
    ``rc_requests``: the requests it brings fresh) are its
    ``emu.dispatch`` span's counts beside the task's. ``trace_ctx`` is as
    on :class:`GroupTask`; the prefetch thread re-enters it too."""
    fn: Callable[..., Any]
    pack: Callable[[], Tuple[Any, Any]]
    windows: Callable[[Any], Any]  # ctx -> iterable of (arg tuple, counts)
    consume: Callable[[tuple, Any], None]
    finalize: Callable[[Any, Any], None]
    label: str = ""
    cost: int = 0
    counts: dict = dataclasses.field(default_factory=dict)
    trace_ctx: Any = None

    # a failed window loop cannot be replayed: the stream iterators and
    # chunker buffers are partially consumed — never auto-retry
    retryable = False

    _PREFETCH = 2  # max staged windows in flight (bounds memory)

    def run(self) -> None:
        with spans.attach(self.trace_ctx):
            self._run()

    def _run(self) -> None:
        import queue as _queue

        with spans.span("emu.pack"):
            state, ctx = self.pack()
        tctx = spans.context()
        q: _queue.Queue = _queue.Queue(maxsize=self._PREFETCH)
        done, stop = object(), threading.Event()

        def put(item) -> bool:
            # _SHUTDOWN poisons the feed at interpreter exit: a prefetch
            # thread mid-stream must not keep generating windows (or
            # block forever on a full queue) while the process tears down
            while not stop.is_set() and not _SHUTDOWN.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except _queue.Full:
                    continue
            return False

        def feed() -> None:
            with spans.attach(tctx):
                try:
                    it = iter(self.windows(ctx))
                    while True:
                        with spans.span("emu.assemble"):
                            win = next(it, done)
                        if win is done:
                            break
                        if not put(win):
                            return      # consumer bailed; stop generating
                    put(done)
                except BaseException as e:  # surface on the consumer
                    put(e)

        th = threading.Thread(target=feed, daemon=True,
                              name="repro-stream-prefetch")
        th.start()
        try:
            while True:
                try:
                    with spans.span("emu.prefetch_wait"):
                        win = q.get(timeout=0.2)
                except _queue.Empty:
                    # a poisoned feeder (interpreter shutdown) never
                    # delivers its `done` sentinel — fail the window
                    # loop instead of blocking a non-daemon pool worker
                    # forever (which would deadlock interpreter exit)
                    if _SHUTDOWN.is_set():
                        raise RuntimeError(
                            f"stream task {self.label or 'task'!r} "
                            f"aborted: executor shut down at interpreter "
                            f"exit")
                    continue
                if win is done:
                    break
                if isinstance(win, BaseException):
                    raise win
                args, fresh = win
                with spans.span("emu.dispatch", **fresh, **self.counts):
                    state, out = self.fn(state, *args)   # device: one window
                with spans.span("emu.wait"):
                    out = tuple(np.asarray(o) for o in out)
                with spans.span("emu.consume"):
                    self.consume(out, ctx)
        finally:
            # deterministic shutdown: signal stop, then DRAIN the queue
            # while joining — a feeder sitting in q.put() frees its slot
            # immediately instead of burning its 0.1s put-timeout per
            # queued window, and the loop converges however many windows
            # are in flight. The deadline only guards a feeder stuck
            # inside the user's window generator (next() cannot be
            # interrupted from outside); that pathological case is
            # reported, not silently leaked.
            stop.set()
            deadline = time.monotonic() + 5.0
            while th.is_alive() and time.monotonic() < deadline:
                try:
                    q.get_nowait()
                except _queue.Empty:
                    pass
                th.join(timeout=0.02)
            if th.is_alive():  # pragma: no cover - needs a hung generator
                warnings.warn(
                    f"stream prefetch thread for {self.label or 'task'!r} "
                    f"did not stop within 5s (window generator blocked); "
                    f"leaking a daemon thread", RuntimeWarning)
        with spans.span("emu.finalize"):
            self.finalize(state, ctx)


def _env_int(name: str, default: int) -> int:
    """Parse an integer env knob; a bad value must not kill library
    import — warn with the offending value and fall back."""
    env = os.environ.get(name)
    if not env:
        return default
    try:
        return int(env)
    except ValueError:
        import warnings
        warnings.warn(f"ignoring non-integer {name}={env!r}; "
                      f"using default {default}", stacklevel=2)
        return default


def _workers_default() -> int:
    return max(1, _env_int("REPRO_EXEC_WORKERS",
                           min(os.cpu_count() or 1, 8)))


_LOCK = threading.Lock()
_POOL: Optional[ThreadPoolExecutor] = None
_WORKERS = _workers_default()
# set once, at interpreter exit (or by an explicit shutdown()): poisons
# StreamTask prefetch feeds and queue waits so no worker blocks teardown
_SHUTDOWN = threading.Event()


def workers() -> int:
    """Current overlapped-execution worker count."""
    return _WORKERS


def set_workers(n: int) -> int:
    """Resize the worker pool; returns the previous count. ``n <= 1``
    makes :func:`execute` fall back to the serial in-order loop."""
    global _POOL, _WORKERS
    if n < 1:
        raise ValueError(f"worker count must be >= 1, got {n}")
    with _LOCK:
        old = _WORKERS
        _SHUTDOWN.clear()   # re-arm after an explicit shutdown() (tests)
        if n != _WORKERS:
            if _POOL is not None:
                _POOL.shutdown(wait=True)
                _POOL = None
            _WORKERS = n
    return old


def _pool() -> ThreadPoolExecutor:
    global _POOL
    with _LOCK:
        if _SHUTDOWN.is_set():
            raise RuntimeError(
                "executor pool is shut down (interpreter exit or explicit "
                "executor.shutdown()); no further dispatches accepted")
        if _POOL is None:
            _POOL = ThreadPoolExecutor(
                max_workers=_WORKERS, thread_name_prefix="repro-exec")
        return _POOL


def is_shutdown() -> bool:
    """True once the executor has been poisoned (interpreter exit or an
    explicit :func:`shutdown`); new dispatches are refused."""
    return _SHUTDOWN.is_set()


def shutdown(wait: bool = False) -> None:
    """Drain/poison the executor for process teardown.

    Ordering matters at interpreter exit: ThreadPoolExecutor's own
    threading hook JOINS its (non-daemon) worker threads, so any worker
    blocked on a queue — a StreamTask consumer whose prefetch feeder
    died, a feeder stuck in ``q.put`` — would deadlock ``python`` on
    exit, and a killed client could leave a server's dispatch threads
    holding the device indefinitely. This runs FIRST (module ``atexit``
    handlers precede threading's join of non-daemon threads): it poisons
    the StreamTask feed/consume loops via the module event, cancels
    queued-but-unstarted tasks, and lets in-flight XLA executions finish
    on their own (they cannot be interrupted, only awaited). Idempotent;
    :func:`set_workers` after an explicit shutdown re-arms the pool."""
    global _POOL
    _SHUTDOWN.set()
    with _LOCK:
        pool, _POOL = _POOL, None
    if pool is not None:
        pool.shutdown(wait=wait, cancel_futures=True)


atexit.register(shutdown)


@dataclasses.dataclass
class TaskFailure:
    """One task that did not complete: the task object, its label, the
    exception from its final attempt, and how many attempts ran (0 for
    a dispatch timeout — the attempt never settled)."""
    task: Any
    label: str
    error: BaseException
    attempts: int


class ExecutionError(RuntimeError):
    """Aggregate of every failed task in one :func:`execute` call. The
    message names EVERY failed group label (a sweep debugging session
    should not need N reruns to see N failures) and carries the first
    underlying error's text; ``failures`` holds the full records."""

    def __init__(self, failures: Sequence[TaskFailure]):
        self.failures = list(failures)
        labels = ", ".join(
            (f.label or f"task{i}") for i, f in enumerate(self.failures))
        first = self.failures[0].error
        super().__init__(
            f"{len(self.failures)} task(s) failed [{labels}]; first: "
            f"{type(first).__name__}: {first}")


def _attempt(task: Any, retries: int, backoff: float
             ) -> Optional[TaskFailure]:
    """Run one task to completion with bounded retry-with-backoff.
    Only ``task.retryable`` tasks are re-attempted (GroupTask packing is
    idempotent; a StreamTask's iterators are consumed). Returns None on
    success, else the failure record — never raises."""
    attempts = 0
    while True:
        attempts += 1
        try:
            task.run()
            return None
        except BaseException as e:
            if not getattr(task, "retryable", False) or attempts > retries:
                return TaskFailure(task, getattr(task, "label", ""),
                                   e, attempts)
            time.sleep(backoff * (2 ** (attempts - 1)))


def submit_task(task: Any, retries: Optional[int] = None,
                backoff: Optional[float] = None) -> "Future":
    """Asynchronous single-task entry point (what the sweep service's
    dispatcher uses): submit one PREPARED task to the overlapped worker
    pool and return its :class:`concurrent.futures.Future`, which
    resolves to ``None`` on success or a :class:`TaskFailure` record —
    never an exception (same ``_attempt`` semantics as :func:`execute`,
    including bounded retry-with-backoff for retryable tasks). The
    caller owns result demultiplexing: the task's ``finalize`` has run
    by the time the future resolves ``None``. Raises ``RuntimeError``
    after :func:`shutdown` (teardown refuses new dispatches)."""
    if retries is None:
        retries = max(0, _env_int("REPRO_EXEC_RETRIES", 0))
    if backoff is None:
        backoff = float(os.environ.get("REPRO_EXEC_BACKOFF_S", "") or 0.05)
    return _pool().submit(_attempt, task, retries, backoff)


def execute(tasks: Sequence[Any], serial: Optional[bool] = None,
            timeout: Optional[float] = None, retries: Optional[int] = None,
            backoff: Optional[float] = None,
            raise_on_error: bool = True) -> List[TaskFailure]:
    """Run every task; overlapped across the worker pool unless
    ``serial`` (or a single task / single worker) forces the in-order
    loop. Tasks were prepared in submission order on the caller's
    thread, so compile-cache counters are already settled; execution
    order does not affect results (disjoint result slots).

    Failure isolation: a raising task never stops its siblings — every
    task settles, failures are collected into :class:`TaskFailure`
    records, and (``raise_on_error``, the default) one
    :class:`ExecutionError` naming every failed label is raised at the
    end; ``raise_on_error=False`` returns the records instead (what
    ``Campaign.run(on_error='quarantine')`` uses).

    Transient-failure recovery: ``retries`` (default
    ``REPRO_EXEC_RETRIES``, 0) re-attempts each *retryable* task with
    exponential backoff starting at ``backoff`` seconds (default
    ``REPRO_EXEC_BACKOFF_S``, 0.05). ``timeout`` (default
    ``REPRO_EXEC_TIMEOUT_S``, none) bounds each task's wall time in
    overlapped mode: a task past its deadline is recorded as a
    ``TimeoutError`` failure and ABANDONED — Python threads cannot be
    killed, so its worker keeps running detached (it may still write
    its disjoint result slots later); treat timed-out sweeps' result
    lists as tainted and re-dispatch. In serial mode there is no second
    thread to watch the clock, so ``timeout`` is not enforced."""
    tasks = list(tasks)
    if retries is None:
        retries = max(0, _env_int("REPRO_EXEC_RETRIES", 0))
    if backoff is None:
        backoff = float(os.environ.get("REPRO_EXEC_BACKOFF_S", "") or 0.05)
    if timeout is None:
        env_t = os.environ.get("REPRO_EXEC_TIMEOUT_S")
        timeout = float(env_t) if env_t else None
    if serial is None:
        serial = len(tasks) <= 1 or _WORKERS <= 1

    failures: List[TaskFailure] = []
    if serial:
        for t in tasks:
            fail = _attempt(t, retries, backoff)
            if fail is not None:
                failures.append(fail)
    else:
        # longest-processing-time-first: dispatching expensive groups
        # first minimizes the tail where one worker finishes a big group
        # alone (order is free to change — results land in disjoint slots)
        tasks.sort(key=lambda t: t.cost, reverse=True)
        starts: dict = {}

        def tracked(t):
            starts[id(t)] = time.monotonic()
            return _attempt(t, retries, backoff)

        pending = {_pool().submit(tracked, t): t for t in tasks}
        if timeout is None:
            for f in pending:           # block; _attempt never raises
                fail = f.result()
                if fail is not None:
                    failures.append(fail)
        else:
            while pending:              # poll so deadlines fire on time
                for f in list(pending):
                    t = pending[f]
                    started = starts.get(id(t))
                    if f.done():
                        del pending[f]
                        fail = f.result()
                        if fail is not None:
                            failures.append(fail)
                    elif started is not None \
                            and time.monotonic() - started > timeout:
                        del pending[f]  # abandon; see docstring
                        failures.append(TaskFailure(
                            t, getattr(t, "label", ""),
                            TimeoutError(
                                f"task {getattr(t, 'label', '')!r} "
                                f"exceeded the {timeout}s dispatch "
                                f"timeout"), 0))
                if pending:
                    time.sleep(0.005)

    if failures and raise_on_error:
        raise ExecutionError(failures)
    return failures
