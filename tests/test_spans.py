"""Program spans (``repro.core.spans``) and the executor's use of them,
without compiling anything: outside a profiler session nothing is
recorded or annotated; inside one (or with the session's flag forced),
a task run on an executor worker, and a stream window assembled on the
prefetch thread, record under the caller's call id and span; the log
is bounded. The emulator's own spans, on real dispatches inside a real
session, are checked on the benchmark's tiny cells
(``bench/tests/test_bench_spans.py``)."""
import threading

import jax
import numpy as np
import pytest

from repro.core import executor, spans
from repro.utils import jax_compat


@pytest.fixture
def annotated(monkeypatch):
    """Names of every TraceAnnotation opened while the test runs."""
    names = []
    orig = jax.profiler.TraceAnnotation

    def counting(name, **kw):
        names.append(name)
        return orig(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", counting)
    spans.clear()
    yield names
    spans.clear()


@pytest.fixture
def recording(monkeypatch, annotated):
    """Spans record as inside a profiler session."""
    monkeypatch.setattr(spans, "profiler_active", lambda: True)
    return annotated


COUNTS = {"slots": 7, "lanes": 4, "requests": 5, "shards": 1}


def _group_tasks(ctx=None):
    def task(i):
        return executor.GroupTask(
            fn=lambda a: {"out": a + 1},
            pack=lambda: ((np.arange(i + 1),), i),
            finalize=lambda out, c: None, label=f"g{i}", cost=i,
            counts=dict(COUNTS, requests=i), trace_ctx=ctx)
    return [task(0), task(1)]


def _stream_task(ctx=None, n_windows=3):
    """Window ``i`` brings ``i`` fresh requests."""
    return executor.StreamTask(
        fn=lambda state, a: (state + 1, (a,)), pack=lambda: (0, None),
        windows=lambda c: (((np.full(2, i),), {"requests": i})
                           for i in range(n_windows)),
        consume=lambda out, c: None, finalize=lambda state, c: None,
        label="s", counts={k: v for k, v in COUNTS.items() if k != "requests"},
        trace_ctx=ctx)


def test_profiler_active_follows_the_session(tmp_path):
    assert not jax_compat.profiler_active()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert jax_compat.profiler_active()
    finally:
        jax.profiler.stop_trace()
    assert not jax_compat.profiler_active()


def test_nothing_recorded_or_annotated_outside_a_session(annotated):
    executor.execute(_group_tasks(), serial=False)
    _stream_task().run()
    assert spans.records() == [] and annotated == []
    assert not spans.span("emu.call") and spans.context() is None


@pytest.mark.parametrize("kind", ["group", "stream"])
def test_tasks_record_under_the_callers_call(recording, kind):
    with spans.span("emu.call") as top:
        ctx = spans.context()
        if kind == "group":
            executor.execute(_group_tasks(ctx), serial=False)
        else:
            _stream_task(ctx).run()
    recs = spans.records()
    assert sorted(recording) == sorted(r.name for r in recs)
    assert {r.call for r in recs} == {top.call}
    inner = [r for r in recs if r is not recs[-1]]
    assert recs[-1].name == "emu.call" and recs[-1].parent is None
    assert all(r.parent == top.id for r in inner)
    # another thread: the executor's worker, or the prefetch thread
    assert {r.thread for r in inner} - {threading.get_ident()}
    disp = [r.counts for r in recs if r.name == "emu.dispatch"]
    if kind == "group":
        assert {r.name for r in inner} == {
            "emu.pack", "emu.dispatch", "emu.wait", "emu.finalize"}
        assert sorted(c["requests"] for c in disp) == [0, 1]
    else:
        assert {r.name for r in inner} == {
            "emu.pack", "emu.assemble", "emu.prefetch_wait", "emu.dispatch",
            "emu.wait", "emu.consume", "emu.finalize"}
        assert disp == [dict(COUNTS, requests=i) for i in range(3)]
        assert all(r.thread != threading.get_ident() for r in recs
                   if r.name == "emu.assemble")


def test_a_task_without_a_caller_starts_its_own_call(recording):
    executor.execute(_group_tasks(), serial=False)
    by_call = {}
    for r in spans.records():
        by_call.setdefault(r.call, set()).add(r.name)
        assert r.parent is None
    assert len(by_call) == 2


def test_a_new_session_empties_the_log(annotated, monkeypatch):
    """The log outlives its session, for the readers, and the first
    span of the next session starts it afresh."""
    on = [True]
    monkeypatch.setattr(spans, "profiler_active", lambda: on[0])
    with spans.span("emu.call"):
        pass
    on[0] = False
    assert not spans.span("emu.call")
    assert [r.name for r in spans.records()] == ["emu.call"]
    on[0] = True
    with spans.span("emu.plan"):
        pass
    assert [r.name for r in spans.records()] == ["emu.plan"]


def test_log_is_bounded_and_threads_attach(recording, monkeypatch):
    """Past its capacity the log counts what it drops; a thread that
    attaches a captured context records under the caller's call id and
    span, one that attaches none starts a call of its own."""
    monkeypatch.setattr(spans, "CAPACITY", 4)
    with spans.span("emu.call") as top:
        ctx = spans.context()

        def worker(c):
            with spans.attach(c):
                with spans.span("emu.pack"):
                    pass

        for c in (ctx, None):
            th = threading.Thread(target=worker, args=(c,))
            th.start()
            th.join()
        for _ in range(3):
            with spans.span("emu.wait"):
                pass
    recs = spans.records()
    assert len(recs) == 4 and spans.dropped() == 2
    attached, alone = recs[0], recs[1]
    assert (attached.call, attached.parent) == (top.call, top.id)
    assert alone.call != top.call and alone.parent is None
    spans.clear()
    assert spans.records() == [] and spans.dropped() == 0


def test_concurrent_spans_lose_nothing(recording, monkeypatch):
    """Threads recording at once, with a short switch interval and a cap
    some of them run into: every span is kept or counted as dropped, and
    span ids stay unique."""
    import os
    import sys
    n_threads, n_spans = 4 * (os.cpu_count() or 1) + 4, 100
    cap = n_threads * n_spans // 2
    monkeypatch.setattr(spans, "CAPACITY", cap)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def worker():
            for _ in range(n_spans):
                with spans.span("emu.pack"):
                    pass

        threads = [threading.Thread(target=worker) for _ in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=30)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(old)
    recs = spans.records()
    assert len(recs) == cap
    assert len(recs) + spans.dropped() == n_threads * n_spans
    assert len({r.id for r in recs}) == len(recs)


@pytest.mark.parametrize("arms", ["none", "all", "mixed"])
def test_batched_dispatches_count_masked_requests(monkeypatch, arms):
    """Every batched dispatch counts the requests of its lanes whose
    weak-row filter mask is off (0 where no lane is masked), and every
    stream window counts 0. Planned only: nothing compiles."""
    from repro.core import emulator
    from repro.core.emulator import Trace
    from repro.core.timescale import JETSON_NANO
    monkeypatch.setattr(emulator._CachedRunner, "prime", lambda self: self)
    kinds = ([0, 1, 4, 0], [1, 4, 4, 4], [0] * 40, [1, 0, 1])
    trs = [Trace.of(kind=k, bank=np.zeros(len(k)), row=np.arange(len(k)),
                    delta=np.ones(len(k))) for k in kinds]
    bloom = (np.zeros(32, np.uint32), 2, 1024)
    blooms = {"none": None, "all": bloom,
              "mixed": [bloom, None, None, bloom]}[arms]
    tasks = emulator.prepare_tasks(trs, JETSON_NANO, "ts", blooms,
                                   [None] * len(trs))
    got = sorted((t.counts["requests"], t.counts["masked_requests"])
                 for t in tasks)
    # buckets 32 (traces 0, 1, 3) and 64 (trace 2)
    want = {"none": [(7, 0), (40, 0)], "all": [(7, 0), (40, 0)],
            "mixed": [(7, 1), (40, 40)]}[arms]
    assert got == want
    if arms != "mixed":
        (st,) = emulator.prepare_stream_tasks(trs, JETSON_NANO, "ts", blooms,
                                              [None] * len(trs))
        assert st.counts["masked_requests"] == 0
