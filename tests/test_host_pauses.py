"""Host pauses on the steps' clock (ISSUE 38): ``tracing.HOST_PAUSES``.

The contract: every garbage collection is counted by generation; one of a
millisecond or more is also a ``host_pause`` span of cause ``gc`` with its start
on ``perf_counter_ns`` and the thread it ran on; the profiler's own start and
stop are pauses of cause ``profiler``; the collector's callback writes no span
itself (a collection can begin while the span ring's lock is held) and builds a
``host.gc`` annotation only while a device trace runs.
"""

import gc
import threading
import time

import pytest

from dynamo_tpu import tracing

from test_step_phases import fake_annotation  # noqa: F401  (the fixture)


class Node:
    def __init__(self):
        self.me = self


def planted_cycles(n=300_000):
    """Garbage only the cyclic collector frees; it takes a full collection milliseconds."""
    return [Node() for _ in range(n)]


def pause_spans(cause=None):
    return [s for s in tracing.SPANS.query(request_id="host_pause") if cause in (None, s["cause"])]


def test_a_full_collection_is_one_gc_pause_on_the_steps_clock(host_pauses):
    tracing.install_host_pauses()
    heap = planted_cycles()
    gc.collect()  # what is older than the planted heap is not this test's
    before = list(host_pauses.gc_count)
    host_pauses.recent.clear(), host_pauses.pending.clear()
    del heap
    t0 = time.perf_counter_ns()
    collected = gc.collect()
    t1 = time.perf_counter_ns()
    assert collected >= 300_000
    assert pause_spans() == [] and len(host_pauses.pending) == 1  # the callback wrote nothing
    host_pauses.flush()
    (span,) = pause_spans("gc")
    assert span["name"] == "host_pause" and span["generation"] == 2 and span["collected"] == collected
    assert span["uncollectable"] == 0 and span["thread"] == threading.current_thread().name
    assert t0 <= span["t0_ns"] and span["t0_ns"] + span["duration_ms"] * 1e6 <= t1 + 1000
    assert span["duration_ms"] >= 1.0 and span["start_mono"] == pytest.approx(span["t0_ns"] / 1e9)
    assert abs(span["start_ts"] - time.time()) < 5.0
    assert list(host_pauses.recent) == [(span["t0_ns"], pytest.approx(span["duration_ms"] * 1e6, abs=1000), "gc", 2)]
    assert host_pauses.gc_count[2] == before[2] + 1 and host_pauses.gc_ns[2] >= span["duration_ms"] * 1e6 - 1000


def test_a_collection_under_the_floor_counts_and_leaves_no_span(host_pauses):
    tracing.install_host_pauses()
    gc.collect()
    host_pauses.recent.clear(), host_pauses.pending.clear()
    before, ns_before = list(host_pauses.gc_count), list(host_pauses.gc_ns)
    for _ in range(5):
        gc.collect(0)  # the youngest generation, nearly empty: microseconds
    assert host_pauses.gc_count[0] == before[0] + 5 and host_pauses.gc_count[1:] == before[1:]
    assert ns_before[0] < host_pauses.gc_ns[0] < ns_before[0] + 5 * tracing.GC_SPAN_FLOOR_NS
    assert not host_pauses.pending and not host_pauses.recent
    host_pauses.flush()
    assert pause_spans() == []


def test_install_twice_is_one_callback_and_uninstall_leaves_none(host_pauses):
    found = list(gc.callbacks)
    assert tracing.install_host_pauses() is host_pauses and tracing.install_host_pauses() is host_pauses
    assert gc.callbacks == found + [host_pauses._on_gc] and host_pauses.installed
    tracing.uninstall_host_pauses()
    tracing.uninstall_host_pauses()
    assert gc.callbacks == found and not host_pauses.installed
    gc.collect()
    assert host_pauses.gc_count == [0, 0, 0]


def test_the_first_engine_hooks_the_collector_once(host_pauses):
    from dynamo_tpu.mocker import build_mock_core

    found = list(gc.callbacks)
    a, b = build_mock_core(realtime=False), build_mock_core(realtime=False)
    assert a._host_pauses is b._host_pauses is host_pauses
    assert gc.callbacks == found + [host_pauses._on_gc]


@pytest.mark.parametrize("tracing_on", [False, True], ids=["no_trace", "trace_running"])
def test_host_gc_is_annotated_only_while_a_trace_runs(host_pauses, fake_annotation, monkeypatch, tracing_on):  # noqa: F811
    tracing.install_host_pauses()
    monkeypatch.setattr(tracing, "_annotating", tracing_on)
    gc.collect()
    log = [e for e in fake_annotation.log if e[1] == "host.gc"]
    assert log == ([("open", "host.gc"), ("close", "host.gc")] if tracing_on else [])
    assert not any(name.startswith("engine.") for _, name in fake_annotation.log)  # the reducer keeps those


class FakeProfiler:
    """Stands in for jax.profiler's session calls; each takes a while."""

    calls: list = []

    @classmethod
    def start_trace(cls, log_dir, profiler_options=None):
        cls.calls.append(("start", time.perf_counter_ns()))
        time.sleep(0.02)

    @classmethod
    def stop_trace(cls):
        cls.calls.append(("stop", time.perf_counter_ns()))
        time.sleep(0.03)


def test_the_profilers_start_and_stop_are_one_pause_each(host_pauses, monkeypatch, tmp_path):
    import jax

    FakeProfiler.calls = []
    monkeypatch.setattr(jax.profiler, "start_trace", FakeProfiler.start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", FakeProfiler.stop_trace)
    assert tracing.start_device_trace(str(tmp_path)) is True
    assert tracing.start_device_trace(str(tmp_path)) is False  # one at a time: no second pause
    assert host_pauses.profiler_since_ns == 0
    assert tracing.stop_device_trace() == str(tmp_path)
    assert tracing.stop_device_trace() is None
    spans = pause_spans("profiler")
    assert [s["what"] for s in spans] == ["start", "stop"] and len(pause_spans()) == 2
    for span, (what, called_ns), least_ms in zip(spans, FakeProfiler.calls, (20.0, 30.0)):
        assert span["t0_ns"] <= called_ns <= span["t0_ns"] + 2_000_000 and least_ms <= span["duration_ms"] < least_ms + 15
        assert "generation" not in span and span["thread"] == threading.current_thread().name
    assert [(c, g) for _, _, c, g in host_pauses.recent] == [("profiler", -1)] * 2
    # What GET /debug/traces/host_pause assembles from the ring.
    from dynamo_tpu.observability.service import assemble_timeline

    timeline = assemble_timeline("host_pause", tracing.SPANS.query(request_id="host_pause"))
    assert [s["what"] for s in timeline["spans"]] == ["start", "stop"]


def test_overlap_is_by_cause_with_the_oldest_generation_and_a_running_profiler_call(host_pauses):
    ms = 1_000_000
    host_pauses.note("gc", 10 * ms, 4 * ms, generation=1, collected=0, uncollectable=0)
    host_pauses.note("gc", 20 * ms, 10 * ms, generation=2, collected=0, uncollectable=0)
    host_pauses.note("profiler", 40 * ms, 5 * ms, what="start")
    assert host_pauses.overlap_ms(0, 100 * ms) == (14.0, 2, 5.0)
    assert host_pauses.overlap_ms(12 * ms, 22 * ms) == (4.0, 2, 0.0)  # 2 ms of the first, 2 of the second
    assert host_pauses.overlap_ms(12 * ms, 14 * ms) == (2.0, 1, 0.0)
    assert host_pauses.overlap_ms(50 * ms, 60 * ms) == (0.0, -1, 0.0)
    host_pauses.profiler_since_ns = 55 * ms  # a call that has not returned yet covers what follows its start
    assert host_pauses.overlap_ms(50 * ms, 60 * ms) == (0.0, -1, 5.0)
    for i in range(100):  # the last 64 are kept
        host_pauses.note("gc", (100 + i) * ms, ms, generation=0, collected=0, uncollectable=0)
    assert len(host_pauses.recent) == 64 and host_pauses.recent[0][0] == 136 * ms


def test_a_collection_begun_under_the_span_rings_lock_does_not_deadlock(host_pauses):
    """The callback may run on a thread that holds ``SPANS``' lock (any
    allocation can begin a collection): it must not take it."""
    tracing.install_host_pauses()
    heap = planted_cycles()
    gc.collect()
    host_pauses.pending.clear()
    del heap
    done = []

    def collect_under_the_lock():
        with tracing.SPANS._lock:
            done.append(gc.collect())

    worker = threading.Thread(target=collect_under_the_lock, daemon=True)
    worker.start()
    worker.join(timeout=30)
    assert not worker.is_alive() and done and done[0] >= 300_000
    assert len(host_pauses.pending) == 1 and host_pauses.pending[0][2]["thread"] == worker.name
