"""Unit tests for the goodput/SLO observability plane (ISSUE 4).

Covers the pieces below the full-stack test in test_observability.py:
the flight-recorder ring (ordering, wrap, filters, JSONL crash dumps),
compile-tracker determinism (one event per bucket, warn-once storms),
EngineCore step/crash records on the mock runner, the P^2 streaming
quantile estimators, SLO accounting, and trace-id log injection.
"""

import json
import logging

import pytest

from dynamo_tpu.config import SloSettings, load_slo_settings
from dynamo_tpu.mocker import build_mock_core
from dynamo_tpu.observability.compile import (
    REASON_NEW_SHAPE,
    REASON_WARM_CACHE,
    CompileTracker,
    timed_dispatch,
)
from dynamo_tpu.observability.flight import CRASH, STEP, FlightRecorder
from dynamo_tpu.observability.slo import (
    SloAccountant,
    StreamingQuantile,
    StreamingQuantiles,
    percentile,
)
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime.logging import TraceContextFilter
from dynamo_tpu.tracing import Span


# -- flight recorder ring ----------------------------------------------------


def test_flight_ring_orders_and_wraps():
    ring = FlightRecorder(capacity=4)
    for i in range(10):
        ring.record(STEP, i=i)
    records = ring.snapshot()
    assert len(records) == 4
    # seq is globally monotonic, so a wrap shows as a gap from 0.
    assert [r["seq"] for r in records] == [6, 7, 8, 9]
    assert [r["i"] for r in records] == [6, 7, 8, 9]
    assert all(r["kind"] == STEP and "ts" in r for r in records)


def test_flight_snapshot_filters():
    ring = FlightRecorder(capacity=16)
    for i in range(6):
        ring.record(STEP, i=i)
    ring.record(CRASH, error="Boom")
    assert len(ring.snapshot(kind=CRASH)) == 1
    steps = ring.snapshot(kind=STEP, last=2)
    assert [r["i"] for r in steps] == [4, 5]
    assert len(ring.snapshot(last=3)) == 3
    ring.clear()
    assert len(ring) == 0


def test_flight_dump_jsonl_explicit_path(tmp_path):
    ring = FlightRecorder(capacity=8)
    ring.record(STEP, decode_rows=2)
    ring.record(CRASH, error="RuntimeError", detail="boom")
    path = ring.dump_jsonl(str(tmp_path / "dump.jsonl"), reason="engine_step_failure")
    lines = [json.loads(line) for line in open(path)]
    assert lines[0]["kind"] == "dump_header"
    assert lines[0]["reason"] == "engine_step_failure"
    assert lines[0]["records"] == 2
    assert [l["kind"] for l in lines[1:]] == [STEP, CRASH]
    assert lines[2]["error"] == "RuntimeError"


def test_flight_dump_default_dir_env(tmp_path, monkeypatch):
    monkeypatch.setenv("DYN_FLIGHT_DUMP_DIR", str(tmp_path / "dumps"))
    ring = FlightRecorder(capacity=8)
    ring.record(STEP)
    path = ring.dump_jsonl()
    assert path.startswith(str(tmp_path / "dumps"))
    assert len(open(path).readlines()) == 2  # header + 1 record


def test_flight_capacity_env(monkeypatch):
    monkeypatch.setenv("DYN_FLIGHT_BUFFER", "3")
    ring = FlightRecorder()
    for i in range(5):
        ring.record(STEP, i=i)
    assert len(ring) == 3


# -- compile tracker ---------------------------------------------------------


def test_compile_tracker_one_event_per_bucket():
    sink_events = []
    tracker = CompileTracker(threshold_ms=50.0)
    tracker.bind_sink(lambda kind, **f: sink_events.append((kind, f)))
    key = (8, 16, 4, 0, "reference")

    first = tracker.observe("step", key, 0.2)  # 200 ms: a real compile
    assert first is not None
    assert first["reason"] == REASON_NEW_SHAPE
    assert first["bucket"] == list(key)
    # Re-hit of the same bucket: deterministic zero events, regardless of time.
    for _ in range(5):
        assert tracker.observe("step", key, 0.3) is None
    # Same bucket under a different program is a distinct compile.
    assert tracker.observe("multi_step", key, 0.001)["reason"] == REASON_WARM_CACHE

    assert tracker.counts() == {
        ("step", REASON_NEW_SHAPE): 1,
        ("multi_step", REASON_WARM_CACHE): 1,
    }
    assert tracker.total == 2
    assert len(tracker.events()) == 2
    assert [k for k, _ in sink_events] == ["compile", "compile"]


def test_timed_dispatch_noop_and_exception_paths():
    # None tracker: pure no-op, call sites need no branching.
    with timed_dispatch(None, "step", (1,)):
        pass
    tracker = CompileTracker(threshold_ms=50.0)
    with pytest.raises(ValueError):
        with timed_dispatch(tracker, "step", (1,)):
            raise ValueError("dispatch failed")
    # A failed dispatch is not a first execution: the bucket stays unseen.
    assert tracker.total == 0
    with timed_dispatch(tracker, "step", (1,)):
        pass
    assert tracker.total == 1


# -- EngineCore integration (mock runner) ------------------------------------


def _greedy_req(prompt, max_tokens=4):
    return PreprocessedRequest(
        token_ids=list(prompt),
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=max_tokens),
    )


def test_engine_core_records_step_flight():
    core = build_mock_core(realtime=False)
    core.add_request(_greedy_req([1, 2, 3, 4, 5], max_tokens=4))
    core.add_request(_greedy_req([7, 8, 9], max_tokens=4))
    for _ in range(64):
        if not core.has_work:
            break
        core.step()
    records = core.flight.snapshot(kind=STEP)
    assert records, "engine steps produced no flight records"
    seqs = [r["seq"] for r in records]
    assert seqs == sorted(seqs)
    for r in records:
        for key in ("step_kind", "decode_rows", "chunk_rows", "chunk_tokens",
                    "free_pages", "waiting", "running", "wall_ms", "preemptions"):
            assert key in r, r
        assert r["step_kind"] in ("mixed", "prefill", "decode", "drain")
    # The mock fleet prefilled then decoded: both compositions appear.
    kinds = {r["step_kind"] for r in records}
    assert kinds & {"mixed", "prefill"}
    assert "decode" in kinds


def test_engine_core_crash_record_and_dump(tmp_path, monkeypatch):
    core = build_mock_core(realtime=False)
    core.add_request(_greedy_req([1, 2, 3], max_tokens=4))
    core.step()  # one healthy step so the dump has context before the crash

    def boom():
        raise RuntimeError("device array poisoned")

    monkeypatch.setattr(core, "_step_locked", boom)
    with pytest.raises(RuntimeError, match="device array poisoned"):
        core.step()

    crashes = core.flight.snapshot(kind=CRASH)
    assert len(crashes) == 1
    assert crashes[0]["error"] == "RuntimeError"
    assert "device array poisoned" in crashes[0]["detail"]
    assert "free_pages" in crashes[0]

    # The crash dump (what engine/service.py writes on loop death) carries
    # both the healthy context and the crash record.
    path = core.flight.dump_jsonl(str(tmp_path / "crash.jsonl"), reason="engine_step_failure")
    lines = [json.loads(line) for line in open(path)]
    assert lines[0]["reason"] == "engine_step_failure"
    kinds = [l["kind"] for l in lines[1:]]
    assert STEP in kinds and CRASH in kinds
    assert kinds[-1] == CRASH  # ordered: the crash is the last thing recorded


# -- P^2 streaming quantiles -------------------------------------------------


def test_percentile_nearest_rank():
    assert percentile([], 0.5) == 0.0
    assert percentile([3.0], 0.99) == 3.0
    xs = [float(i) for i in range(100)]
    assert percentile(xs, 0.5) == 50.0
    assert percentile(xs, 0.99) == 99.0


def test_streaming_quantile_exact_under_five_samples():
    est = StreamingQuantile(0.5)
    for x in (5.0, 1.0, 3.0):
        est.observe(x)
    assert est.value() == 3.0
    assert StreamingQuantile(0.5).value() == 0.0
    with pytest.raises(ValueError):
        StreamingQuantile(1.0)


def test_streaming_quantile_tracks_known_distribution():
    import random

    rng = random.Random(42)
    xs = [rng.random() for _ in range(10000)]
    bundle = StreamingQuantiles()
    for x in xs:
        bundle.observe(x)
    xs.sort()
    for q in (0.5, 0.95, 0.99):
        exact = percentile(xs, q)
        assert bundle.get(q) == pytest.approx(exact, abs=0.02), q
    assert bundle.count == 10000
    snap = bundle.snapshot()
    assert set(snap) == {0.5, 0.95, 0.99}
    assert snap[0.5] <= snap[0.95] <= snap[0.99]


def test_streaming_quantile_shifted_distribution():
    # The fixed-bucket failure mode: all mass near the 500 ms SLO boundary.
    est = StreamingQuantile(0.5)
    for i in range(1000):
        est.observe(0.49 + (i % 100) * 0.0002)  # 490..510 ms
    assert 0.49 <= est.value() <= 0.51


# -- SLO accounting ----------------------------------------------------------


def test_slo_accountant_goodput_ledger():
    acct = SloAccountant(SloSettings(ttft_ms=100.0, itl_p99_ms=20.0))
    # Attains: fast TTFT, tight gaps.
    v = acct.account(ttft_s=0.05, itl_gaps=[0.01] * 5, output_tokens=10, ok=True)
    assert v.met and v.ttft_ok and v.itl_ok
    # TTFT blown: tokens counted, goodput not.
    v = acct.account(ttft_s=0.2, itl_gaps=[0.01], output_tokens=20, ok=True)
    assert not v.met and not v.ttft_ok and v.itl_ok
    # ITL p99 blown.
    v = acct.account(ttft_s=0.05, itl_gaps=[0.01] * 9 + [0.5], output_tokens=5, ok=True)
    assert not v.met and v.ttft_ok and not v.itl_ok
    # Fast but failed: never goodput.
    acct.account(ttft_s=0.01, itl_gaps=[], output_tokens=7, ok=False)
    assert acct.output_tokens_total == 42
    assert acct.goodput_tokens_total == 10
    assert acct.attainment() == pytest.approx(0.25)
    snap = acct.snapshot()
    assert snap["goodput_tokens_total"] == 10
    assert snap["output_tokens_total"] == 42
    assert snap["targets"] == {"ttft_ms": 100.0, "itl_p99_ms": 20.0}


def test_slo_accountant_vacuous_itl_and_empty_state():
    acct = SloAccountant(SloSettings(ttft_ms=100.0, itl_p99_ms=20.0))
    assert acct.attainment() == 1.0  # no requests yet: vacuously attaining
    # A 1-token response has no gaps; its ITL attains by definition.
    assert acct.classify(0.05, []).met


def test_slo_settings_env_override(monkeypatch):
    assert load_slo_settings().ttft_ms == 500.0  # north-star default
    monkeypatch.setenv("DYN_SLO_TTFT_MS", "250")
    monkeypatch.setenv("DYN_SLO_ITL_P99_MS", "25")
    settings = load_slo_settings()
    assert settings.ttft_ms == 250.0
    assert settings.itl_p99_ms == 25.0


# -- trace-id log injection --------------------------------------------------


def _make_record():
    return logging.LogRecord("t", logging.INFO, __file__, 1, "msg", (), None)


def test_trace_context_filter_stamps_active_span():
    f = TraceContextFilter()
    outside = _make_record()
    assert f.filter(outside) is True
    assert not hasattr(outside, "trace_id")  # no span open: record untouched
    with Span("frontend.request") as span:
        inside = _make_record()
        assert f.filter(inside) is True
        assert inside.trace_id == span.trace_id
        assert inside.span_id == span.span_id
    after = _make_record()
    f.filter(after)
    assert not hasattr(after, "trace_id")


def test_trace_context_filter_keeps_explicit_trace_id():
    f = TraceContextFilter()
    with Span("frontend.request"):
        rec = _make_record()
        rec.trace_id = "explicit"
        f.filter(rec)
        assert rec.trace_id == "explicit"
