"""Long steps named where the step is recorded (ISSUE 38).

The contract: a step's period is the eleven phases its STEP record holds less
``no_work``; per step kind and bucket of decode rows the sentinel keeps an
expected period (the mean of the first 32 steps, then an exponential mean at
1/32 over the steps that were not long); a step is long when its period is over
five times that and at least 10 ms over it. A long step, and no other, is one
``engine_long_step`` span with what was lost, the phase that holds most of it
and a cause: ``profiler`` or ``gc`` where the kept host pauses cover half of
what was lost, ``compile`` where the step met a new shape, else none. The STEP
record's keys and the tokens do not change.
"""

from types import SimpleNamespace

import pytest

from dynamo_tpu import tracing
from dynamo_tpu.engine.core import EngineConfig, EngineCore
from dynamo_tpu.mocker import MockRunner
from dynamo_tpu.observability import anomaly
from dynamo_tpu.observability.anomaly import AnomalySentinel
from dynamo_tpu.protocols.common import PreprocessedRequest, SamplingOptions, StopConditions

STEP_MS = 8.0


class VirtualTime:
    """Stands in for the ``time`` module inside ``dynamo_tpu.tracing``: the
    steps' clock moves only when the runner says so, so a period is what the
    test made it and no loaded host adds a long step of its own."""

    def __init__(self):
        self.ns = 5_000_000_000_000

    def perf_counter_ns(self):
        return self.ns

    def perf_counter(self):
        return self.ns / 1e9

    def time(self):
        return 1_700_000_000.0 + self.ns / 1e9

    def sleep(self, seconds):
        self.ns += int(seconds * 1e9)


@pytest.fixture
def clock(monkeypatch, host_pauses):
    virtual = VirtualTime()
    monkeypatch.setattr(tracing, "time", virtual)
    return virtual


class SlowOnce(MockRunner):
    """A program takes ``STEP_MS`` on the steps' clock; once, ``at`` dispatches
    in, ``ms`` more: inside the dispatch itself (the engine's ``dispatch``
    phase) or in the read-back (its ``wait``)."""

    def __init__(self, *, clock, at, where, ms=100.0, on_slow=None, **kw):
        super().__init__(**kw)
        self.vtime, self.at, self.where, self.ms, self.on_slow = clock, at, where, ms, on_slow
        self.dispatches = 0

    def step_async(self, batch, lp_k=0, **kw):
        self.dispatches += 1
        slow = self.dispatches == self.at
        if slow and self.where == "dispatch":
            self.pause()
        handle = super().step_async(batch, lp_k, **kw)
        result = handle.result

        def read():  # the pipelined loop reads this program's tokens in its next step
            self.vtime.sleep(STEP_MS / 1e3)
            if slow and self.where == "wait":
                self.pause()
            return result()

        handle.result = read
        return handle

    def pause(self):
        if self.on_slow is not None:
            self.on_slow()
        self.vtime.sleep(self.ms / 1e3)


def make_core(clock, **runner_kw):
    config = EngineConfig(num_pages=256, page_size=16, max_batch_size=8, max_seq_len=1024)
    runner_kw.setdefault("at", 0)
    runner_kw.setdefault("where", "wait")
    runner = SlowOnce(clock=clock, num_pages=config.num_pages, page_size=config.page_size, realtime=False, **runner_kw)
    core = EngineCore(runner, config)
    tracing.uninstall_host_pauses()  # the test host's own collections are not this test's
    return core


def drive(core, out=60, between=None):
    req = PreprocessedRequest(request_id="r0", token_ids=list(range(3, 19)), sampling=SamplingOptions(temperature=0.0),
                              stop=StopConditions(max_tokens=out, ignore_eos=True))
    core.add_request(req)
    tokens, n = [], 0
    while core.has_work:
        for _, o in core.step():
            tokens.extend(o.token_ids)
        n += 1
        if between is not None:
            between(n)
    return tokens, core.flight.snapshot(kind="step")


def long_spans():
    return tracing.SPANS.query(request_id="engine_long_step")


@pytest.mark.parametrize("where", ["wait", "dispatch"])
def test_one_slow_step_is_one_long_step_with_the_phase_slept_in(clock, where):
    core = make_core(clock, at=42, where=where)
    _, steps = drive(core)
    (span,) = long_spans()
    assert span["name"] == "engine_long_step" and span["phase"] == where and span["cause"] == ""
    assert span["lost_ms"] == pytest.approx(100.0, abs=20.0) and span["lost_ms"] == pytest.approx(100.0, abs=0.01)
    assert span["expected_ms"] == pytest.approx(STEP_MS) and span["duration_ms"] == pytest.approx(STEP_MS + 100.0)
    assert span["phase_ms"] == pytest.approx(100.0 + STEP_MS * (where == "wait"))
    assert (span["gc_ms"], span["gc_generation"], span["profiler_ms"], span["traced"]) == (0.0, -1, 0.0, False)
    record = next(r for r in steps if r["seq"] == span["seq"])  # the step's own STEP record
    assert (span["step_kind"], span["decode_rows"], span["t0_ns"]) == ("decode", 1, record["t0_ns"])
    assert record["phases_us"][where] / 1e3 == pytest.approx(span["phase_ms"]) and record["wall_ms"] == STEP_MS + 100.0
    # The span starts where the period does (the tail of the step before, the gap, then the step)
    # and ends where the step's ``record`` phase begins.
    assert span["start_mono"] == pytest.approx(record["t0_ns"] / 1e9) and span["start_ts"] == pytest.approx(
        clock.time() - (clock.ns - record["t0_ns"]) / 1e9)
    assert core.long_steps == {"": 1} and core.long_step_lost_ms == {"": pytest.approx(100.0)}


def test_no_step_is_long_before_its_kind_is_armed(clock):
    core = make_core(clock, at=12, where="wait")  # the 12th dispatch: fewer than 32 decode steps came before
    _, steps = drive(core)
    assert max(r["wall_ms"] for r in steps) == STEP_MS + 100.0 and long_spans() == [] and core.long_steps == {}


def test_waiting_for_a_request_is_no_long_step(clock):
    core = make_core(clock)

    def idle(n):
        if n == 45:  # what the service's loop marks while it has nothing to run
            core.clock.mark(tracing.NO_WORK)
            clock.sleep(2.0)
            core.clock.mark(tracing.SUBMIT)
        if n == 50:  # the same two seconds in ``handoff``: the loop did not come back
            clock.sleep(2.0)

    _, steps = drive(core, between=idle)
    assert sorted(r["gap_ms"] for r in steps)[-2:] == [2000.0, 2000.0]
    assert max(r["phases_us"]["no_work"] for r in steps) == 2e9 / 1e3
    (span,) = long_spans()
    assert (span["phase"], span["phase_ms"], span["lost_ms"]) == ("handoff", 2000.0, pytest.approx(2000.0))


@pytest.mark.parametrize("cause", ["gc", "profiler", "profiler_running", "compile", "short_gc"])
def test_a_long_step_takes_the_cause_of_what_lies_inside_its_period(clock, host_pauses, cause):
    events = []

    def on_slow():
        now, ms = clock.ns, 1_000_000
        if cause == "gc":
            host_pauses.note("gc", now + 5 * ms, 90 * ms, generation=2, collected=7, uncollectable=0)
            host_pauses.note("gc", now + 96 * ms, 2 * ms, generation=1, collected=0, uncollectable=0)
            host_pauses.note("gc", now - 500 * ms, 100 * ms, generation=2, collected=0, uncollectable=0)  # long before
        elif cause == "short_gc":  # under half of what was lost: not the cause
            host_pauses.note("gc", now + 5 * ms, 30 * ms, generation=2, collected=7, uncollectable=0)
        elif cause == "profiler":  # first in line where both cover half
            host_pauses.note("profiler", now, 80 * ms, what="start")
            host_pauses.note("gc", now + 5 * ms, 90 * ms, generation=2, collected=7, uncollectable=0)
        elif cause == "profiler_running":  # stop_trace takes seconds: it has not returned when the step ends
            host_pauses.profiler_since_ns = now
        else:
            events.append({"reason": "new_shape", "wall_ms": 100.0})

    core = make_core(clock, at=42, where="wait", on_slow=on_slow)
    core._compile_tracker = SimpleNamespace(events=lambda: list(events))
    drive(core)
    host_pauses.profiler_since_ns = 0
    (span,) = long_spans()
    want = {"gc": "gc", "short_gc": "", "profiler": "profiler", "profiler_running": "profiler", "compile": "compile"}[cause]
    assert span["cause"] == want and core.long_steps == {want: 1} and span["lost_ms"] == pytest.approx(100.0)
    got = (span["gc_ms"], span["gc_generation"], span["profiler_ms"])
    assert got == {"gc": (92.0, 2, 0.0), "short_gc": (30.0, 2, 0.0), "profiler": (90.0, 2, 80.0),
                   "profiler_running": (0.0, -1, 100.0), "compile": (0.0, -1, 0.0)}[cause]
    # The engine's step wrote the planted pauses' spans: nobody else comes by to do it.
    assert not host_pauses.pending
    assert len(tracing.SPANS.query(request_id="host_pause")) == {"gc": 3, "short_gc": 1, "profiler": 2}.get(cause, 0)


def test_a_long_step_does_not_move_the_expected_period(clock):
    core = make_core(clock, at=42, where="wait")
    seen = []
    drive(core, between=lambda n: seen.append(list(core.sentinel._periods.get(("decode", 1), [0, 0.0, 0]))))
    (span,) = long_spans()
    armed = [state for state in seen if state[0] == 32]
    assert len(armed) >= 20 and all(state[1] == pytest.approx(STEP_MS) for state in armed)  # before it and after
    assert [state[2] for state in armed].count(1) == 1  # the one step that folded nothing


def test_the_step_record_keeps_its_keys_and_the_tokens_are_those_of_a_run_without(clock):
    from dynamo_tpu.observability.flight import STEP_KEYS

    with_long, steps = drive(make_core(clock, at=42, where="wait"))
    assert len(long_spans()) == 1 and all(tuple(r) == STEP_KEYS for r in steps) and len(STEP_KEYS) == 52  # 44 + `moe_extra_passes` (ISSUE 39) + `state_rows`, `state_slots_live` (ISSUE 40) + the three pool keys (ISSUE 42) + `router_select` (ISSUE 43) + `moe_pad_positions` (ISSUE 50)
    assert not {"lost_ms", "expected_ms", "period_ms", "cause"} & set(STEP_KEYS)
    core = make_core(clock)
    core.sentinel.observe_period = lambda *a: 0.0  # no detector
    without, _ = drive(core)
    assert with_long == without and len(with_long) == 60 and len(long_spans()) == 1


def test_the_sentinel_tells_steps_apart_by_kind_and_rows_bucket_and_arms_anew_after_a_new_regime():
    s = AnomalySentinel()
    assert (anomaly.LONG_STEP_RATIO, anomaly.LONG_STEP_FLOOR_MS, anomaly.LONG_STEP_ARM) == (5.0, 10.0, 32)
    for _ in range(32):
        assert s.observe_period("decode", 2, 3.0) == 0.0  # arming: the plain mean
    assert s._periods[("decode", 2)][:2] == [32, pytest.approx(3.0)]
    assert s.observe_period("decode", 3, 14.9) == 0.0  # under five times; the same bucket as 2 rows
    assert s._periods[("decode", 2)][1] == pytest.approx(3.0 + 11.9 / 32)
    s._periods[("decode", 2)][1] = 3.0
    assert s.observe_period("decode", 2, 12.9) == 0.0  # over five times but not 10 ms over
    s._periods[("decode", 2)][1] = 3.0
    assert s.observe_period("decode", 2, 16.0) == 3.0 and s._periods[("decode", 2)] == [32, 3.0, 1]
    assert s.observe_period("decode", 48, 17.0) == 0.0 and s.observe_period("mixed", 2, 23.0) == 0.0  # other kinds
    assert s.observe_period("decode", 2, 3.0) == 0.0 and s._periods[("decode", 2)][2] == 0  # back to normal
    # 32 long steps in a row are what this kind of step now takes: it arms anew and then follows.
    assert [s.observe_period("decode", 2, 40.0) for _ in range(32)] == [3.0] * 32
    assert s._periods[("decode", 2)] == [0, 0.0, 0]
    assert [s.observe_period("decode", 2, 40.0) for _ in range(33)] == [0.0] * 33
    assert s.observe_period("decode", 2, 300.0) == pytest.approx(40.0)


async def test_long_steps_and_collections_reach_the_metrics_plane(clock, host_pauses):
    from dynamo_tpu.observability.metrics import EngineMetrics

    core = make_core(clock, at=42, where="wait",
                     on_slow=lambda: host_pauses.note("gc", clock.ns, 95_000_000, generation=2, collected=1, uncollectable=0))
    metrics = EngineMetrics(worker="w1").bind_core(core)
    drive(core)
    host_pauses.gc_count[2] += 3
    host_pauses.gc_ns[2] += 250_000_000
    text = (await metrics.render()).decode()
    assert 'dynamo_engine_long_steps_total{cause="gc",worker="w1"} 1.0' in text
    lost = float(next(l for l in text.splitlines()
                      if l.startswith('dynamo_engine_long_step_lost_seconds_total{cause="gc"')).split()[-1])
    assert lost == pytest.approx(0.1)
    assert 'dynamo_host_gc_pauses_total{generation="2",worker="w1"} 3.0' in text
    assert 'dynamo_host_gc_pause_seconds_total{generation="2",worker="w1"} 0.25' in text
    assert 'dynamo_host_gc_pauses_total{generation="0",worker="w1"}' in text
    text = (await metrics.render()).decode()  # a second scrape adds nothing: the counters are delta-synced
    assert 'dynamo_engine_long_steps_total{cause="gc",worker="w1"} 1.0' in text
    assert 'dynamo_host_gc_pauses_total{generation="2",worker="w1"} 3.0' in text
