"""A first call says what it spent, where it happened (ISSUE 51).

The contract: ``timed_dispatch`` opens a collector only for a key its tracker
has not seen; while it is open, one pair of ``jax.monitoring`` listeners
(installed once a process) adds what JAX reports on that thread to it; the
tracker folds it into the event it already made, which also becomes a
``runner_first_call`` span under the trace of the worker's bring-up. The engine
charges a first call as a recompile only where a step made it.
"""

import threading

import jax
import jax.numpy as jnp
import pytest
from jax import monitoring

from dynamo_tpu import tracing
from dynamo_tpu.engine.core import EngineConfig, EngineCore
from dynamo_tpu.mocker import MockRunner
from dynamo_tpu.observability import compile as oc
from dynamo_tpu.observability.compile import CompileTracker, timed_dispatch
from dynamo_tpu.protocols.common import PreprocessedRequest, SamplingOptions, StopConditions

PARTS = ("trace_ms", "lower_ms", "backend_ms", "rest_ms")
BACKEND_EVENT = next(e for e, part in oc._PART_EVENTS.items() if part == oc.BACKEND)


@pytest.fixture
def spans(monkeypatch):
    ring = tracing.SpanBuffer(256)
    monkeypatch.setattr(tracing, "SPANS", ring)
    return ring


def fresh_program(scale: float):
    """A jitted function nothing has called: an inner jitted function and a few
    ``jnp`` calls, each of which JAX traces with an event of its own."""

    @jax.jit
    def inner(x):
        return jnp.sin(x) * scale

    @jax.jit
    def program(x):
        y = jnp.where(x > 0, inner(x), 0.0)
        return jnp.einsum("ij,jk->ik", y, y) + jnp.linalg.norm(y)

    return program


def first_calls(ring):
    return ring.query(request_id="runner_first_call")


def test_a_first_call_is_one_event_and_one_span_with_its_parts(spans):
    bring_up = tracing.TraceContext.new()
    tracker = CompileTracker(threshold_ms=0.0)
    tracker.trace = bring_up
    sunk = []
    tracker.bind_sink(lambda kind, **f: sunk.append((kind, f)))
    program, x = fresh_program(2.0), jnp.ones((8, 8))
    with timed_dispatch(tracker, "step", (8, 8), in_step=False) as timed:
        program(x).block_until_ready()
    (event,) = tracker.events()
    assert event["trace_ms"] > 0 and event["lower_ms"] > 0 and event["backend_ms"] > 0
    assert event["modules"] == 1 and event["in_step"] is False and event["cache"] in ("hit", "miss", "off")
    assert event["wall_ms"] == pytest.approx(timed.seconds * 1e3, abs=1e-3)
    assert sum(event[p] for p in PARTS) == pytest.approx(event["wall_ms"], abs=0.01) and event["rest_ms"] >= 0
    assert event["cache_hits"] + event["cache_misses"] == (0 if event["cache"] == "off" else 1)
    (span,) = first_calls(spans)
    assert span["name"] == "runner_first_call" and span["duration_ms"] == event["wall_ms"]
    assert (span["trace_id"], span["parent_id"]) == (bring_up.trace_id, bring_up.span_id)
    assert span["start_mono"] == pytest.approx(event["t0_ns"] / 1e9)
    assert {k: span[k] for k in event} == event  # every field of the event
    assert sunk == [("compile", event)]  # the flight ring's record, with the new keys


def test_a_seen_keys_dispatch_opens_no_collector_and_records_nothing(spans, monkeypatch):
    tracker = CompileTracker(threshold_ms=0.0)
    program, x = fresh_program(3.0), jnp.ones((8, 8))
    with timed_dispatch(tracker, "step", (8, 8)):
        program(x)
    opened = []
    monkeypatch.setattr(oc, "open_first_call", lambda: opened.append(1))
    monkeypatch.setattr(oc, "FirstCall", None)  # building one would raise
    other = fresh_program(4.0)  # compiles inside the block: nothing may listen
    with timed_dispatch(tracker, "step", (8, 8)) as timed:
        other(x).block_until_ready()
        assert oc._OPEN.call is None
    assert opened == [] and timed._call is None and timed.seconds > 0
    assert len(tracker.events()) == 1 and len(first_calls(spans)) == 1


def test_nested_traces_count_once():
    call = oc.FirstCall()
    real = oc.time.perf_counter
    try:
        now = [100.0]
        oc.time.perf_counter = lambda: now[0]
        # an inner jitted function traced 1 s (ends at 102), an eager
        # operation compiled 0.5 s (ends at 103), both inside an outer trace
        # of 4 s that ends at 104; then lowering 2 s and the backend 3 s.
        for at, part, seconds in ((102.0, oc.TRACE, 1.0), (103.0, oc.BACKEND, 0.5), (104.0, oc.TRACE, 4.0),
                                  (106.0, oc.LOWER, 2.0), (109.0, oc.BACKEND, 3.0)):
            now[0] = at
            call.add_part(part, seconds)
    finally:
        oc.time.perf_counter = real
    assert call.parts_s == [pytest.approx(3.5), pytest.approx(2.0), pytest.approx(3.5)] and call.modules == 2
    fields = call.fields(9500.0)
    assert (fields["trace_ms"], fields["lower_ms"], fields["backend_ms"], fields["rest_ms"]) == (3500.0, 2000.0, 3500.0, 500.0)
    assert call.fields(8000.0)["rest_ms"] == 0.0  # never negative


def test_a_compile_on_another_thread_adds_nothing_to_an_open_collector():
    tracker = CompileTracker(threshold_ms=0.0)
    program, x = fresh_program(5.0), jnp.ones((8, 8))

    def compile_elsewhere():
        assert oc._OPEN.call is None  # this thread has none open
        program(x).block_until_ready()

    with timed_dispatch(tracker, "step", (1,)):
        thread = threading.Thread(target=compile_elsewhere)
        thread.start()
        thread.join(timeout=60)
        assert not thread.is_alive()
    (event,) = tracker.events()
    assert (event["trace_ms"], event["lower_ms"], event["backend_ms"], event["modules"]) == (0.0, 0.0, 0.0, 0)
    assert event["cache"] == "none" and event["rest_ms"] == event["wall_ms"]


def test_the_listeners_are_installed_once_however_many_trackers_are_made():
    for _ in range(3):
        CompileTracker()
    assert oc.install_listeners() is False
    from jax._src import monitoring as jm

    assert jm.get_event_listeners().count(oc._on_event) == 1
    assert jm.get_event_duration_listeners().count(oc._on_duration) == 1


@pytest.mark.parametrize("cache,events", [
    ("hit", ["request", "hit", "backend"]),
    ("miss", ["request", "backend"]),
    ("miss", ["request", "hit", "backend", "request", "backend"]),  # two modules, one of them found
    ("off", ["backend"]),
    ("none", []),
])
def test_cache_says_what_jax_said(cache, events):
    CompileTracker()  # the listeners are in
    feed = {"request": lambda: monitoring.record_event(oc._CACHE_REQUEST_EVENT),
            "hit": lambda: (monitoring.record_event(oc._CACHE_HIT_EVENT),
                            monitoring.record_event_duration_secs(oc._CACHE_READ_EVENT, 0.25),
                            monitoring.record_event_duration_secs(oc._CACHE_SAVED_EVENT, 7.5)),
            "backend": lambda: monitoring.record_event_duration_secs(BACKEND_EVENT, 0.001, fun_name="jit(f)")}
    monitoring.record_event(oc._CACHE_HIT_EVENT)  # none open: heard by no one
    call = oc.open_first_call()
    try:
        for name in events:
            feed[name]()
    finally:
        oc.close_first_call()
    monitoring.record_event(oc._CACHE_HIT_EVENT)
    fields = call.fields(1000.0)
    hits = events.count("hit")
    assert fields["cache"] == cache and fields["modules"] == events.count("backend")
    assert (fields["cache_hits"], fields["cache_misses"]) == (hits, events.count("request") - hits)
    assert (fields["cache_read_ms"], fields["cache_saved_ms"]) == (250.0 * hits, 7500.0 * hits)


# -- the executable store's word (ISSUE 52) -------------------------------------------


def _stored_call(tmp_path, tracker, program, x):
    """One first call through a runner's table of kept programs, as ``_enqueue``
    makes it inside ``_dispatch``'s block; a new table each time: a new process."""
    from dynamo_tpu import executable_store as es

    programs = es.StepPrograms(es.ExecutableStore(str(tmp_path), "build"), "runner", jax.devices()[:1],
                               note=oc.note_store, cache_hits=oc.persistent_cache_hits)
    with timed_dispatch(tracker, "step", (8, 8), in_step=False):
        return programs.call(program, "step", (8, 8), (), (x,), {}).block_until_ready()


def test_a_store_miss_says_what_a_first_call_says_and_a_store_hit_traces_nothing(spans, tmp_path, fresh_compiles):
    program, x = fresh_program(6.0), jnp.ones((8, 8))
    cold, warm = CompileTracker(threshold_ms=0.0), CompileTracker(threshold_ms=0.0)
    want = _stored_call(tmp_path, cold, program, x)
    (miss,) = cold.events()
    assert miss["store"] == "miss" and 0 < miss["store_read_ms"] < miss["rest_ms"]
    assert miss["trace_ms"] > 0 and miss["lower_ms"] > 0 and miss["backend_ms"] > 0 and miss["modules"] == 1
    assert (miss["cache"], miss["cache_hits"], miss["cache_misses"]) == ("off", 0, 0)  # JAX's cache is off here
    assert sum(miss[p] for p in PARTS) == pytest.approx(miss["wall_ms"], abs=0.01)
    assert (_stored_call(tmp_path, warm, program, x) == want).all()
    (hit,) = warm.events()
    assert hit["store"] == "hit" and (hit["trace_ms"], hit["lower_ms"]) == (0.0, 0.0)
    assert hit["store_read_ms"] > 0 and hit["backend_ms"] == hit["store_read_ms"]  # the load is the backend's part
    assert (hit["cache"], hit["cache_hits"], hit["cache_misses"], hit["modules"]) == ("hit", 1, 0, 1)
    assert (hit["cache_read_ms"], hit["cache_saved_ms"]) == (0.0, 0.0)  # JAX's own cache was not asked
    assert sum(hit[p] for p in PARTS) == pytest.approx(hit["wall_ms"], abs=0.01) and hit["rest_ms"] >= 0
    assert [s["store"] for s in first_calls(spans)] == ["miss", "hit"]  # the spans carry the event's fields
    assert first_calls(spans)[1]["store_read_ms"] == hit["store_read_ms"]


def test_a_first_call_without_a_store_says_off(spans):
    tracker = CompileTracker(threshold_ms=0.0)
    with timed_dispatch(tracker, "step", (8, 8)):
        fresh_program(7.0)(jnp.ones((8, 8)))
    (event,) = tracker.events()
    assert (event["store"], event["store_read_ms"]) == ("off", 0.0)
    oc.note_store("hit", 1.0)  # no call open on this thread: heard by no one
    assert oc._OPEN.call is None


def test_the_threads_count_of_persistent_cache_hits_runs_with_no_call_open():
    CompileTracker()  # the listeners are in
    before = oc.persistent_cache_hits()
    monitoring.record_event(oc._CACHE_HIT_EVENT)
    heard = []
    thread = threading.Thread(target=lambda: heard.append(oc.persistent_cache_hits()))
    thread.start()
    thread.join(timeout=60)
    assert oc.persistent_cache_hits() == before + 1 and heard == [0]  # another thread's count is its own


def test_a_raise_inside_the_block_leaves_the_key_unseen_and_the_collector_closed(spans):
    tracker = CompileTracker(threshold_ms=0.0)
    with pytest.raises(ValueError):
        with timed_dispatch(tracker, "step", (1,)):
            assert oc._OPEN.call is not None
            raise ValueError("dispatch failed")
    assert oc._OPEN.call is None and not tracker.seen("step", (1,)) and tracker.total == 0
    assert first_calls(spans) == []
    with timed_dispatch(tracker, "step", (1,)):  # the next call of the key is its first
        pass
    assert tracker.total == 1 and len(first_calls(spans)) == 1


def test_an_event_without_the_runners_word_carries_no_in_step_key(spans):
    tracker = CompileTracker(threshold_ms=50.0)
    event = tracker.observe("step", (4, 1), 0.2)
    assert "in_step" not in event and "trace_ms" not in event
    (span,) = first_calls(spans)  # a planted event is a span all the same, ending now
    assert span["duration_ms"] == 200.0 and span["parent_id"] is None


# -- the engine charges only what a step paid ---------------------------------------


class FirstCallsInside(MockRunner):
    """A mock runner with the real runner's tracker and its rule for
    ``in_step``; its ``first_call_at``-th dispatch is a key nothing has seen."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.compile_tracker = CompileTracker(threshold_ms=0.0)
        self.clock = None
        self.dispatches, self.first_call_at = 0, 0

    def dispatch(self, key):
        in_step = self.clock is not None and self.clock.in_step
        with timed_dispatch(self.compile_tracker, "step", key, in_step=in_step):
            pass

    def step_async(self, batch, lp_k=0, **kw):
        self.dispatches += 1
        if self.dispatches == self.first_call_at:
            self.dispatch(("inside", self.dispatches))
        return super().step_async(batch, lp_k, **kw)


def test_first_calls_outside_a_step_are_no_recompiles_and_one_inside_a_step_is(spans):
    config = EngineConfig(num_pages=256, page_size=16, max_batch_size=8, max_seq_len=1024)
    runner = FirstCallsInside(num_pages=config.num_pages, page_size=config.page_size, realtime=False)
    core = EngineCore(runner, config)
    assert runner.clock is core.clock
    for i in range(12):  # a warm-up drives the runner outside any step
        runner.dispatch(("warm", i))
    runner.first_call_at = 5
    core.add_request(PreprocessedRequest(
        request_id="r0", token_ids=list(range(3, 19)), sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=8, ignore_eos=True)))
    charged = []
    while core.has_work:
        core.step()
        charged.append((core.recompile_count, core.lost_time_ms.get("recompile", 0.0)))
    events = runner.compile_tracker.events()
    assert [e["in_step"] for e in events] == [False] * 12 + [True] and all(e["reason"] == "new_shape" for e in events)
    # The engine's first steps are charged nothing for the warm-up's twelve ...
    assert charged[0] == (0, 0.0) and charged[3] == (0, 0.0)
    # ... and the step that made a first call itself is charged that one.
    assert charged[-1][0] == 1 and charged[-1][1] == pytest.approx(events[-1]["wall_ms"])
    assert "recompile_storm" not in core.sentinel.active


# -- a worker's bring-up is one timeline -------------------------------------------


async def test_a_workers_bring_up_is_one_trace_with_its_first_calls(spans):
    from benchmark.serving import null_batch
    from dynamo_tpu import launch
    from dynamo_tpu.runtime.component import DistributedRuntime

    spec = launch.WorkerSpec.from_preset("test-tiny", num_pages=16, page_size=4, max_batch_size=2, max_seq_len=32)
    runtime = DistributedRuntime.detached()
    service = await launch.serve_worker(runtime, spec)
    try:
        (root,) = [s for s in spans.query(request_id="worker_bring_up") if s["name"] == "worker_bring_up"]
        timeline = spans.query(trace_id=root["trace_id"])
        by_name = {s["name"]: s for s in timeline}
        assert list(by_name) == ["worker_params", "runner_init", "worker_register", "worker_bring_up"]
        assert root["name"] == "worker_bring_up" and root["parent_id"] is None
        assert (root["model"], root["worker"]) == ("test-tiny", f"{service.instance.lease_id:x}")
        children = [by_name[n] for n in ("worker_params", "runner_init", "worker_register")]
        assert all(c["parent_id"] == root["span_id"] and c["request_id"] == "worker_bring_up" for c in children)
        runner = service.core.runner
        assert (by_name["worker_params"]["source"], by_name["worker_params"]["bytes"]) == ("init", launch._tree_bytes(runner.params))
        assert by_name["runner_init"]["kv_pool_bytes"] == runner.cache_memory_bytes() and "state_bytes" not in by_name["runner_init"]
        # the children lie inside the root, in the order the worker came up
        starts = [c["start_mono"] for c in children]
        assert starts == sorted(starts) and starts[0] >= root["start_mono"]
        assert sum(c["duration_ms"] for c in children) <= root["duration_ms"]
        # a first call, whoever makes it, lands under the same trace as a child of the root
        runner.step(null_batch(1, 1, 1))
        (call,) = first_calls(spans)
        assert (call["trace_id"], call["parent_id"], call["in_step"]) == (root["trace_id"], root["span_id"], False)
        assert call["trace_ms"] > 0 and call["modules"] >= 1
        assert service.core.flight.snapshot(kind="compile")[-1]["trace_ms"] == call["trace_ms"]
    finally:
        await service.close()
        await runtime.close()


@pytest.mark.parametrize("kind", ["plain", "two_pools", "slots"])
def test_runner_init_says_what_the_runner_allocated_by_kind(kind):
    import dataclasses

    from dynamo_tpu.engine.runner import ModelRunner
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import FULL, PRESETS, SLIDING

    cfg = PRESETS["test-tiny-hybrid" if kind == "slots" else "test-tiny"]
    if kind == "two_pools":  # one full layer beside one sliding layer: a pool each, the window's derived
        cfg = dataclasses.replace(cfg, layer_types=(SLIDING, FULL), sliding_window=8)
    runner = ModelRunner(cfg, llama.init_params(cfg, 0), num_pages=32, page_size=4, max_batch_size=2,
                         attn_impl="reference", window_chunk=8 if kind == "two_pools" else None)
    got = runner.memory_bytes_by_kind()
    kv = runner.k_cache.nbytes + runner.v_cache.nbytes
    assert got["kv_pool_bytes"] == kv and sum(v for k, v in got.items() if k != "window_pool_bytes") == runner.cache_memory_bytes()
    assert set(got) == {"plain": {"kv_pool_bytes"}, "two_pools": {"kv_pool_bytes", "window_pool_bytes"},
                        "slots": {"kv_pool_bytes", "state_bytes"}}[kind]
    if kind == "two_pools":  # [1, 32 full pages + the window's, page, width]: the window's pages over all of them
        assert 0 < runner.window_pages < 32 and runner.k_cache.shape[1] == 32 + runner.window_pages
        assert got["window_pool_bytes"] == kv * runner.window_pages // (32 + runner.window_pages)
