"""Device-cost plane (ISSUE 19): roofline ledger + on-demand profiler capture.

Covers the CostRegistry's two sourcing paths (background XLA extraction on
CPU, model-derived estimate fallback), the multi-step iteration scaling,
roofline math and bound classification, the metrics Counter monotonicity,
the worker/frontend HTTP surfaces (including the profiler-unavailable and
single-flight refusals), the control-tower panel, the engine-core flight
join on the mock runner, and the DYN_COST_PLANE=0 acceptance: bit-identical
tokens with zero extraction work (spied via the module global EXTRACTIONS).
"""

import functools
import os

import aiohttp
import numpy as np
import pytest

from dynamo_tpu.observability import cost as cost_mod
from dynamo_tpu.observability.cost import (
    CostRegistry,
    chip_peaks,
    cost_plane_enabled,
    decode_step_estimate,
    make_lower_thunk,
    weight_stream_bytes,
)
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime.engine import Context


@pytest.fixture(autouse=True)
def _cost_plane_on(monkeypatch):
    """conftest defaults DYN_COST_PLANE=0 so background extraction stays out
    of the rest of the suite; these tests exercise the plane itself, so flip
    it back on (individual tests re-override where they test the off path)."""
    monkeypatch.setenv("DYN_COST_PLANE", "1")


def _greedy_req(prompt, max_tokens=4, ignore_eos=True):
    return PreprocessedRequest(
        token_ids=list(prompt),
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=max_tokens, ignore_eos=ignore_eos),
    )


# -- peaks --------------------------------------------------------------------


def test_chip_peaks_env_override(monkeypatch):
    monkeypatch.setenv("DYN_PEAK_HBM_GBPS", "819")
    monkeypatch.setenv("DYN_PEAK_TFLOPS", "197")
    hbm, tflops, source = chip_peaks()
    assert (hbm, tflops, source) == (819.0, 197.0, "env")


def test_chip_peaks_cpu_proxy(monkeypatch):
    monkeypatch.delenv("DYN_PEAK_HBM_GBPS", raising=False)
    monkeypatch.delenv("DYN_PEAK_TFLOPS", raising=False)
    hbm, tflops, source = chip_peaks()
    # The test mesh is virtual CPU devices: DDR-class proxies, labelled so.
    assert (hbm, tflops) == cost_mod.CPU_PROXY_PEAKS
    assert source.startswith("cpu-proxy:")


@pytest.mark.parametrize("kind,expect", [("TPU v5 lite", (819.0, 197.0)), ("TPU v9x", None)])
def test_chip_peaks_accelerator_table_or_error(monkeypatch, kind, expect):
    """A known accelerator reads the table; an unknown one raises instead of
    inheriting CPU-class peaks."""
    import types

    import jax

    monkeypatch.delenv("DYN_PEAK_HBM_GBPS", raising=False)
    monkeypatch.delenv("DYN_PEAK_TFLOPS", raising=False)
    fake = types.SimpleNamespace(platform="tpu", device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [fake])
    if expect is None:
        with pytest.raises(RuntimeError, match="v9x"):
            chip_peaks()
    else:
        assert chip_peaks() == (*expect, f"table:{kind}")


# -- extraction vs estimate ---------------------------------------------------


def test_xla_extraction_agrees_with_model_within_15pct():
    """The CPU-proxy acceptance: a weight-dominated f32 program (the 1B
    decode regime, where the weight stream IS the byte budget) must show
    XLA cost-analysis bytes within 15% of the modeled operand bytes."""
    import jax
    import jax.numpy as jnp

    W = jnp.zeros((2048, 2048), jnp.float32)
    x = jnp.zeros((8, 2048), jnp.float32)
    fn = jax.jit(lambda w, v: v @ w)
    modeled = float(W.nbytes + x.nbytes + 8 * 2048 * 4)

    reg = CostRegistry(peaks=(50.0, 0.5))
    before = cost_mod.EXTRACTIONS
    # Deliberately-off estimate: extraction must retroactively correct it.
    reg.submit("decode_proxy", (8,), "decode",
               lower=make_lower_thunk(fn, (W, x), {}),
               estimate={"bytes": modeled / 3, "flops": 1.0})
    reg.observe("decode_proxy", (8,), 0.010, "decode")
    assert reg.drain(timeout=60.0), "background extraction did not finish"
    assert cost_mod.EXTRACTIONS == before + 1

    rec = reg.record_for("decode_proxy")
    assert rec.source == "xla"
    assert abs(rec.bytes - modeled) / modeled < 0.15, (rec.bytes, modeled)
    led = reg.ledger()["decode"]
    # The ledger cell the estimate already touched was retro-adjusted too.
    assert abs(led["bytes_per_step"] - rec.bytes) < 1.0
    reg.close()


def test_estimate_stands_when_no_lowering_offered():
    reg = CostRegistry(peaks=(50.0, 0.5))
    reg.submit("mock", (1,), "prefill", estimate={"bytes": 1e6, "flops": 2e6})
    reg.observe("mock", (1,), 0.001, "prefill")
    rec = reg.record_for("mock")
    assert rec.source == "estimate" and rec.bytes == 1e6
    assert reg.ledger()["prefill"]["bytes"] == 1e6
    assert reg.extract_calls == 0


def test_extraction_failure_degrades_to_estimate():
    reg = CostRegistry(peaks=(50.0, 0.5))

    def bad_lower():
        raise RuntimeError("lowering exploded")

    reg.submit("bad", (2,), "decode", lower=bad_lower,
               estimate={"bytes": 7.0, "flops": 3.0})
    assert reg.drain(timeout=30.0)
    assert reg.extract_failures == 1
    rec = reg.record_for("bad")
    assert rec.source == "estimate" and rec.bytes == 7.0
    reg.close()


def test_estimate_helpers_shapes():
    """The shared helpers bench.py / profile_1b_decode consume."""
    import jax.numpy as jnp

    params = {"layer": {"w": jnp.zeros((4, 4), jnp.float32)}}

    class Cfg:
        tie_embeddings = True

        def kv_bytes_per_token(self, itemsize=2):
            return 8 * itemsize

    est = decode_step_estimate(params, Cfg(), batch=2, context_tokens=16)
    assert est["bytes"] == weight_stream_bytes(params, Cfg()) + 2 * 16 * 16
    assert est["flops"] == 2.0 * 16 * 2


# -- roofline math ------------------------------------------------------------


def test_roofline_classification():
    reg = CostRegistry(peaks=(100.0, 1.0))  # 100 GB/s, 1 TFLOP/s
    # 50 GB in 1 s -> 0.5 of the memory peak; 0.1 TFLOP -> 0.1 of compute.
    frac, bound = reg.roofline_of(50e9, 0.1e12, 1.0)
    assert bound == "memory" and frac == pytest.approx(0.5)
    frac, bound = reg.roofline_of(1e9, 0.9e12, 1.0)
    assert bound == "compute" and frac == pytest.approx(0.9)
    assert reg.roofline_of(0.0, 0.0, 1.0) == (0.0, "")
    assert reg.roofline_of(1e9, 0.0, 0.0) == (0.0, "")


def test_multi_step_scales_by_iteration_units():
    """XLA counts a fused-loop body once; observe(steps=N) must scale the
    ledger so burst dispatches account N iterations, wall unscaled."""
    reg = CostRegistry(peaks=(100.0, 1.0))
    reg.submit("multi_step", (8,), "decode", estimate={"bytes": 10.0, "flops": 4.0})
    reg.observe("multi_step", (8,), 0.002, "decode", steps=4)
    reg.observe("multi_step", (8,), 0.002, "decode", steps=4)
    led = reg.ledger()["decode"]
    assert led["bytes"] == 80.0 and led["flops"] == 32.0
    assert led["dispatches"] == 2 and led["steps"] == 8
    assert led["bytes_per_step"] == 10.0 and led["bytes_per_dispatch"] == 40.0
    rec = reg.record_for("multi_step")
    assert rec.dispatches == 2 and rec.step_units == 8
    # take_step: the engine-core join sees burst-scaled bytes once.
    assert reg.take_step() == (80.0, 32.0)
    assert reg.take_step() == (0.0, 0.0)


def test_timed_dispatch_forwards_cost_and_steps():
    from dynamo_tpu.observability.compile import timed_dispatch

    reg = CostRegistry(peaks=(100.0, 1.0))
    reg.submit("step", (1,), "decode", estimate={"bytes": 5.0, "flops": 1.0})
    with timed_dispatch(None, "step", (1,), cost=reg, kind="decode", steps=3):
        pass
    led = reg.ledger()["decode"]
    assert led["bytes"] == 15.0 and led["steps"] == 3
    # An exception inside the body suppresses the observation (no wall).
    with pytest.raises(ValueError):
        with timed_dispatch(None, "step", (1,), cost=reg, kind="decode"):
            raise ValueError("boom")
    assert reg.ledger()["decode"]["dispatches"] == 1


# -- engine-core join + metrics (mock runner) ---------------------------------


def _run_mock_core(steps=64):
    from dynamo_tpu.mocker import build_mock_core

    core = build_mock_core(realtime=False)
    core.add_request(_greedy_req([1, 2, 3, 4, 5], max_tokens=4))
    core.add_request(_greedy_req([7, 8, 9], max_tokens=4))
    for _ in range(steps):
        if not core.has_work:
            break
        core.step()
    return core


def test_step_flight_records_carry_cost_fields():
    from dynamo_tpu.observability.flight import STEP

    core = _run_mock_core()
    assert core.runner.cost_registry is not None
    records = core.flight.snapshot(kind=STEP)
    assert records
    for r in records:
        assert "hbm_bytes" in r and "flops" in r and "roofline_frac" in r, r
    assert any(r["hbm_bytes"] > 0 for r in records)
    led = core.runner.cost_registry.ledger()
    assert "decode" in led and led["decode"]["bytes"] > 0
    assert led["decode"]["bound"] in ("memory", "compute")


async def test_cost_counters_monotone_across_scrapes():
    from dynamo_tpu.observability.metrics import EngineMetrics
    from dynamo_tpu.top import parse_prometheus

    core = _run_mock_core()
    metrics = EngineMetrics(worker="w1").bind_core(core)

    def counter_value(text, name, kind):
        total = 0.0
        found = False
        for n, lab, v in parse_prometheus(text):
            if n == name and lab.get("step_kind") == kind:
                total, found = total + v, True
        assert found, f"{name} missing from scrape"
        return total

    text1 = (await metrics.render()).decode()
    first = counter_value(text1, "dynamo_engine_hbm_bytes_total", "decode")
    assert first > 0
    assert counter_value(text1, "dynamo_engine_flops_total", "decode") > 0
    # Second scrape with no new work: delta-sync must not double-count.
    text2 = (await metrics.render()).decode()
    assert counter_value(text2, "dynamo_engine_hbm_bytes_total", "decode") == first
    # More work strictly raises the counter.
    core.add_request(_greedy_req([5, 6, 7], max_tokens=3))
    for _ in range(32):
        if not core.has_work:
            break
        core.step()
    text3 = (await metrics.render()).decode()
    assert counter_value(text3, "dynamo_engine_hbm_bytes_total", "decode") > first
    # Gauges: one roofline sample per (step_kind, bound).
    assert any(
        n == "dynamo_engine_roofline_frac" and lab.get("step_kind") == "decode"
        for n, lab, _ in parse_prometheus(text3)
    )


# -- HTTP surfaces ------------------------------------------------------------


async def test_worker_debug_server_serves_cost():
    from dynamo_tpu.observability.http import WorkerDebugServer
    from dynamo_tpu.observability.metrics import EngineMetrics

    reg = CostRegistry(worker="w-0", peaks=(100.0, 1.0))
    reg.submit("step", (1,), "decode", estimate={"bytes": 64.0, "flops": 8.0})
    reg.observe("step", (1,), 0.001, "decode")
    server = WorkerDebugServer(EngineMetrics(worker="w-0"), cost=reg)
    port = await server.start("127.0.0.1", 0)
    try:
        async with aiohttp.ClientSession() as s:
            async with s.get(f"http://127.0.0.1:{port}/debug/cost") as r:
                assert r.status == 200
                doc = await r.json()
        assert doc["enabled"] is True
        assert doc["peaks"]["source"] == "caller"
        assert doc["programs"][0]["program"] == "step"
        assert doc["ledger"]["decode"]["bytes"] == 64.0
    finally:
        await server.close()
    # Cost plane off: 200 with enabled=false, not a 404.
    server = WorkerDebugServer(EngineMetrics(worker="w-0"), cost=None)
    port = await server.start("127.0.0.1", 0)
    try:
        async with aiohttp.ClientSession() as s:
            async with s.get(f"http://127.0.0.1:{port}/debug/cost") as r:
                assert r.status == 200
                assert (await r.json())["enabled"] is False
    finally:
        await server.close()


class _FakeCostTelemetry:
    """WorkerTelemetryClient stand-in for the frontend fan-out routes."""

    def __init__(self, capture_doc):
        self.capture_doc = capture_doc
        self.capture_calls = []

    async def collect_cost(self):
        return {"w-1": {"enabled": True, "ledger": {"decode": {"bytes": 10.0}}},
                "w-2": {"enabled": False}}

    async def profile_status(self, worker=None):
        docs = {"w-1": {"available": True, "running": False},
                "w-2": {"available": False, "running": False}}
        if worker in (None, "all"):
            return docs
        return {k: v for k, v in docs.items() if k == worker}

    async def capture_profile(self, worker, duration_ms):
        self.capture_calls.append((worker, duration_ms))
        if worker == "w-missing":
            return None
        return dict(self.capture_doc)

    async def collect_metrics_texts(self):
        return []


async def _cost_frontend(capture_doc):
    from dynamo_tpu.frontend.http import HttpService
    from dynamo_tpu.frontend.metrics import FrontendMetrics
    from dynamo_tpu.frontend.model_manager import ModelManager

    telemetry = _FakeCostTelemetry(capture_doc)
    service = HttpService(ModelManager(), metrics=FrontendMetrics(), telemetry=telemetry)
    port = await service.start("127.0.0.1", 0)
    return service, f"http://127.0.0.1:{port}", telemetry


async def test_frontend_debug_cost_and_profile_routes():
    ok_doc = {"ok": True, "artifact": "/tmp/p/w-1-1", "file_count": 2,
              "files": ["a.pb", "b.json"], "total_bytes": 10, "duration_ms": 50.0}
    service, base, telemetry = await _cost_frontend(ok_doc)
    try:
        async with aiohttp.ClientSession() as s:
            async with s.get(f"{base}/debug/cost") as r:
                assert r.status == 200
                doc = await r.json()
            assert doc["count"] == 2
            assert doc["workers"]["w-1"]["ledger"]["decode"]["bytes"] == 10.0
            assert doc["workers"]["w-2"]["enabled"] is False

            async with s.get(f"{base}/debug/profile/w-1") as r:
                assert r.status == 200
                assert (await r.json())["workers"]["w-1"]["available"] is True
            async with s.get(f"{base}/debug/profile/w-nope") as r:
                assert r.status == 404

            async with s.post(f"{base}/debug/profile/w-1?duration_ms=50") as r:
                assert r.status == 200
                cap = await r.json()
            assert cap["ok"] and cap["artifact"] == "/tmp/p/w-1-1"
            assert telemetry.capture_calls == [("w-1", 50.0)]
            async with s.post(f"{base}/debug/profile/w-missing") as r:
                assert r.status == 404
            async with s.post(f"{base}/debug/profile/w-1?duration_ms=banana") as r:
                assert r.status == 400
    finally:
        await service.stop()


async def test_frontend_profile_refusals_map_to_http_statuses():
    for reason, status in (("busy", 409), ("profiler_unavailable", 501),
                           ("capture_failed", 502)):
        service, base, _ = await _cost_frontend({"ok": False, "reason": reason})
        try:
            async with aiohttp.ClientSession() as s:
                async with s.post(f"{base}/debug/profile/w-1") as r:
                    assert r.status == status, reason
                    assert (await r.json())["reason"] == reason
        finally:
            await service.stop()


# -- profile capture service --------------------------------------------------


async def _one(agen):
    return [doc async for doc in agen][0]


async def test_profile_service_status_and_unavailable(monkeypatch, tmp_path):
    from dynamo_tpu.observability.service import ProfileCaptureService

    monkeypatch.setenv("DYN_PROFILE_DIR", str(tmp_path))
    svc = ProfileCaptureService(worker="w-7")
    status = await _one(svc.generate({}, Context()))
    assert status["worker"] == "w-7"
    assert status["artifact_dir"] == str(tmp_path)
    assert "available" in status and "running" in status

    # A stripped build (no jax.profiler): structured refusal, not an error.
    monkeypatch.setattr(cost_mod, "profiler_available", lambda: False)
    doc = await _one(svc.generate({"action": "capture"}, Context()))
    assert doc["ok"] is False and doc["reason"] == "profiler_unavailable"


async def test_profile_service_capture_and_single_flight(monkeypatch, tmp_path):
    import dynamo_tpu.tracing as tracing
    from dynamo_tpu.observability.service import ProfileCaptureService

    monkeypatch.setenv("DYN_PROFILE_DIR", str(tmp_path))
    monkeypatch.setenv("DYN_PROFILE_MAX_MS", "100")
    monkeypatch.setattr(cost_mod, "profiler_available", lambda: True)

    async def fake_profile_for(seconds, log_dir):
        # Clamp applied upstream: 5000 ms request, 100 ms cap.
        assert seconds == pytest.approx(0.1)
        os.makedirs(log_dir, exist_ok=True)
        with open(os.path.join(log_dir, "t.xplane.pb"), "wb") as f:
            f.write(b"x" * 16)
        return log_dir

    monkeypatch.setattr(tracing, "profile_for", fake_profile_for)
    svc = ProfileCaptureService(worker="w-7")
    doc = await _one(svc.generate({"action": "capture", "duration_ms": 5000}, Context()))
    assert doc["ok"] is True
    assert doc["file_count"] == 1 and doc["files"] == ["t.xplane.pb"]
    assert doc["total_bytes"] == 16
    assert doc["artifact"].startswith(str(tmp_path))

    # Single-flight: profile_for answers None when a trace is running.
    async def busy_profile_for(seconds, log_dir):
        return None

    monkeypatch.setattr(tracing, "profile_for", busy_profile_for)
    doc = await _one(svc.generate({"action": "capture"}, Context()))
    assert doc["ok"] is False and doc["reason"] == "busy"


def test_device_trace_single_flight_primitive(tmp_path):
    """tracing.start_device_trace's single-flight lock, which the capture
    service inherits: a second arm while one runs is refused."""
    from dynamo_tpu import tracing

    if not cost_mod.profiler_available():
        pytest.skip("jax.profiler unavailable")
    assert tracing.start_device_trace(str(tmp_path / "t")) is True
    try:
        assert tracing.trace_running() is True
        assert tracing.start_device_trace(str(tmp_path / "t2")) is False
    finally:
        assert tracing.stop_device_trace() == str(tmp_path / "t")
    assert tracing.trace_running() is False


# -- control tower + incident bundle ------------------------------------------


def test_top_renders_roofline_panel():
    from dynamo_tpu.top import FleetSnapshot, render

    samples = [
        ("dynamo_engine_roofline_frac",
         {"worker": "w-1", "step_kind": "decode", "bound": "memory"}, 0.72),
        ("dynamo_engine_roofline_frac",
         {"worker": "w-1", "step_kind": "prefill", "bound": "compute"}, 0.31),
    ]
    frame = render(FleetSnapshot(samples, None, None, []), url="http://x")
    assert "roofline" in frame
    assert "decode" in frame and "memory-bound" in frame
    assert "0.720" in frame and "compute-bound" in frame
    # No samples: the panel says why instead of vanishing.
    empty = render(FleetSnapshot([], None, None, []), url="http://x")
    assert "no cost-plane samples" in empty


def test_incident_bundle_embeds_cost_and_capture_state(tmp_path, monkeypatch):
    from dynamo_tpu.observability.incidents import IncidentCapture, IncidentStore

    monkeypatch.setenv("DYN_PROFILE_DIR", str(tmp_path / "profiles"))
    core = _run_mock_core()
    recorder = IncidentCapture(
        store=IncidentStore(str(tmp_path / "inc")), core=core, worker="w-1"
    )
    bundle_id = recorder.capture("anomaly", {"detector": "step_gap_regression"})
    bundle = recorder.store.get(bundle_id)
    assert bundle["cost"]["enabled"] is True
    assert bundle["cost"]["ledger"]["decode"]["bytes"] > 0
    trace_state = bundle["device_trace"]
    assert "capture_available" in trace_state
    assert trace_state["artifact_dir"] == str(tmp_path / "profiles")


# -- DYN_COST_PLANE=0 acceptance ---------------------------------------------


def _tiny_core_tokens():
    from dynamo_tpu.engine.core import EngineConfig, EngineCore
    from dynamo_tpu.engine.runner import ModelRunner
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import PRESETS

    cfg = PRESETS["test-tiny"]
    params = llama.init_params(cfg, 0)
    runner = ModelRunner(cfg, params, num_pages=64, page_size=4, max_batch_size=8,
                         prefill_bucket=16, attn_impl="reference")
    core = EngineCore(runner, EngineConfig(
        num_pages=64, page_size=4, max_batch_size=8, max_prefill_tokens=256,
        max_seq_len=64, decode_steps=2,
    ))
    rng = np.random.default_rng(0)
    core.add_request(_greedy_req(
        rng.integers(1, cfg.vocab_size - 1, size=8).tolist(), max_tokens=6))
    tokens = []
    for _ in range(64):
        if not core.has_work:
            break
        for _, out in core.step():
            tokens.extend(out.token_ids)
    return runner, tokens


def test_cost_plane_off_bit_identical_zero_extractions(monkeypatch):
    """The hard gate: DYN_COST_PLANE=0 must produce the same tokens with no
    registry and no extraction lowerings at all (EXTRACTIONS spy flat)."""
    monkeypatch.setenv("DYN_COST_PLANE", "1")
    assert cost_plane_enabled()
    runner_on, tokens_on = _tiny_core_tokens()
    assert runner_on.cost_registry is not None
    assert runner_on.cost_registry.drain(timeout=60.0)
    assert runner_on.cost_registry.extract_calls > 0
    led = runner_on.cost_registry.ledger()
    assert "decode" in led and led["decode"]["bytes"] > 0

    monkeypatch.setenv("DYN_COST_PLANE", "0")
    assert not cost_plane_enabled()
    before = cost_mod.EXTRACTIONS
    runner_off, tokens_off = _tiny_core_tokens()
    assert runner_off.cost_registry is None
    assert cost_mod.EXTRACTIONS == before, "extraction ran with the plane off"
    assert tokens_on == tokens_off and len(tokens_on) == 6


# -- the plane stays out of the serving path's way ----------------------------


def test_lowering_is_submitted_after_the_call_returns():
    """First sight of a bucket: the serving call compiles the program (and
    writes the compile cache) BEFORE the registry's background thread is
    handed the same lowering — two compiles of one program side by side were
    what the one-chip host stalled under."""
    runner, _ = _tiny_core_tokens()
    order = []
    real_submit = runner.cost_registry.submit

    def fn(*args, **kwargs):
        order.append("call")
        return "out"

    fn.lower = lambda *a, **k: None  # make_lower_thunk only closes over it

    def submit(*args, **kwargs):
        order.append("submit")
        return real_submit(*args, **kwargs)

    runner.cost_registry.submit = submit
    padded = None  # _cost_estimate tolerates anything (best-effort)
    assert runner._cost_call("probe", (1, 2, 3), "decode", padded, fn) == "out"
    assert order == ["call", "submit"]
    assert runner.cost_registry.seen("probe", (1, 2, 3))
    assert runner._cost_call("probe", (1, 2, 3), "decode", padded, fn) == "out"
    assert order == ["call", "submit", "call"]  # warm: one set lookup, no resubmit
    runner.cost_registry.close()


def test_close_drops_the_queue_and_joins_the_thread():
    """A daemon thread left inside the compiler at interpreter exit crashed
    the process on the chip: close() must leave no extraction thread alive,
    and must not wait for work that has not started."""
    import threading

    reg = CostRegistry(peaks=(50.0, 0.5))
    started, release = threading.Event(), threading.Event()
    ran = []

    def slow_lower():
        started.set()
        release.wait(30.0)
        raise RuntimeError("stop here")

    def never_lower():
        ran.append(1)
        raise RuntimeError("must have been dropped")

    reg.submit("slow", (1,), "decode", lower=slow_lower)
    assert started.wait(30.0)
    reg.submit("queued", (2,), "decode", lower=never_lower)
    threading.Timer(0.2, release.set).start()
    reg.close()
    assert reg._thread is not None and not reg._thread.is_alive()
    assert not ran
    reg.close()  # idempotent


@pytest.mark.parametrize("committed", [False, True], ids=["uncommitted", "committed-to-device"])
def test_extraction_finds_the_calls_executable(committed):
    """The avatars must lower to the very module the serving call compiled:
    the registry's ``lower().compile()`` then finds that executable (in
    memory, or in the compile cache) and the backend compiles nothing twice.
    On the chip every bucket used to be compiled a second time."""
    import jax
    import jax.numpy as jnp

    misses, compiles = [], []

    def on_event(event, **_kw):
        if event == "/jax/compilation_cache/cache_misses":
            misses.append(event)

    def on_duration(event, seconds, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(seconds)

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    @functools.partial(jax.jit, static_argnames=("bias",), donate_argnums=(1,))
    def fn(w, cache, x, *, bias):
        return (x @ w).sum() + bias, cache + 1.0

    w = np.full((64, 64), 0.5, np.float32)
    bias = 7 if committed else 9  # static: a program of its own per case
    if committed:
        dev = jax.devices()[-1]
        args = (jax.device_put(w, dev), jax.device_put(np.zeros((8, 8), np.float32), dev),
                jax.device_put(np.ones((4, 64), np.float32), dev))
    else:
        args = (jnp.asarray(w), jnp.zeros((8, 8)), jnp.ones((4, 64)))
    thunk = make_lower_thunk(fn, args, {"bias": bias})
    fn(*args, bias=bias)  # the serving call: compiles
    seen_misses, seen_compiles = len(misses), len(compiles)
    compiled = thunk().compile()  # the registry's extraction, after the call
    assert len(misses) == seen_misses, "the re-lowering missed the compile cache: a second compile"
    if not committed:
        assert len(compiles) == seen_compiles  # found in memory, no backend work at all
    assert compiled.cost_analysis() is not None
