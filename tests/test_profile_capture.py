"""On-demand profiler capture and what stayed of the cost plane's file: the
chip-peaks table (now ``chip_smoke.chip_peaks``), the frontend's
``/debug/profile`` routes with their refusals, the worker's
``ProfileCaptureService`` (clamp, single flight, a build without
``jax.profiler``), ``tracing.start_device_trace``'s single-flight lock, and
the capture state inside an incident bundle.
"""

import os

import aiohttp
import pytest

import chip_smoke
from dynamo_tpu import tracing
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime.engine import Context


def _greedy_req(prompt, max_tokens=4, ignore_eos=True):
    return PreprocessedRequest(
        token_ids=list(prompt),
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=max_tokens, ignore_eos=ignore_eos),
    )


# -- peaks --------------------------------------------------------------------


def test_chip_peaks_cpu_proxy():
    hbm, tflops, source = chip_smoke.chip_peaks()
    # The test mesh is virtual CPU devices: DDR-class proxies, labelled so.
    assert (hbm, tflops) == chip_smoke.CPU_PROXY_PEAKS
    assert source.startswith("cpu-proxy:")


@pytest.mark.parametrize("kind,expect", [("TPU v5 lite", (819.0, 197.0)), ("TPU v9x", None)])
def test_chip_peaks_accelerator_table_or_error(monkeypatch, kind, expect):
    """A known accelerator reads the table; an unknown one raises instead of
    inheriting CPU-class peaks."""
    import types

    import jax

    fake = types.SimpleNamespace(platform="tpu", device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [fake])
    if expect is None:
        with pytest.raises(RuntimeError, match="v9x"):
            chip_smoke.chip_peaks()
    else:
        assert chip_smoke.chip_peaks() == (*expect, f"table:{kind}")


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


# -- the frontend's routes --------------------------------------------------------


class _FakeTelemetry:
    """WorkerTelemetryClient stand-in for the frontend fan-out routes."""

    def __init__(self, capture_doc):
        self.capture_doc = capture_doc
        self.capture_calls = []

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


async def _frontend(capture_doc):
    from dynamo_tpu.frontend.http import HttpService
    from dynamo_tpu.frontend.metrics import FrontendMetrics
    from dynamo_tpu.frontend.model_manager import ModelManager

    telemetry = _FakeTelemetry(capture_doc)
    service = HttpService(ModelManager(), metrics=FrontendMetrics(), telemetry=telemetry)
    port = await service.start("127.0.0.1", 0)
    return service, f"http://127.0.0.1:{port}", telemetry


async def test_frontend_profile_routes():
    ok_doc = {"ok": True, "artifact": "/tmp/p/w-1-1", "file_count": 2,
              "files": ["a.pb", "b.json"], "total_bytes": 10, "duration_ms": 50.0}
    service, base, telemetry = await _frontend(ok_doc)
    try:
        async with aiohttp.ClientSession() as s:
            async with s.get(f"{base}/debug/cost") as r:
                assert r.status == 404  # the cost plane's route went with it

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
        service, base, _ = await _frontend({"ok": False, "reason": reason})
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
    monkeypatch.setattr(tracing, "profiler_available", lambda: False)
    doc = await _one(svc.generate({"action": "capture"}, Context()))
    assert doc["ok"] is False and doc["reason"] == "profiler_unavailable"


async def test_profile_service_capture_and_single_flight(monkeypatch, tmp_path):
    from dynamo_tpu.observability.service import ProfileCaptureService

    monkeypatch.setenv("DYN_PROFILE_DIR", str(tmp_path))
    monkeypatch.setenv("DYN_PROFILE_MAX_MS", "100")
    monkeypatch.setattr(tracing, "profiler_available", lambda: True)

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
    if not tracing.profiler_available():
        pytest.skip("jax.profiler unavailable")
    assert tracing.start_device_trace(str(tmp_path / "t")) is True
    try:
        assert tracing.trace_running() is True
        assert tracing.start_device_trace(str(tmp_path / "t2")) is False
    finally:
        assert tracing.stop_device_trace() == str(tmp_path / "t")
    assert tracing.trace_running() is False


# -- incident bundle ------------------------------------------


def test_incident_bundle_embeds_capture_state(tmp_path, monkeypatch):
    from dynamo_tpu.observability.incidents import IncidentCapture, IncidentStore

    monkeypatch.setenv("DYN_PROFILE_DIR", str(tmp_path / "profiles"))
    core = _run_mock_core()
    recorder = IncidentCapture(
        store=IncidentStore(str(tmp_path / "inc")), core=core, worker="w-1"
    )
    bundle_id = recorder.capture("anomaly", {"detector": "step_gap_regression"})
    bundle = recorder.store.get(bundle_id)
    assert "cost" not in bundle and bundle["flight"] and bundle["loss"] is not None
    trace_state = bundle["device_trace"]
    assert "capture_available" in trace_state
    assert trace_state["artifact_dir"] == str(tmp_path / "profiles")
