"""Tracing (device trace + spans) and the object store.

Device traces run against the CPU backend here (same jax.profiler API the
TPU path uses); spans assert on structured log records; object store tests
cover chunking, checksums, partial uploads, and card artifact round-trips.
"""

import json
import logging

import pytest

from dynamo_tpu.runtime.discovery import MemoryStore
from dynamo_tpu.runtime.objects import ObjectError, ObjectStore, is_object_url, object_name


async def test_object_roundtrip_chunked():
    objects = ObjectStore(MemoryStore(), chunk_size=8)
    data = bytes(range(256)) * 3  # 768 bytes -> 96 chunks
    url = await objects.put("art/blob.bin", data)
    assert url == "object://art/blob.bin"
    assert await objects.get("art/blob.bin") == data
    meta = await objects.stat("art/blob.bin")
    assert meta["chunks"] == 96 and meta["size"] == 768
    assert await objects.delete("art/blob.bin")
    with pytest.raises(ObjectError, match="not found"):
        await objects.get("art/blob.bin")


async def test_overwrite_cleans_orphan_chunks():
    store = MemoryStore()
    objects = ObjectStore(store, chunk_size=4)
    await objects.put("x", b"0123456789ab")  # 3 chunks
    await objects.put("x", b"zz")  # 1 chunk
    assert await objects.get("x") == b"zz"
    assert await store.get("objects/x/chunk/00000001") is None
    assert await store.get("objects/x/chunk/00000002") is None


async def test_card_dir_tokenizer_uploaded(tmp_path):
    from dynamo_tpu.model_card import ModelDeploymentCard
    from dynamo_tpu.sentencepiece import NORMAL, UNKNOWN, write_model

    mdir = tmp_path / "model"
    mdir.mkdir()
    (mdir / "tokenizer.model").write_bytes(
        write_model([("<unk>", 0.0, UNKNOWN), ("▁a", -1.0, NORMAL)], bos_id=-1, eos_id=-1)
    )
    objects = ObjectStore(MemoryStore())
    card = ModelDeploymentCard(name="m2", tokenizer=str(mdir))
    await card.move_to_store(objects)
    assert card.tokenizer == "object://cards/m2/tokenizer.model"


async def test_object_missing_chunk_detected():
    store = MemoryStore()
    objects = ObjectStore(store, chunk_size=4)
    await objects.put("x", b"0123456789")
    await store.delete("objects/x/chunk/00000001")
    with pytest.raises(ObjectError, match="missing chunk"):
        await objects.get("x")


async def test_object_checksum_detects_corruption():
    store = MemoryStore()
    objects = ObjectStore(store, chunk_size=4)
    await objects.put("x", b"0123456789")
    await store.put("objects/x/chunk/00000000", b"9999")
    with pytest.raises(ObjectError, match="checksum"):
        await objects.get("x")


def test_object_url_helpers():
    assert is_object_url("object://a/b")
    assert not is_object_url("/tmp/a")
    assert not is_object_url(None)
    assert object_name("object://a/b") == "a/b"
    with pytest.raises(ObjectError):
        object_name("/tmp/nope")


async def test_card_artifact_distribution(tmp_path):
    """Card -> object store -> fresh 'worker host' -> identical tokenizer."""
    from dynamo_tpu.model_card import ModelDeploymentCard
    from dynamo_tpu.sentencepiece import NORMAL, UNKNOWN, write_model
    from dynamo_tpu.tokenizer import load_tokenizer

    pieces = [("<unk>", 0.0, UNKNOWN), ("▁hi", -1.0, NORMAL), ("▁yo", -1.2, NORMAL)]
    src = tmp_path / "src" / "tokenizer.model"
    src.parent.mkdir()
    src.write_bytes(write_model(pieces, bos_id=-1, eos_id=-1))

    objects = ObjectStore(MemoryStore())
    card = ModelDeploymentCard(name="m1", tokenizer=str(src))
    await card.move_to_store(objects)
    assert is_object_url(card.tokenizer)

    # simulate shipping the card: serialize/deserialize, resolve elsewhere
    card2 = ModelDeploymentCard.from_bytes(card.to_bytes())
    cache = tmp_path / "worker-cache"
    await card2.resolve_from_store(objects, cache)
    assert not is_object_url(card2.tokenizer)
    tok = load_tokenizer(card2.tokenizer)
    assert tok.encode("hi yo") == [1, 2]


async def test_device_trace_writes_xplane(tmp_path):
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.tracing import device_trace, trace_running

    with device_trace(str(tmp_path / "trace")):
        assert trace_running()
        jnp.dot(jnp.ones((64, 64)), jnp.ones((64, 64))).block_until_ready()
    assert not trace_running()
    dumps = list((tmp_path / "trace").rglob("*.xplane.pb"))
    assert dumps, "no xplane dump written"


@pytest.fixture
def profiler_calls(monkeypatch):
    """jax.profiler's session calls, recorded and not run."""
    import jax

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda log_dir, **kw: calls.append(("start", str(log_dir), kw.get("profiler_options"))))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: calls.append(("stop",)))
    return calls


async def _capture_from_env(tmp_path, monkeypatch):
    import asyncio

    from dynamo_tpu import tracing

    monkeypatch.setenv("DYN_TRACE_DIR", str(tmp_path / "env"))
    monkeypatch.setenv("DYN_TRACE_SECONDS", "0.01")
    tracing.maybe_trace_from_env()
    for _ in range(200):
        if not tracing.trace_running():
            break
        await asyncio.sleep(0.01)


async def _capture_over_http(tmp_path, monkeypatch):
    """``POST /debug/profile/{worker}``: the worker endpoint's own body."""
    from dynamo_tpu.observability.service import ProfileCaptureService
    from dynamo_tpu.runtime.engine import Context

    monkeypatch.setenv("DYN_PROFILE_DIR", str(tmp_path / "http"))
    svc = ProfileCaptureService(worker="w0")
    docs = [d async for d in svc.generate({"action": "capture", "duration_ms": 5}, Context())]
    assert docs[-1]["ok"] is True, docs


async def _capture_as_the_benchmark_does(tmp_path, monkeypatch):
    from dynamo_tpu import tracing

    assert tracing.start_device_trace(str(tmp_path / "bench")) is True
    assert tracing.stop_device_trace() == str(tmp_path / "bench")


@pytest.mark.parametrize("capture", [_capture_from_env, _capture_over_http, _capture_as_the_benchmark_does],
                         ids=["DYN_TRACE_DIR", "debug_profile", "benchmark"])
async def test_every_capture_entry_point_leaves_the_python_tracer_off(capture, tmp_path, monkeypatch, profiler_calls):
    """One place sets the profiler's options, and every way to start a capture
    goes through it (``POST /engine/profile`` is gone: it traced the frontend's
    process, which holds no chip in a split deployment)."""
    from dynamo_tpu import tracing

    await capture(tmp_path, monkeypatch)
    assert [c[0] for c in profiler_calls] == ["start", "stop"] and not tracing.trace_running()
    opts = profiler_calls[0][2]
    assert opts is not None and opts.python_tracer_level == 0 and opts.host_tracer_level == 1


async def test_engine_profile_route_is_gone():
    import aiohttp

    from dynamo_tpu.launch import run_local, stop_local

    handles = await run_local("test-tiny", port=0, mock=True, num_pages=64)
    try:
        async with aiohttp.ClientSession() as s:
            r = await s.post(f"http://127.0.0.1:{handles['port']}/engine/profile", json={"seconds": 0.1})
            assert r.status in (404, 405)
    finally:
        await stop_local(handles)


async def test_one_request_yields_its_five_path_spans_under_one_trace():
    """``frontend_pre_engine`` -> ``engine_queue_wait`` -> ``engine_admission_wait``
    -> ``engine_prefill`` -> ``frontend_first_byte``: one trace id, in start
    order, each inside ``http_request``, each naming a parent of that trace."""
    import aiohttp

    from dynamo_tpu.launch import run_local, stop_local
    from dynamo_tpu.tracing import SPANS

    handles = await run_local("test-tiny", port=0, num_pages=64, max_batch_size=4)
    try:
        async with aiohttp.ClientSession() as s:
            r = await s.post(
                f"http://127.0.0.1:{handles['port']}/v1/completions",
                json={"model": "test-tiny", "prompt": [5, 6, 7, 8, 9, 10, 11], "max_tokens": 4, "stream": True})
            assert r.status == 200
            trace_id = r.headers["x-dynamo-trace-id"]
            body = await r.text()
            assert "[DONE]" in body
    finally:
        await stop_local(handles)
    spans = SPANS.query(trace_id=trace_id)
    by_name = {s["name"]: s for s in spans}
    path = ["frontend_pre_engine", "engine_queue_wait", "engine_admission_wait", "engine_prefill",
            "frontend_first_byte"]
    assert set(path) <= set(by_name), sorted(by_name)
    root = by_name["http_request"]
    starts = [by_name[n]["start_mono"] for n in path]
    assert starts == sorted(starts) and starts[0] == pytest.approx(root["start_mono"], abs=1e-3)
    ids = {s["span_id"] for s in spans}
    for n in path:
        sp = by_name[n]
        assert sp["parent_id"] in ids and sp["trace_id"] == trace_id
        assert sp["start_mono"] >= root["start_mono"] - 1e-3
        assert sp["start_mono"] + sp["duration_ms"] / 1e3 <= root["start_mono"] + root["duration_ms"] / 1e3 + 1e-3
    assert by_name["frontend_pre_engine"]["parent_id"] == root["span_id"] == by_name["frontend_first_byte"]["parent_id"]
    pf = by_name["engine_prefill"]
    assert (pf["prompt_tokens"], pf["cached_tokens"], pf["chunks"]) == (7, 0, 1) and pf["steps"] >= 1
    # The path's parts are contiguous: together they span the request's start to its first byte.
    end = by_name["frontend_first_byte"]["start_mono"] + by_name["frontend_first_byte"]["duration_ms"] / 1e3
    assert sum(by_name[n]["duration_ms"] for n in path) / 1e3 == pytest.approx(end - root["start_mono"], abs=5e-3)


def test_span_buffer_counts_what_it_drops():
    from dynamo_tpu.tracing import SpanBuffer

    ring = SpanBuffer(capacity=3)
    for i in range(3):
        ring.record({"name": "s", "i": i})
    assert ring.dropped == 0 and len(ring) == 3
    ring.record({"name": "s", "i": 3})
    ring.record({"name": "s", "i": 4})
    assert ring.dropped == 2 and [s["i"] for s in ring.query()] == [2, 3, 4]
    ring.clear()
    assert ring.dropped == 0 and len(ring) == 0


def test_trace_context_carries_its_root_across_hops():
    from dynamo_tpu.tracing import Span, TraceContext

    with Span("http_request") as root:
        ctx = root.context
    assert (ctx.root_id, ctx.root_ts) == (root.span_id, root.t_wall)
    hop = TraceContext.from_dict(ctx.to_dict())
    with Span("rpc_client", trace=hop) as child:
        pass
    far = TraceContext.from_dict(child.context.to_dict())
    assert far.span_id == child.span_id and (far.root_id, far.root_ts) == (root.span_id, root.t_wall)
    assert far.under_root().span_id == root.span_id
    bare = TraceContext.from_traceparent(f"00-{'a' * 32}-{'b' * 16}-01")
    assert bare.to_dict() == {"trace_id": "a" * 32, "span_id": "b" * 16}  # nothing added on the W3C path


def test_span_logs_structured_fields(caplog):
    from dynamo_tpu.tracing import Span

    with caplog.at_level(logging.DEBUG, logger="dynamo.trace"):
        with Span("prefill", request_id="r1", tokens=7):
            pass
        with pytest.raises(ValueError):
            with Span("decode", request_id="r2"):
                raise ValueError("boom")
    records = [r for r in caplog.records if getattr(r, "span", None)]
    assert records[0].span == "prefill" and records[0].request_id == "r1"
    assert records[0].duration_ms >= 0
    assert records[1].span == "decode" and records[1].error == "ValueError"


def test_jsonl_formatter_flattens_span_fields():
    from dynamo_tpu.runtime.logging import JsonlFormatter
    from dynamo_tpu.tracing import Span

    captured = []

    class Sink(logging.Handler):
        def emit(self, record):
            captured.append(JsonlFormatter().format(record))

    log = logging.getLogger("dynamo.trace")
    sink = Sink(level=logging.DEBUG)
    log.addHandler(sink)
    old = log.level
    log.setLevel(logging.DEBUG)
    try:
        with Span("step", request_id="r9", tokens=3):
            pass
    finally:
        log.setLevel(old)
        log.removeHandler(sink)
    doc = json.loads(captured[-1])
    assert doc["span"] == "step" and doc["request_id"] == "r9" and doc["tokens"] == 3
