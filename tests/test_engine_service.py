"""Async engine service tests: streaming, concurrency, cancellation."""

import asyncio

import pytest

from dynamo_tpu.engine.core import EngineConfig, EngineCore
from dynamo_tpu.engine.runner import ModelRunner
from dynamo_tpu.engine.service import JaxEngineService
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import PRESETS
from dynamo_tpu.protocols.common import PreprocessedRequest, SamplingOptions, StopConditions
from dynamo_tpu.runtime.engine import Context

CFG = PRESETS["test-tiny"]
PARAMS = llama.init_params(CFG, 0)


def make_service(**engine):
    config = EngineConfig(num_pages=64, page_size=4, max_batch_size=8, max_seq_len=128, **engine)
    runner = ModelRunner(CFG, PARAMS, num_pages=64, page_size=4, max_batch_size=8,
                         prefill_bucket=16, attn_impl="reference")
    return JaxEngineService(EngineCore(runner, config))


def req(prompt, max_tokens=5):
    return PreprocessedRequest(
        token_ids=prompt, sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=max_tokens),
    ).to_dict()


async def test_stream_tokens():
    svc = make_service()
    try:
        outs = [o async for o in svc.generate(req([1, 2, 3]), Context())]
        tokens = [t for o in outs for t in o["token_ids"]]
        assert len(tokens) == 5
        assert outs[-1]["finish_reason"] == "length"
        assert outs[-1]["prompt_tokens"] == 3
    finally:
        await svc.close()


async def test_concurrent_streams():
    svc = make_service()
    try:
        async def run(prompt):
            return [t async for o in svc.generate(req(prompt, 6), Context()) for t in o["token_ids"]]

        results = await asyncio.gather(run([1, 2]), run([3, 4, 5]), run([9, 8, 7, 6]))
        assert all(len(r) == 6 for r in results)
        # Same prompt twice gives identical greedy output.
        again = await run([1, 2])
        assert again == results[0]
    finally:
        await svc.close()


async def test_cancellation_ends_stream():
    svc = make_service()
    try:
        ctx = Context()
        got = []
        async for o in svc.generate(req([1, 2, 3], max_tokens=500), ctx):
            got.append(o)
            if len(got) == 2:
                ctx.stop_generating()
        assert got[-1]["finish_reason"] in ("cancelled", "stop", "length")
        # The pipelined loop may have dispatched one more step with the row
        # before it saw the stop: it reads that step next (its token is
        # discarded) and has nothing left.
        for _ in range(200):
            if not svc.core.has_work:
                break
            await asyncio.sleep(0.01)
        assert not svc.core.has_work
        assert svc.core.allocator.stats().active_pages == 0
    finally:
        await svc.close()


# -- outputs are routed once the next step's program is enqueued -----------------


def _spy(svc):
    """Record, in order, every enqueue (the runner's callback) and every routed
    batch of outputs (how many tokens it carried)."""
    log = []
    on_enqueued, route = svc._on_enqueued, svc._route

    def enq():
        log.append("enqueued")
        on_enqueued()

    def routed(outputs):
        log.append(("routed", sum(len(o.token_ids) for _, o in outputs)))
        route(outputs)

    svc._on_enqueued, svc._route = enq, routed
    return log


async def test_a_steps_outputs_are_routed_after_the_next_enqueue():
    svc = make_service(overlap=False)  # the synchronous step: what the pipelined loop barriers to
    log = _spy(svc)
    try:
        outs = [o async for o in svc.generate(req([1, 2, 3], 6), Context())]
        assert [t for o in outs for t in o["token_ids"]] and outs[-1]["finish_reason"] == "length"
        routed = [i for i, e in enumerate(log) if e != "enqueued"]
        assert sum(log[i][1] for i in routed) == 6
        # Every batch but the last waited for the enqueue after the step that made it:
        # between two routed batches lies exactly one enqueue, and none is routed
        # before the second step is on the device.
        assert log[:2] == ["enqueued", "enqueued"]
        for a, b in zip(routed, routed[1:-1]):
            assert log[a + 1: b] == ["enqueued"]
        # The last step has no successor: its outputs go when the engine idles.
        assert log[-1][0] == "routed" and not svc._held and not svc._streams
    finally:
        await svc.close()


@pytest.mark.parametrize("overlap", [True, False], ids=["pipelined", "synchronous"])
async def test_streams_see_every_token_in_order_under_deferred_routing(overlap):
    svc = make_service(overlap=overlap)
    try:
        async def run(prompt, n):
            return [t async for o in svc.generate(req(prompt, n), Context()) for t in o["token_ids"]]

        prompts = ([1, 2], [3, 4, 5], [9, 8, 7, 6])
        got = await asyncio.gather(*(run(p, 4 + i) for i, p in enumerate(prompts)))
        want = []
        for i, p in enumerate(prompts):  # the same requests stepped by hand, no service between
            core = make_service().core
            seq = core.add_request(PreprocessedRequest.from_dict(req(p, 4 + i)), Context())
            toks = []
            while core.has_work:
                toks += [t for s, o in core.step() if s is seq for t in o.token_ids]
            want.append(toks)
        assert got == want
    finally:
        await svc.close()


async def test_a_runner_without_the_callback_routes_at_once():
    svc = make_service(overlap=False)
    log = _spy(svc)
    try:
        await svc.start()
        await asyncio.sleep(0)
        svc.core.runner.on_enqueued = None  # the loop installed it when it started
        outs = [o async for o in svc.generate(req([1, 2, 3], 4), Context())]
        assert sum(len(o["token_ids"]) for o in outs) == 4
        assert "enqueued" not in log and not svc._held
        assert [e[1] for e in log] == [1, 1, 1, 1]  # one batch a step, none held back
    finally:
        await svc.close()


async def test_the_overlapped_loop_holds_nothing_back():
    svc = make_service()  # the serving loop; step_async never blocks on its own result: no callback
    log = _spy(svc)
    try:
        outs = [o async for o in svc.generate(req([1, 2, 3], 5), Context())]
        assert sum(len(o["token_ids"]) for o in outs) == 5
        assert "enqueued" not in log and not svc._held
    finally:
        await svc.close()


async def test_close_takes_the_callback_off_the_runner():
    svc = make_service()
    await svc.start()
    await asyncio.sleep(0)
    assert svc.core.runner.on_enqueued == svc._on_enqueued
    await svc.close()
    assert svc.core.runner.on_enqueued is None
