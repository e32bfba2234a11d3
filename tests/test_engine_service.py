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


# -- a step's outputs are routed when the step returns ---------------------------


def _spy(svc):
    """Record, in order, every step the loop submits (when it begins, and the
    tokens it hands back when it returns) and every routed batch of outputs
    (how many tokens it carried)."""
    log = []
    step, route = svc.core.step, svc._route

    def stepped():
        log.append("step")
        outputs = step()
        log.append(("returned", sum(len(o.token_ids) for _, o in outputs)))
        return outputs

    def routed(outputs):
        log.append(("routed", sum(len(o.token_ids) for _, o in outputs)))
        route(outputs)

    svc.core.step, svc._route = stepped, routed
    return log


@pytest.mark.parametrize("overlap", [True, False], ids=["pipelined", "synchronous"])
async def test_a_steps_outputs_are_routed_before_the_next_step_is_submitted(overlap):
    svc = make_service(overlap=overlap)
    log = _spy(svc)
    on_the_runner = set(vars(svc.core.runner))
    try:
        outs = [o async for o in svc.generate(req([1, 2, 3], 6), Context())]
        assert sum(len(o["token_ids"]) for o in outs) == 6 and outs[-1]["finish_reason"] == "length"
        # step, returned n, routed n, and only then the next step: nothing waits for a later enqueue.
        assert len(log) % 3 == 0 and len(log) >= 3 * 6
        for begin, returned, routed in zip(log[0::3], log[1::3], log[2::3]):
            assert begin == "step" and returned[0] == "returned" and routed == ("routed", returned[1])
        assert sum(e[1] for e in log[2::3]) == 6 and not svc._streams
        # The pipelined loop hands a step's tokens back one call later; the synchronous step its own.
        assert log[1] == ("returned", 0 if overlap else 1)
        # The service knows the runner through the core alone: it has put nothing on it.
        assert set(vars(svc.core.runner)) == on_the_runner
    finally:
        await svc.close()


@pytest.mark.parametrize("overlap", [True, False], ids=["pipelined", "synchronous"])
async def test_streams_see_every_token_in_order(overlap):
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
