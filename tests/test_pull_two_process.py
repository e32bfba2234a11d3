"""Two-OS-process KV pull: real descriptor exchange over the runtime
transport (VERDICT r3 item 3b).

The sender (this process) prefills a prompt through a real engine core,
then runs the full ``send_pull_offer`` protocol against a receiver engine
living in a SEPARATE OS process (tests/_pull_child.py) over a real TCP
runtime transport:

- "wire" mode: phase-1 miss negotiation, then a phase-2 pull whose bytes
  cross the process boundary over the socket wire (tests/_pull_wire.py —
  same contract as the PJRT transfer engine, which CPU lacks); injected
  page content is read back from the child and compared bit-for-bit.
- "unsupported" mode: the child's capability probe says no, the sender
  must get ``None`` back (no gather, no offer) and the packed-bytes
  fallback must inject the chain — the fallback negotiation end to end.

The real ``jax.experimental.transfer`` wire is probed at run time
(``device_pull_supported``): ``chip_smoke.py`` prints whether it exists on
the machine it runs on, and ``bench.py``'s kv_pull probe takes it when it
does and records ``"transfer_engine": "unsupported"`` when it does not.
"""

import asyncio
import os
import subprocess
import sys

import numpy as np
import pytest

from dynamo_tpu.disagg.pull_transport import set_transport
from dynamo_tpu.disagg.transfer import (
    collect_prefill_blocks,
    send_blocks,
    send_pull_offer,
)
from dynamo_tpu.engine.core import EngineConfig, EngineCore
from dynamo_tpu.engine.runner import ModelRunner
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import PRESETS
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.runtime.tcp import TcpTransport
from dynamo_tpu.tokens import compute_block_hashes

from _pull_wire import SocketWireTransport

CHILD = os.path.join(os.path.dirname(__file__), "_pull_child.py")
PAGE = 4
PROMPT = [(i * 7 + 3) % 64 for i in range(32)]  # 8 full pages


def _spawn_child(mode: str) -> tuple[subprocess.Popen, str, str]:
    proc = subprocess.Popen(
        [sys.executable, CHILD, mode],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    for line in proc.stdout:
        if line.startswith("ADDR "):
            _tag, kv_addr, read_addr = line.split()
            return proc, kv_addr, read_addr
    raise RuntimeError(f"child exited without ADDR (rc={proc.wait()})")


def _stop_child(proc: subprocess.Popen) -> None:
    try:
        proc.stdin.close()
        proc.wait(timeout=20)
    except Exception:
        proc.kill()


def _sender_core() -> EngineCore:
    cfg = PRESETS["test-tiny"]
    params = llama.init_params(cfg, 0)
    runner = ModelRunner(
        cfg, params, num_pages=32, page_size=PAGE, max_batch_size=4,
        prefill_bucket=16, attn_impl="reference",
    )
    core = EngineCore(runner, EngineConfig(
        num_pages=32, page_size=PAGE, max_batch_size=4,
        max_prefill_tokens=128, max_seq_len=128,
    ))
    # A real 1-token generation commits the prompt's full pages — the same
    # thing the prefill worker does before shipping KV.
    core.add_request(PreprocessedRequest(
        token_ids=list(PROMPT), sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=1, ignore_eos=True), request_id="warm",
    ), Context())
    for _ in range(50):
        if not core.has_work:
            break
        core.step()
    return core


async def _read_child_pages(transport, read_addr, hashes) -> dict:
    out = {}
    async for item in transport.generate(read_addr, {"hashes": hashes}, Context()):
        out = item
    return out


@pytest.mark.e2e
async def test_two_process_pull_wire():
    wire = SocketWireTransport()
    set_transport(wire, supported=True)
    proc, kv_addr, read_addr = _spawn_child("wire")
    transport = TcpTransport(host="127.0.0.1")
    try:
        core = _sender_core()
        hashes = compute_block_hashes(PROMPT, PAGE, salt=core.config.salt)
        assert len(hashes) == 8

        result = await send_pull_offer(transport, kv_addr, "req-1", core, hashes)
        assert result is not None and result["injected"] == len(hashes), result
        assert wire.served >= 1, "the offer was never pulled over the socket wire"
        assert not wire.offers, "offer not released after completion"

        # Bit-for-bit content check: the child's committed pages must equal
        # the sender's source pages.
        child = await _read_child_pages(transport, read_addr, hashes)
        assert child["n"] == len(hashes)
        src_pages = core.allocator.match_prefix(hashes)
        try:
            src = core.runner.read_pages(src_pages)
        finally:
            core.allocator.release(src_pages)
        for i, (k, v) in enumerate(src):
            assert child["k"][i] == np.ascontiguousarray(k).tobytes(), f"page {i} K mismatch"
            assert child["v"][i] == np.ascontiguousarray(v).tobytes(), f"page {i} V mismatch"

        # Warm-cache re-offer: the child already has the chain, so phase 1
        # completes it — no new gather/offer (the ADVICE r3 leak class).
        offered_before = wire.offered
        result2 = await send_pull_offer(transport, kv_addr, "req-2", core, hashes)
        assert result2 is not None and result2["injected"] == len(hashes)
        assert wire.offered == offered_before
    finally:
        _stop_child(proc)
        await transport.close()
        set_transport(None, None)
        wire.close()


@pytest.mark.e2e
async def test_two_process_fallback_negotiation():
    """Receiver without transfer-engine support: the sender's phase-1 query
    must come back pull_unsupported (send_pull_offer -> None, nothing
    offered) and the packed-bytes stream must deliver the chain."""
    wire = SocketWireTransport()
    set_transport(wire, supported=True)  # sender side WOULD do pulls
    proc, kv_addr, read_addr = _spawn_child("unsupported")
    transport = TcpTransport(host="127.0.0.1")
    try:
        core = _sender_core()
        hashes = compute_block_hashes(PROMPT, PAGE, salt=core.config.salt)

        result = await send_pull_offer(transport, kv_addr, "req-1", core, hashes)
        assert result is None
        assert wire.offered == 0, "sender gathered/offered despite unsupported receiver"

        blocks = collect_prefill_blocks(core, hashes)
        assert len(blocks) == len(hashes)
        summary = await send_blocks(transport, kv_addr, "req-1", blocks)
        assert summary["injected"] == len(hashes), summary

        child = await _read_child_pages(transport, read_addr, hashes)
        assert child["n"] == len(hashes)
    finally:
        _stop_child(proc)
        await transport.close()
        set_transport(None, None)
        wire.close()
