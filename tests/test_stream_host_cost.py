"""What the event loop does for one streamed token, made cheaper in PR 34's
third session (64 streams a step made the host, not the device, the period of
the saturated LongCat cell): the pre-rendered SSE chunk, the batched hand-in
of inter-token gaps, and the detokenizer's kept prefix. Each is held to the
plain form it replaced: same bytes, same counts, same text. (The step
builder's plain-integer decode row is held by tests/test_overlap.py: the
pipelined loop against the synchronous one, token for token.)"""

from __future__ import annotations

import random

import pytest

from dynamo_tpu.frontend import metrics as fm
from dynamo_tpu.frontend.openai_format import ChatStream, CompletionStream, sse_encode
from dynamo_tpu.protocols.common import BackendOutput, FinishReason
from dynamo_tpu.tokenizer import ByteTokenizer, IncrementalDetokenizer

_OUTPUTS = {
    "text": BackendOutput(text="plain", token_ids=[1]),
    "escapes": BackendOutput(text='hé\n"q" \\ \x00 �  ', token_ids=[2]),
    "empty": BackendOutput(text="", token_ids=[3]),
    "finish": BackendOutput(text="a", token_ids=[4], finish_reason=FinishReason.LENGTH,
                            prompt_tokens=3, cumulative_tokens=4, cached_tokens=2),
    "finish_empty": BackendOutput(text="", finish_reason=FinishReason.STOP, prompt_tokens=3, cumulative_tokens=4),
    "logprobs": BackendOutput(text="c", token_ids=[5],
                              logprobs=[{"id": 5, "logprob": -0.5, "token": "c", "top": [[5, -0.5, "c"]]}]),
}


@pytest.mark.parametrize("stream", [ChatStream, CompletionStream])
@pytest.mark.parametrize("case", sorted(_OUTPUTS))
def test_sse_delta_is_the_encoded_delta_byte_for_byte(stream, case):
    fmt = stream("some/model", send_usage=True)
    # every case twice and in a mixed order: the pre-rendered parts are kept across calls
    for name in (case, "text", case, "empty", case):
        out = _OUTPUTS[name]
        assert fmt.sse_delta(out) == sse_encode(fmt.delta(out)), name


def _itl_count(m: fm.FrontendMetrics, model: str) -> float:
    return m.registry.get_sample_value("dynamo_frontend_inter_token_latency_seconds_count", {"model": model}) or 0.0


def test_gaps_are_handed_in_by_the_batch_and_all_of_them_by_the_end(monkeypatch):
    clock = [100.0]
    monkeypatch.setattr(fm.time, "monotonic", lambda: clock[0])
    m = fm.FrontendMetrics()
    tokens = fm._ITL_BATCH + 5  # one full batch and a rest
    with m.tracker("mdl", "completions") as tr:
        for i in range(tokens):
            clock[0] += 0.001
            tr.on_token()
            gaps = i  # the first token has no gap
            # nothing until the batch is full, then the whole batch at once
            assert _itl_count(m, "mdl") == (fm._ITL_BATCH if gaps >= fm._ITL_BATCH else 0), i
            assert m.slo.itl.count == _itl_count(m, "mdl")
        tr.on_usage(4, tokens, 0)
    assert _itl_count(m, "mdl") == m.slo.itl.count == tokens - 1  # the rest went in at the end
    assert m.slo.requests_total == 1 and m.slo.output_tokens_total == tokens


def test_a_slow_stream_hands_in_its_gaps_by_the_clock(monkeypatch):
    clock = [5.0]
    monkeypatch.setattr(fm.time, "monotonic", lambda: clock[0])
    m = fm.FrontendMetrics()
    with m.tracker("slow", "chat") as tr:
        tr.on_token()
        clock[0] += 0.4
        tr.on_token()
        assert _itl_count(m, "slow") == 0  # one gap, under a second since the first token
        clock[0] += fm._ITL_BATCH_S
        tr.on_token()
        assert _itl_count(m, "slow") == 2  # both gaps, a batch of two by the clock
    assert _itl_count(m, "slow") == 2


def _plain_push(tok, state: dict, token_ids: list[int]) -> str:
    """The two-offset algorithm with both decodes made on every push."""
    ids = state.setdefault("ids", [])
    ids.extend(token_ids)
    p, r = state.get("p", 0), state.get("r", 0)
    prefix, full = tok.decode(ids[p:r]), tok.decode(ids[p:])
    if len(full) <= len(prefix) or full.endswith("�"):
        return ""
    state["p"], state["r"] = r, len(ids)
    return full[len(prefix):]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_kept_prefix_changes_no_delta(seed):
    rng = random.Random(seed)
    tok = ByteTokenizer()
    text = "".join(rng.choice(["a", "é", "€", "𝄞", " ", "\n"]) for _ in range(200))
    ids = tok.encode(text)
    # whole characters, characters split across pushes, skipped ids and invalid bytes
    for _ in range(40):
        ids.insert(rng.randrange(len(ids)), rng.choice([300, 9000, 0xFF, 0xC3]))
    detok, state, got, want = IncrementalDetokenizer(tok), {}, [], []
    i = 0
    while i < len(ids):
        n = rng.choice([1, 1, 1, 2, 3])
        got.append(detok.push(ids[i:i + n]))
        want.append(_plain_push(tok, state, ids[i:i + n]))
        i += n
    assert got == want
    assert detok.token_count == len(ids)
