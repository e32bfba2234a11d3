"""``correct``: the live engine's logprobs against the plain reference.

A seeded sample of prompts goes through the engine service the HTTP frontend
feeds, while another request is decoding: the scheduler cuts each prompt into
chunks that ride mixed steps beside the decoding row, then the prompt's own
tokens are decoded through the paged cache. Asked for are the logprob of each
chosen token and the top 20. The same tokens go through the configuration's
plain reference (float32, ``highest`` matmul precision, whole sequence, no
cache) on the same benchmark-made weights. Compared are logprobs at the ids
the engine named (not the ids: random weights give near-tied argmaxes):

    rel_err = max |served - reference| / max |reference logit|

The limit is the configuration's ``serve.logprob_rel_limit`` (PERF.md has the
readings it was set from). Every run prints the number beside its limit.
"""

from __future__ import annotations

import asyncio
import functools
import importlib

import numpy as np

#: (prompt tokens, output tokens) of the checked sequences and of the row that
#: decodes beside them; all multiples of the pinned chunk.
CHECKED = [(192, 4), (128, 4)]
FILLER = (64, 40)
PAD_TO = 256


def reference_logprobs(conf: dict, params, sequences: list[list[int]], spans: list[tuple[int, int]]):
    """Log-softmax rows [len(sequences)][steps, vocab] at the positions that
    predict each generated token, and the largest |logit| seen."""
    import jax
    import jax.numpy as jnp

    ref = importlib.import_module(f"benchmark.reference.{conf['reference']}")
    fwd = jax.jit(functools.partial(ref.forward, hf=conf["hf"]))
    rows, absmax = [], 0.0
    with jax.default_matmul_precision("highest"):
        for seq, (first, steps) in zip(sequences, spans):
            pad = max(PAD_TO, len(seq))
            toks = np.zeros(pad, np.int32)
            toks[: len(seq)] = seq  # causal: the padded tail cannot reach back
            logits = np.asarray(fwd(params, tokens=jnp.asarray(toks))[first: first + steps], np.float32)
            if not np.isfinite(logits).all():
                raise FloatingPointError("reference logits are not finite")
            absmax = max(absmax, float(np.abs(logits).max()))
            z = logits - logits.max(axis=-1, keepdims=True)
            rows.append(z - np.log(np.exp(z).sum(axis=-1, keepdims=True)))
    return rows, absmax


async def _served(service, prompt: list[int], n_out: int, *, logprobs: bool, started: asyncio.Event | None = None):
    from dynamo_tpu.engine.core import LOGPROBS_TOP_K
    from dynamo_tpu.protocols.common import PreprocessedRequest, SamplingOptions, StopConditions
    from dynamo_tpu.runtime.engine import Context

    request = PreprocessedRequest(
        token_ids=prompt,
        sampling=SamplingOptions(temperature=0.0, logprobs=LOGPROBS_TOP_K + 1 if logprobs else None),
        stop=StopConditions(max_tokens=n_out, ignore_eos=True))
    entries, ids = [], []
    async for out in service.generate(request, Context()):
        entries.extend(out.get("logprobs") or [])
        ids.extend(out.get("token_ids") or [])
        if started is not None and ids:
            started.set()
    if logprobs and len(entries) != n_out:
        raise RuntimeError(f"asked the engine for {n_out} tokens with logprobs, got {len(entries)}")
    return entries


async def serve_sample(service, conf: dict, seed: int, *, scale: float = 1.0) -> dict:
    """The engine's half: the seeded prompts through the live service.
    ``scale`` shrinks the lengths for the CPU rehearsal's tiny context."""
    vocab = conf["hf"]["vocab_size"]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 77]))
    sized = [(max(8, int(p * scale)), n) for p, n in CHECKED]
    prompts = [rng.integers(1, vocab, size=p).tolist() for p, _ in sized]
    filler = rng.integers(1, vocab, size=max(8, int(FILLER[0] * scale))).tolist()
    started = asyncio.Event()
    beside = asyncio.ensure_future(_served(service, filler, FILLER[1], logprobs=False, started=started))
    await started.wait()
    served = [await _served(service, p, n, logprobs=True) for p, (_, n) in zip(prompts, sized)]
    await beside
    return {"served": served,
            "sequences": [p + [e["id"] for e in row][:-1] for p, row in zip(prompts, served)],
            "spans": [(len(p) - 1, len(row)) for p, row in zip(prompts, served)]}


def score(conf: dict, params, sample: dict) -> dict:
    """The reference's half, on the weights as configured, and the verdict."""
    ref_rows, absmax = reference_logprobs(conf, params, sample["sequences"], sample["spans"])
    worst, total, agree, n = 0.0, 0.0, 0, 0
    for row, ref in zip(sample["served"], ref_rows):
        for j, e in enumerate(row):
            ids = [e["id"]] + [i for i, _ in e["top"]]
            got = np.asarray([e["logprob"]] + [lp for _, lp in e["top"]], np.float64)
            diff = np.abs(got - ref[j, ids])
            worst, total, n = max(worst, float(diff.max())), total + float(diff.sum()), n + diff.size
            agree += int(e["id"] == int(ref[j].argmax()))
    limit = float(conf["serve"]["logprob_rel_limit"])
    rel = worst / absmax
    return {"rel_err": rel, "limit": limit, "ok": bool(rel <= limit), "mean_rel_err": total / n / absmax,
            "ref_logit_absmax": absmax, "argmax_agree": agree,
            "tokens": sum(len(r) for r in sample["served"]), "ids_compared": n}


async def compare(service, conf: dict, params, seed: int, *, scale: float = 1.0) -> dict:
    """Both halves on a live stack: ``{"rel_err", "limit", "ok", ...}``."""
    sample = await serve_sample(service, conf, seed, scale=scale)
    # Off the event loop: the server is live, and its keep-alives run there.
    return await asyncio.get_running_loop().run_in_executor(None, functools.partial(score, conf, params, sample))
