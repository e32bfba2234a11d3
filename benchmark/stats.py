"""Percentile and due-time arithmetic of the benchmark (pure Python)."""

from __future__ import annotations

import math

#: What a failed or refused request reads as in any latency list: beyond
#: every percentile, and far from any real value so it shows.
FAILED_MS = 1.0e9


def percentile(values, q: float) -> float:
    """``q`` in [0, 100]; linear interpolation between order statistics (the
    rule numpy's default uses). An empty list has no percentile."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty list")
    if len(xs) == 1:
        return float(xs[0])
    rank = (len(xs) - 1) * q / 100.0
    lo = math.floor(rank)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (rank - lo))


def request_latencies(results: list[dict], window_s: float | None = None) -> dict:
    """Client-side times of the counted requests, in ms.

    A result is ``{"due", "sent", "tokens": [arrival s...], "ok", "want"}``,
    all times in seconds on the window's clock (0 = first second of the
    window). TTFT runs from the *due* time, not from the send: what a stall
    imposes on later requests counts. A request that failed, or that came
    back with another number of tokens than asked, reads FAILED_MS.

    ``window_s`` is given where requests have no due time of their own (a
    closed loop: a request is due when its client's last one ends). Then the
    requests are all that were alive in the window, also those begun in the
    lead-in, and of their numbers those count that fell inside [0, window_s):
    the gaps that ended there and the first tokens of requests sent there."""
    ttft, gaps, lateness, failed = [], [], [], 0
    for r in results:
        if r.get("cancelled") and not r["tokens"]:
            continue  # cut at the window's end before its first token: neither served nor failed
        good = r["ok"] and r["tokens"] and (r.get("cancelled") or len(r["tokens"]) == r["want"])
        if not good:
            failed += 1
            ttft.append(FAILED_MS)
            continue
        if window_s is None or 0.0 <= r["due"] < window_s:
            lateness.append((r["sent"] - r["due"]) * 1e3)
            ttft.append((r["tokens"][0] - r["due"]) * 1e3)
        gaps.extend((b - a) * 1e3 for a, b in zip(r["tokens"], r["tokens"][1:])
                    if window_s is None or 0.0 <= b < window_s)
    return {"ttft_ms": ttft, "gaps_ms": gaps, "lateness_ms": lateness, "failed": failed}


def tokens_in_window(results: list[dict], seconds: float) -> int:
    """Output tokens whose arrival fell inside [0, seconds)."""
    return sum(1 for r in results for t in r["tokens"] if 0.0 <= t < seconds)
