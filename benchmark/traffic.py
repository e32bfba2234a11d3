"""The one traffic generator: a mix is a data file, a seed orders it.

A mix (``benchmark/traffic/<name>.json``) fixes *what* is offered: a table
of (prompt tokens, output tokens) per 100 requests, the loop kind with its
rate or client count, the lead-in, optionally shared prefixes, bursts and
sessions, and with ``schedule_seed`` the order of the table's rows and where
the arrivals fall. The run's seed draws the token ids (and, in the harness,
the weights); never how many requests there are, which lengths, nor when. Every run of a cell therefore offers the same work.

Schema (all keys but ``loop`` and ``lengths_per_100`` optional):

- ``loop``: "open" | "closed". ``schedule_seed``: the seed of the schedule (default 0).
- ``rate_rps`` (open): requests per second; N = round(rate * seconds) arrive
  in the window at the sorted values of N seeded uniforms (a Poisson process
  given its count). ``lead_in_s`` seconds of the same rate come first and are
  not counted.
- ``clients`` (closed): each sends its next request when the last one ends.
  ``first_answer_share``: [lo, hi], each client's first answer is cut to a
  seeded share of its length so that the clients do not end in waves.
- ``lengths_per_100``: [[prompt, output], ...] 100 rows, in an order of which
  every prefix is balanced; request i takes row i mod 100.
- ``prefix_levels``: [{"tokens": n, "groups": g}, ...] up to two levels of
  shared prefix: level 0 is drawn from ``g0`` distinct prefixes, level 1 from
  ``g1`` per level-0 group. Prefix tokens count inside the prompt length.
- ``bursts``: {"size": [lo, hi], "within_s": s, "every_s": [lo, hi]}: the
  window's arrivals are grouped into bursts instead of spread uniformly.
- ``sessions``: {"count": n, "turns": [lo, hi], "think_s": [lo, hi]}: a
  session's turn t+1 is due ``think`` seconds after turn t ended and its
  prompt is turn t's prompt, a stand-in for its answer, and new tokens.
- ``prebuilt`` and ``router``: caches built during set-up and replicas behind
  a router; carried by the schema, refused by the harness until it has them.

What belongs to one cell (a configuration under this mix) is in a file of the
cell's own, ``benchmark/cells/<cell>.json``, so that a new configuration on a
mix that is there edits nothing: ``rate_rps`` (a share of *that* system's
knee) or ``clients``, and ``warm``: {"max_rows", "max_context_tokens"}, the
step programs the cell can reach, for the warm-up. ``why`` and ``knee`` there
are for the reader.
"""

from __future__ import annotations

import hashlib
import json
import pathlib

import numpy as np

KNOWN = {"schedule_seed", "loop", "rate_rps", "clients", "lead_in_s", "lengths_per_100", "first_answer_share",
         "prefix_levels", "bursts", "sessions", "prebuilt", "router", "why", "requests_per_client"}
#: Keys of a cell's own file; all but ``why`` and ``knee`` are laid over the mix.
CELL_KEYS = {"why", "knee", "rate_rps", "clients", "warm"}


def load_mix(path, cell_path=None) -> dict:
    """The mix, with the cell's own rate (or clients) and warm-up list over it."""
    mix = json.loads(pathlib.Path(path).read_text())
    unknown = set(mix) - KNOWN
    if unknown:
        raise ValueError(f"{path}: unknown traffic keys {sorted(unknown)}")
    if cell_path is not None:
        own = json.loads(pathlib.Path(cell_path).read_text())
        if set(own) - CELL_KEYS or "warm" not in own:
            raise ValueError(f"{cell_path}: a cell's file holds 'warm' and of {sorted(CELL_KEYS)} no other key")
        mix.update({k: v for k, v in own.items() if k not in ("why", "knee")})
    if mix["loop"] not in ("open", "closed"):
        raise ValueError(f"{path}: loop must be open or closed")
    rows = mix["lengths_per_100"]
    if len(rows) != 100 or any(len(r) != 2 or min(r) < 1 for r in rows):
        raise ValueError(f"{path}: lengths_per_100 must hold 100 [prompt, output] rows")
    for key in ("prebuilt", "router"):
        if mix.get(key):
            raise NotImplementedError(f"{path}: '{key}' is in the schema but the harness cannot run it yet")
    return mix


def _rng(seed: int, stream: int) -> np.random.Generator:
    # Seeds run past 2**31; SeedSequence takes any non-negative integer.
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def _arrivals(mix: dict, n: int, seconds: float, rng) -> list[float]:
    bursts = mix.get("bursts")
    if not bursts:
        return sorted(rng.uniform(0.0, seconds, n).tolist())
    out: list[float] = []
    t = float(rng.uniform(0.0, bursts["every_s"][0]))
    while len(out) < n:
        size = int(rng.integers(bursts["size"][0], bursts["size"][1] + 1))
        size = min(size, n - len(out))
        out.extend((t + rng.uniform(0.0, bursts["within_s"], size)).tolist())
        t += float(rng.uniform(*bursts["every_s"]))
    # Fold bursts that ran past the window back into it: the count is fixed.
    return sorted(x % seconds for x in out)


def _prompt(rng, length: int, vocab: int, prefix: list[int]) -> list[int]:
    body = rng.integers(1, vocab, size=max(length - len(prefix), 1)).tolist()
    return (prefix + body)[:length] if prefix else body


def _prefixes(mix: dict, vocab: int, rng):
    levels = mix.get("prefix_levels") or []
    if len(levels) > 2:
        raise ValueError("at most two levels of shared prefix")
    if not levels:
        return lambda r: []
    top = [rng.integers(1, vocab, size=levels[0]["tokens"]).tolist() for _ in range(levels[0]["groups"])]
    sub = None
    if len(levels) == 2:
        sub = [[rng.integers(1, vocab, size=levels[1]["tokens"]).tolist()
                for _ in range(levels[1]["groups"])] for _ in top]

    def pick(r) -> list[int]:
        g = int(r.integers(len(top)))
        return top[g] + (sub[g][int(r.integers(len(sub[g])))] if sub else [])

    return pick


def generate(mix: dict, *, seed: int, seconds: float, vocab: int) -> dict:
    """The requests of one run: ``{"loop", "seconds", "lead_in_s", "requests":
    [...], "clients": [[request index, ...], ...]}``. A request is ``{"id",
    "due", "prompt", "max_tokens", "counted", "after", "think_s"}``; ``due``
    is in seconds on the window's clock (negative in the lead-in)."""
    rows = mix["lengths_per_100"]
    lead = float(mix.get("lead_in_s", 0.0))
    # The schedule (which row arrives when) is the mix's own, drawn from its
    # ``schedule_seed``; the run's seed draws the token ids. Two runs with
    # different seeds differed by 41% in the TTFT tail while two runs of one
    # seed agreed within 1%: the arrival pattern is work, so it is fixed.
    sched = int(mix.get("schedule_seed", 0))
    order_rng, ids_rng, time_rng = _rng(sched, 1), _rng(seed, 2), _rng(sched, 3)
    pick_prefix = _prefixes(mix, vocab, _rng(sched, 4))
    requests: list[dict] = []

    def add(row, due, counted, after=None, think=0.0, base=None, cut=1.0):
        n_in, n_out = rows[row % 100]
        prompt = (base + ids_rng.integers(1, vocab, size=n_in).tolist()) if base is not None \
            else _prompt(ids_rng, n_in, vocab, pick_prefix(ids_rng))
        requests.append({"id": len(requests), "due": due, "prompt": prompt,
                         "max_tokens": max(1, int(round(n_out * cut))), "counted": counted,
                         "after": after, "think_s": think})
        return requests[-1]

    if mix["loop"] == "closed":
        clients = int(mix["clients"])
        per_client = int(mix.get("requests_per_client", 8))
        lo, hi = mix.get("first_answer_share", [1.0, 1.0])
        # Every seed offers the same lengths in the same order: client c's first
        # answer is row c cut to rung c of a fixed ladder of shares, its later
        # requests the next rows in table order. In a closed loop the order
        # decides which work lands in the window, so the seed draws only the
        # token ids (and with them the routing): it changes no length and no turn.
        ladder = np.linspace(lo, hi, clients)
        plan = []
        for c in range(clients):
            mine = [add(c, -lead, True, cut=float(ladder[(c * 19) % clients]))["id"]]
            for j in range(1, per_client):
                mine.append(add(clients * j + c, -lead, True)["id"])
            plan.append(mine)
        return {"loop": "closed", "seconds": seconds, "lead_in_s": lead, "requests": requests, "clients": plan}

    rate = float(mix["rate_rps"])
    n, n_lead = int(round(rate * seconds)), int(round(rate * lead))
    sessions = mix.get("sessions")
    if sessions:
        # Session starts take the place of single arrivals; the count of
        # sessions and each one's turns come from the file and the seed.
        starts = sorted(time_rng.uniform(-lead, seconds, int(sessions["count"])).tolist())
        row = 0
        for s0 in starts:
            turns = int(order_rng.integers(sessions["turns"][0], sessions["turns"][1] + 1))
            prev = None
            for t in range(turns):
                think = float(time_rng.uniform(*sessions["think_s"])) if t else 0.0
                base = None
                if prev is not None:  # history: last prompt + a stand-in for its answer
                    base = prev["prompt"] + ids_rng.integers(1, vocab, size=prev["max_tokens"]).tolist()
                prev = add(row, s0, True, after=None if prev is None else prev["id"], think=think, base=base)
                row += 1
        return {"loop": "open", "seconds": seconds, "lead_in_s": lead, "requests": requests, "clients": []}
    perm = order_rng.permutation(n)
    for due, row in zip(_arrivals(mix, n, seconds, time_rng), perm):
        add(int(row), due, True)
    for k, due in enumerate(sorted(time_rng.uniform(-lead, 0.0, n_lead).tolist())):
        add(n + k, due, False)
    requests.sort(key=lambda r: r["due"])
    for i, r in enumerate(requests):
        r["id"] = i
    return {"loop": "open", "seconds": seconds, "lead_in_s": lead, "requests": requests, "clients": []}


def digest(plan: dict) -> str:
    """Stable hash of everything a run offers (same seed -> same digest)."""
    h = hashlib.sha256()
    h.update(json.dumps({k: plan[k] for k in ("loop", "seconds", "lead_in_s", "clients")}, sort_keys=True).encode())
    for r in plan["requests"]:
        h.update(json.dumps([r["due"], r["max_tokens"], r["counted"], r["after"], r["think_s"]]).encode())
        h.update(np.asarray(r["prompt"], np.int64).tobytes())
    return h.hexdigest()


def lengths(plan: dict, *, counted_only: bool = True) -> list[tuple[int, int]]:
    return sorted((len(r["prompt"]), r["max_tokens"]) for r in plan["requests"]
                  if r["counted"] or not counted_only)
