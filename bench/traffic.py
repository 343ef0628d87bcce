"""The one traffic generator: a mix file of parameters in, sessions out.

A mix (``bench/traffic/<name>.json``) gives the loop (``open`` with a
session rate, or ``closed`` with a number of clients), the length
distributions, shared prefixes and multi-turn sessions.  The sizes,
arrival times and prefix choices are drawn from the mix's own
``pool_seed``, so every seed replays the same schedule; the run's seed
draws the token ids (and, in the harness, the weights).  Two seeds do
the same work, and the spread between seeds is the system's and not
the generator's: a p95 over some tens of requests moves with the
arrival order far more than with the chip.

Length specs: ``{"dist": "const", "value": n}``,
``{"dist": "uniform", "min": a, "max": b}`` (integers, inclusive),
``{"dist": "loguniform", "min": a, "max": b}`` and
``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np


def draw_lengths(spec: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    kind = spec["dist"]
    if kind == "const":
        x = np.full(n, float(spec["value"]))
    elif kind == "uniform":
        x = rng.integers(spec["min"], spec["max"] + 1, n).astype(float)
    elif kind == "loguniform":
        x = np.exp(rng.uniform(np.log(spec["min"]), np.log(spec["max"]), n))
    elif kind == "lognormal":
        x = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    lo, hi = spec.get("min", 1), spec.get("max", np.inf)
    return np.clip(np.round(x), lo, hi).astype(np.int64)


@dataclass
class Turn:
    question: int               # new user tokens in this turn
    answer: int                 # max_new_tokens of the reply
    think_s: float = 0.0        # pause after the previous answer ends


@dataclass
class Session:
    arrival_s: float            # due time of the first turn (open loop)
    prefix: int                 # shared prefix id, -1 for none
    document: int               # document tokens (0 for none)
    turns: list = field(default_factory=list)


@dataclass
class Plan:
    loop: str
    sessions: list              # open: by arrival; closed: work queue
    prefixes: list              # token arrays of the shared prefixes
    clients: int = 0
    max_total: int = 0
    seed: int = 0


def _zipf_counts(n: int, k: int, s: float, rng) -> np.ndarray:
    """Prefix id of each of ``n`` sessions: ranks drawn with Zipf(s)
    popularity over ``k`` prefixes."""
    w = 1.0 / np.arange(1, k + 1) ** s
    return rng.choice(k, size=n, p=w / w.sum())


def _sessions(mix: dict, n: int, rng) -> list:
    doc = (draw_lengths(mix["document"], rng, n) if "document" in mix
           else np.zeros(n, np.int64))
    turns = mix.get("turns", {})
    n_turns = rng.integers(turns.get("min", 1), turns.get("max", 1) + 1, n)
    total = int(n_turns.sum())
    q = draw_lengths(mix["question"], rng, total)
    a = draw_lengths(mix["answer"], rng, total)
    think = rng.exponential(mix.get("think_s", 0.0), total)
    sp = mix.get("shared_prefixes")
    prefix = (_zipf_counts(n, sp["count"], sp["zipf_s"], rng) if sp
              else np.full(n, -1))
    out, j = [], 0
    for i in range(n):
        turns = [Turn(int(q[j + t]), int(a[j + t]),
                      0.0 if t == 0 else float(think[j + t]))
                 for t in range(int(n_turns[i]))]
        j += int(n_turns[i])
        out.append(Session(0.0, int(prefix[i]), int(doc[i]), turns))
    return out


def make_plan(mix: dict, seed: int, seconds: float, vocab: int) -> Plan:
    """Sessions for one run of ``seconds``.  Open loop: exactly
    ``round(rate * seconds)`` sessions due inside the window, at the
    times of a Poisson process of that many arrivals.  Closed loop:
    ``requests`` single-turn sessions, taken in order."""
    pool = np.random.default_rng(mix["pool_seed"])
    run = np.random.default_rng(seed)
    if mix["loop"] == "open":
        n = max(1, int(round(mix["rate_per_s"] * seconds)))
        sessions = _sessions(mix, n, pool)
        for s, t0 in zip(sessions, np.sort(pool.uniform(0.0, seconds, n))):
            s.arrival_s = float(t0)
    elif mix["loop"] == "closed":
        sessions = _sessions(mix, mix["requests"], pool)
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    sp = mix.get("shared_prefixes")
    prefixes = ([run.integers(0, vocab, sp["tokens"], dtype=np.int32)
                 for _ in range(sp["count"])] if sp else [])
    return Plan(mix["loop"], sessions, prefixes,
                clients=int(mix.get("clients", 0)),
                max_total=int(mix["max_total_tokens"]), seed=seed)


def tokens(rng: np.random.Generator, vocab: int, n: int) -> np.ndarray:
    return rng.integers(0, vocab, n, dtype=np.int32)
