"""The one traffic generator. A traffic mix is a data file under
``benchmark/traffic/``; this module turns it and a seed into inputs.

Two kinds of mix:

``batches``  — a training feed: batch i is a pure function of
  (seed, i), every row different. Token ids follow ``tokens.law``
  (``power``: ``vocab * u**exponent``, a heavily skewed unigram law, so
  a loss can fall; ``uniform``). Labels are ``next_token`` (ids shifted
  by one) or ``first_token_mod`` (a class that is a function of the
  row).
``requests`` — an open-loop serving load. The POPULATION (how many
  requests, the set of prompt lengths, of output lengths and of gaps
  between arrivals) is the file's laws at n evenly spaced quantiles, so
  it is the same for every seed; ``--seed`` alone says in which order
  the lengths and the gaps come (three permutations) and draws the
  token ids. Every seed thus offers the same work in another order.
  The lengths come in ROUNDS (``_in_rounds``): every 16 consecutive
  requests hold one length from each sixteenth of the set, so the part
  of the population that a window gets to serve, and the batch in
  flight at any time, is made up alike under every seed. With a plain
  shuffle the 150 documents of 540 that a backlog run starts would be
  a sample whose mean length swings by 2 % from seed to seed, and the
  step time with it.
"""

from __future__ import annotations

import json
import os

import numpy as np
from scipy import special

_HERE = os.path.dirname(os.path.abspath(__file__))


def load_traffic(name: str) -> dict:
    with open(os.path.join(_HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def _rng(*words) -> np.random.Generator:
    return np.random.default_rng([int(w) % (2 ** 32) for w in words])


# ------------------------------------------------------------- batches
def _tokens(law: dict, rng, shape, vocab: int):
    if law["law"] == "power":
        ids = vocab * rng.random(shape) ** law["exponent"]
        return np.minimum(ids, vocab - 1).astype(np.int32)
    if law["law"] == "uniform":
        return rng.integers(0, vocab, shape, dtype=np.int32)
    raise ValueError(f"unknown token law {law['law']!r}")


def batch(traffic: dict, seed: int, index: int, vocab: int) -> tuple:
    """Batch ``index`` of the feed: (ids, labels) as int32 arrays."""
    rng = _rng(seed, index, 0xBA7C)
    b, s = traffic["batch"], traffic["seq"]
    labels = traffic["labels"]
    if labels["law"] == "next_token":
        t = _tokens(traffic["tokens"], rng, (b, s + 1), vocab)
        return t[:, :-1].copy(), t[:, 1:].copy()
    if labels["law"] == "first_token_mod":
        t = _tokens(traffic["tokens"], rng, (b, s), vocab)
        return t, (t[:, 0] % labels["classes"]).astype(np.int32)
    raise ValueError(f"unknown label law {labels['law']!r}")


def tokens_per_batch(traffic: dict) -> int:
    return traffic["batch"] * traffic["seq"]


# ------------------------------------------------------------ requests
def _quantiles(n: int) -> np.ndarray:
    """The mid-points of n equal strata of (0, 1)."""
    return (np.arange(n) + 0.5) / n


def _lengths(law: dict, n: int) -> np.ndarray:
    """The law at n evenly spaced quantiles, clipped and on the ladder."""
    q = _quantiles(n)
    if law["law"] == "lognormal":
        x = law["median"] * np.exp(law["sigma"] * special.ndtri(q))
    elif law["law"] == "choice":
        w = np.asarray(law.get("weights") or [1.0] * len(law["values"]))
        x = np.asarray(law["values"])[
            np.searchsorted(np.cumsum(w) / w.sum(), q)]
    elif law["law"] == "fixed":
        x = np.full(n, law["value"])
    else:
        raise ValueError(f"unknown length law {law['law']!r}")
    x = np.clip(np.ceil(x), law["min"], law["max"]).astype(np.int64)
    ladder = law.get("round_up_to")
    if ladder:
        ladder = np.asarray(sorted(ladder))
        x = ladder[np.searchsorted(ladder, x)]
    return x


def _gaps(arrival: dict, n: int, horizon: float) -> np.ndarray:
    """n gaps (the first one from time 0) that add up to under
    ``horizon``: the process's gap law at n evenly spaced quantiles."""
    proc = arrival["process"]
    if proc == "at-once":
        return np.zeros(n)
    if proc == "poisson":
        g = -np.log1p(-_quantiles(n))
    elif proc == "gamma-burst":
        g = special.gammaincinv(1.0 / arrival["cv"] ** 2, _quantiles(n))
    else:
        raise ValueError(f"unknown arrival process {proc!r}")
    return g / g.sum() * horizon * n / (n + 1)


ROUND = 16


def _in_rounds(n: int, rng) -> np.ndarray:
    """A seeded order of an ascending set of n: the set is cut into
    ``ROUND`` equal strata, each stratum shuffled, and round r takes
    the r-th element of every stratum, in a shuffled order. Returns the
    indices in the order they come."""
    stratum = np.arange(n) * ROUND // n
    by_stratum = np.lexsort((rng.random(n), stratum))
    first = np.cumsum(np.bincount(stratum, minlength=ROUND)) \
        - np.bincount(stratum, minlength=ROUND)
    turn = np.empty(n, np.int64)
    turn[by_stratum] = np.arange(n) - first[stratum[by_stratum]]
    return np.lexsort((rng.random(n), turn))


def population(traffic: dict, seconds: float) -> dict:
    """The part of a run that no seed changes: n, and the sets of
    lengths and gaps (each in ascending order)."""
    arr = traffic["arrival"]
    horizon = max(seconds - arr.get("quiet_tail_s", 0.0), seconds * 0.5)
    n = max(1, int(round(arr["rate_per_s"] * horizon)))
    return {"n": n, "horizon_s": horizon,
            "prompt_len": _lengths(traffic["prompt_len"], n),
            "output_len": _lengths(traffic["output_len"], n),
            "gaps": _gaps(arr, n, horizon)}


def requests(traffic: dict, seed: int, seconds: float, vocab: int) -> list:
    """[{due_s, prompt, max_new, greedy}] sorted by due time."""
    pop = population(traffic, seconds)
    n = pop["n"]
    rng = _rng(seed, 0x70C5)
    plen = pop["prompt_len"][_in_rounds(n, rng)]
    olen = pop["output_len"][_in_rounds(n, rng)]
    due = np.cumsum(pop["gaps"][rng.permutation(n)])
    share = traffic.get("prefix_sharing") or {}
    groups = int(share.get("groups", 0))
    prefixes = [rng.integers(1, vocab, share["prefix_len"]).tolist()
                for _ in range(groups)]
    out = []
    for k in range(n):
        prompt = rng.integers(1, vocab, int(plen[k])).tolist()
        if groups:
            pre = prefixes[int(rng.integers(groups))][:int(plen[k]) - 1]
            prompt[:len(pre)] = pre
        out.append({"due_s": float(due[k]), "prompt": prompt,
                    "max_new": int(olen[k]), "greedy": True})
    return out
