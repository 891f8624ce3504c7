"""The one general load generator: a traffic file's parameters + ``--seed``
-> requests and their due times. No jax, no program.

A traffic file fixes ONE schedule: the request sizes and the gaps between
arrivals are the stratified quantiles of the file's distributions, in an
order shuffled once from the file's ``schedule_seed``. Every ``--seed``
replays that schedule with token ids of its own, so a seed never changes
how much work a run holds or which request queues behind which. Fields of
a traffic file are listed in ``benchmarks/README.md``.
"""

from __future__ import annotations

import math
import random
from typing import List

import numpy as np

SEED_SPACE = 2 ** 31 - 1  # the program's RNGs take 31 bits; --seed may hold more


def fold_seed(seed: int) -> int:
    return int(seed) % SEED_SPACE


def stratified(dist: dict, n: int) -> List[int]:
    """``n`` whole numbers at the quantile midpoints of ``dist``: the same
    list for every call, ascending."""
    kind = dist["dist"]
    if kind == "const":
        return [int(dist["value"])] * n
    if kind != "log_uniform":
        raise ValueError(f"unknown distribution {kind!r}")
    lo, hi = float(dist["min"]), float(dist["max"])
    vals = [math.exp(math.log(lo) + (i + 0.5) / n * (math.log(hi) - math.log(lo)))
            for i in range(n)]
    return [int(min(hi, max(lo, round(v)))) for v in vals]


def prompt_tokens(vocab: int, seed: int, index: int, length: int) -> List[int]:
    """Token ids of request ``index``: distinct per request and per seed."""
    rs = np.random.RandomState((seed * 7919 + index * 104729 + 1) % 2 ** 32)
    return [int(t) for t in rs.randint(0, vocab, size=length)]


def request_count(traffic: dict, seconds: float) -> int:
    """Requests an open loop sends in a window of ``seconds``."""
    return max(1, int(round(float(traffic["rate_per_s"]) * seconds)))


class RequestStream:
    """The ``n`` requests of one window, by index: prompt sizes at the
    quantiles of ``prompt_tokens`` in the file's one shuffled order, each
    with one of the quantiles of ``output_tokens``, shuffled on their own."""

    def __init__(self, traffic: dict, vocab: int, seed: int, n: int):
        self.vocab = vocab
        self.seed = fold_seed(seed)
        base = int(traffic.get("schedule_seed", 0)) * 1000003
        prompts = stratified(traffic["prompt_tokens"], n)
        outputs = stratified(traffic["output_tokens"], n)
        random.Random(base).shuffle(prompts)
        random.Random(base + 1).shuffle(outputs)
        self.sizes = list(zip(prompts, outputs))

    def request(self, index: int) -> dict:
        n_prompt, n_out = self.sizes[index]
        return {"prompt": prompt_tokens(self.vocab, self.seed, index, n_prompt),
                "max_tokens": n_out}


def arrival_times(traffic: dict, seconds: float) -> List[float]:
    """Due times (seconds from the window's start) of an open loop at
    ``rate_per_s`` over ``seconds``: ``round(rate * seconds)`` requests whose
    gaps are the stratified quantiles of an exponential distribution, scaled
    to fill the window exactly, in the file's one shuffled order. A request
    is due at the START of its gap: the first at 0."""
    n = request_count(traffic, seconds)
    gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = seconds / sum(gaps)
    gaps = [g * scale for g in gaps]
    random.Random(int(traffic.get("schedule_seed", 0)) * 2654435761 % 2 ** 32
                  ).shuffle(gaps)
    due, t = [], 0.0
    for g in gaps:
        due.append(t)
        t += g
    return due
