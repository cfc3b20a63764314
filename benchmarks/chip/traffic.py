"""The one traffic generator. A mix is a JSON file of parameters under
``traffic/``; every seed gets the same multiset of sizes, in its own order.

Tokens come from the task mixture of ``repro.serving.workload`` (copied
here so that the yardstick does not move with the program): each task draws
Zipf-skewed tokens from its own slice of the vocabulary, so a random router
still sees task-clustered expert activations.

Lengths are stratified: a block of ``block`` requests takes prompt lengths
at the midpoints of ``block`` equal-probability strata of the stated
distribution, output lengths likewise, and tasks in equal shares. Each block
is shuffled by the seed. A window of a few blocks then holds nearly the
same work under every seed, and differs in order and in tokens.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np


def _task_token_sampler(vocab: int, n_tasks: int, zipf_a: float,
                        vocab_frac: float, task: int):
    """Task ``task`` draws tokens Zipf(``zipf_a``)-skewed from its own
    ``vocab_frac`` slice of the vocabulary (fixed per task)."""
    width = max(8, int(vocab * vocab_frac))
    start = ((task * (vocab - width)) // max(1, n_tasks - 1)
             if n_tasks > 1 else 0)
    ranks = np.arange(1, width + 1, dtype=np.float64)
    probs = ranks ** -zipf_a
    probs /= probs.sum()
    perm = np.random.default_rng(1000 + task).permutation(width)

    def sample(n: int, rng: np.random.Generator) -> np.ndarray:
        local = rng.choice(width, size=n, p=probs)
        return (start + perm[local]).astype(np.int32)
    return sample


def strata(lo: int, hi: int, n: int, dist: str) -> list:
    """``n`` lengths at the midpoints of equal-probability strata of a
    ``uniform`` or ``log_uniform`` distribution over [lo, hi]."""
    out = []
    for j in range(n):
        q = (j + 0.5) / n
        if dist == "log_uniform":
            v = math.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))
        elif dist == "uniform":
            v = lo + q * (hi - lo)
        else:
            raise ValueError(f"unknown length distribution {dist!r}")
        out.append(int(round(v)))
    return out


@dataclass(frozen=True)
class Request:
    task: int
    prompt: np.ndarray        # int32 token ids
    max_new: int


class Traffic:
    """Closed-loop request source for one mix and one seed."""

    def __init__(self, params: dict, vocab: int, seed: int):
        if params["loop"] != "closed" or params["users"] != 1 \
                or params["batch"] != 1:
            raise ValueError("this generator drives one closed-loop user at "
                             "batch one")
        self.p = params
        self.seed = int(seed)
        n = params["n_tasks"]
        self.samplers = [
            _task_token_sampler(vocab, n, params["zipf_a"],
                                params["task_vocab_frac"], t)
            for t in range(n)]
        if params["tasks"] == "mixed":
            if params["block"] % n:
                raise ValueError("block must hold every task equally")
            self.block_tasks = [j % n for j in range(params["block"])]
        elif params["tasks"] == "topic":
            self.block_tasks = [self.seed % n] * params["block"]
        else:
            raise ValueError(f"unknown tasks {params['tasks']!r}")
        lo, hi = params["prompt_len"]
        self.block_prompts = strata(lo, hi, params["block"],
                                    params["prompt_dist"])
        lo, hi = params["output_len"]
        self.block_outputs = strata(lo, hi, params["block"],
                                    params["output_dist"])

    def _rng(self, stream: int) -> np.random.Generator:
        return np.random.default_rng([self.seed, stream])

    def max_prompt(self) -> int:
        return self.p["prompt_len"][1]

    def max_new(self) -> int:
        return self.p["output_len"][1]

    def warmup(self) -> list:
        """One request at each power of two in the prompt range and at its
        top, so every prefill shape the window reaches compiles here."""
        lo, hi = self.p["prompt_len"]
        lens = sorted({1 << k for k in range(lo.bit_length() - 1,
                                             hi.bit_length())
                       if lo <= 1 << k <= hi} | {lo, hi})
        rng = self._rng(0)
        out = []
        for j, plen in enumerate(lens):
            task = self.block_tasks[j % len(self.block_tasks)]
            out.append(Request(task, self.samplers[task](plen, rng),
                               self.p["warmup_outputs"]))
        return out

    def requests(self):
        """Endless stream of measured requests, block by block."""
        rng = self._rng(1)
        while True:
            tasks = rng.permutation(self.block_tasks)
            prompts = rng.permutation(self.block_prompts)
            outputs = rng.permutation(self.block_outputs)
            for task, plen, olen in zip(tasks, prompts, outputs):
                task = int(task)
                yield Request(task, self.samplers[task](int(plen), rng),
                              int(olen))
