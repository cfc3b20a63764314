"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests it finished, drawn
from the seed and always holding the longest, is run through the plain
reference: each prompt with its served tokens. For every served token the
number compared is its gap, ``max(reference logits) - reference logit of
the served token`` at the position that produced it; the run is correct
when the widest gap stays under the configuration's limit. Greedy decoding
is what the program serves, so an exact server shows gaps at rounding level.

The control puts the reference, computed in the next precision below the
configuration's (``CONTROL``), in the program's place and reads the gap of
the token that lower precision puts first (``control_gaps``).
"""
from __future__ import annotations

import numpy as np

N_SAMPLE = 8
CONTROL = {"bfloat16": "fp8", "float16": "fp8", "float32": "bf16"}


def sample(records, seed: int, n: int = N_SAMPLE) -> list:
    """Up to ``n`` finished requests: the longest, then a seeded draw."""
    done = [r for r in records if r.finished]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.tokens), -r.rid))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 7])
    pick = rng.permutation(len(rest))[:n - 1]
    return [longest] + [rest[i] for i in sorted(pick)]


def _rows(recs, length: int, n: int = N_SAMPLE):
    """Token rows (prompt + served tokens but the last), prompt lengths,
    and per row the positions that produced each served token."""
    toks = np.zeros((n, length), np.int32)
    lens = np.ones(n, np.int32)
    where = []
    for i, r in enumerate(recs):
        seq = np.concatenate([np.asarray(r.prompt, np.int32),
                              np.asarray(r.tokens[:-1], np.int32)])
        toks[i, :len(seq)] = seq
        lens[i] = len(r.prompt)
        S = len(r.prompt)
        where.append(np.arange(S - 1, S - 1 + len(r.tokens)))
    return toks, lens, where


def _logits(h, head, i, pos):
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        return jnp.asarray(h[i, pos]) @ head


def gaps(reference, config, seed: int, recs, length: int) -> np.ndarray:
    """Gap of every served token of ``recs`` under the reference."""
    import jax.numpy as jnp
    toks, lens, where = _rows(recs, length)
    h, head = reference.hidden(config["arch"], seed, toks, lens,
                               config["serve"]["prefill_capacity_factor"])
    out = []
    for i, r in enumerate(recs):
        lg = _logits(h, head, i, where[i])
        served = jnp.asarray(np.asarray(r.tokens, np.int32))
        out.append(np.asarray(lg.max(-1)
                              - jnp.take_along_axis(lg, served[:, None],
                                                    -1)[:, 0]))
    return np.concatenate(out) if out else np.zeros(0)


def control_gaps(reference, config, seed: int, recs, length: int):
    """Gap, under the float32 reference, of the token that the reference in
    the control's precision puts first at each served position of ``recs``."""
    import jax.numpy as jnp
    toks, lens, where = _rows(recs, length)
    cf = config["serve"]["prefill_capacity_factor"]
    h, head = reference.hidden(config["arch"], seed, toks, lens, cf)
    hq, headq = reference.hidden(config["arch"], seed, toks, lens, cf,
                                 quant=CONTROL[config["arch"]["dtype"]])
    out = []
    for i in range(len(recs)):
        lg = _logits(h, head, i, where[i])
        pick = jnp.argmax(_logits(hq, headq, i, where[i]), -1)
        out.append(np.asarray(lg.max(-1)
                              - jnp.take_along_axis(lg, pick[:, None],
                                                    -1)[:, 0]))
    return np.concatenate(out) if out else np.zeros(0)


def verdict(g: np.ndarray, limit: float) -> dict:
    """The numbers a run prints: the widest gap beside its limit."""
    widest = float(g.max()) if len(g) else float("inf")
    return {"max_logit_gap": widest, "limit": float(limit),
            "tokens": int(len(g)),
            "median_gap": float(np.median(g)) if len(g) else None,
            "exact_share": float(np.mean(g == 0)) if len(g) else None,
            "correct": bool(len(g) > 0 and widest <= limit)}
