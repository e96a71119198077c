"""The one general traffic generator: a traffic file's parameters + a seed ->
the requests of a run.

Every seed gets the SAME (prompt length, output length) pairs — a pool drawn
once from the mix's own distributions with a generator seeded by the mix's
``pool_seed`` — in the same order, with other token ids.  So a seed changes
what is said, not how much work a run holds or when (PR 23 measured a 50 %
spread of tokens/s over six seeds while each seed drew its own subset of the
pool, and 0.05 % between two runs of one seed).
"""

import numpy as np


def _clip(x, lo, hi):
    return int(min(max(int(round(x)), lo), hi))


def length_pool(traffic):
    """[(prompt_len, output_len)] * pool_size, the same for every seed."""
    rng = np.random.default_rng(int(traffic["pool_seed"]))
    p, o = traffic["prompt_len"], traffic["output_len"]
    pool = []
    for _ in range(int(traffic["pool_size"])):
        if p["dist"] == "lognormal":
            n = rng.lognormal(np.log(p["median"]), p["sigma"])
        elif p["dist"] == "fixed":
            n = p["value"]
        else:
            raise ValueError(f"prompt_len.dist {p['dist']!r}")
        if o["dist"] == "geometric":
            m = rng.geometric(1.0 / o["mean"])
        elif o["dist"] == "fixed":
            m = o["value"]
        else:
            raise ValueError(f"output_len.dist {o['dist']!r}")
        pool.append((_clip(n, p["min"], p["max"]),
                     _clip(m, o["min"], o["max"])))
    return pool


class RequestStream:
    """The requests of a closed loop of sessions.  The ``j``-th request of
    session ``s`` has the length pair ``(s + j * sessions) % pool_size`` of the
    pool, for every seed: the seed decides the token ids and nothing about
    the amount of work or its order.  (PR 23 measured it: with a seeded
    permutation of which session sends which pair, tokens/s fell into two
    modes 1.7 % apart by seed; two runs of one order agree within 0.05 %.)"""

    def __init__(self, traffic, vocab_size, seed):
        self.pool = length_pool(traffic)
        self.vocab = int(vocab_size)
        self.sessions = int(traffic["sessions"])
        self.rng = np.random.default_rng([int(seed), 3])
        self.sent = [0] * self.sessions

    def next(self, session):
        i = (session + self.sent[session] * self.sessions) % len(self.pool)
        self.sent[session] += 1
        n, m = self.pool[i]
        return self.rng.integers(0, self.vocab, size=n).tolist(), m


def check_requests(traffic, vocab_size, seed):
    """The requests of the reference check: the longest prompt the mix
    allows, its median and its shortest, seeded token ids."""
    p = traffic["prompt_len"]
    lengths = [p["max"], int(p.get("median", p.get("value", p["max"]))),
               p["min"]]
    rng = np.random.default_rng([int(seed), 4])
    return [rng.integers(0, int(vocab_size), size=n).tolist()
            for n in lengths]
