"""Training traffic: the rows a run trains on, made from the seed.

One generator reads every traffic file (``workloads/<traffic>.json``):

    seq_len, global_batch     rows of seq_len tokens, b per trained step
    presample_ratio           B = ratio·b candidates scored per step
    scheme                    "presample" (importance sampling) or "uniform"
    tau_th                    the τ gate's threshold (held open when tiny)
    easy_per_block, block     easy rows per block of consecutive ids
    states, branch            the Markov chain easy rows walk
    probe_rows                held-out rows the probe loss is read on (whole
                              blocks, so the probe keeps the mix exactly)
    optim                     the job's AdamW settings

An easy row is a stretch, at a random offset, of one long walk on a
sparse random Markov chain over ``states`` of the vocabulary's ids: each
id is followed by one of its ``branch`` successors, uniformly. The rows
are learnable down to ln(branch) nats a token, far more than a short
window learns; and every row is distinct (each visits its own thousand-odd
of the states), so each trained row leaves its own mark on the gradient.
The walk is made once, when the rows are, so that a row costs a slice and
not a walk inside the measured window. The other rows are iid uniform
tokens (irreducible entropy). Within every block of ``block`` consecutive ids exactly
``easy_per_block`` are easy, at positions drawn from the seed, so every
seed trains on the same mix in another order. Row ``i`` depends only on
(seed, i), so any row can be made again, by the reference, in any order.
Rows are ids 0, 1, 2, ... in training order; the probe rows are the last
whole blocks of the id space, which no window reaches.

The structure follows the program's ``SyntheticLM``
(``src/repro/data/pipeline.py``), whose easy rows sit in contiguous runs
of 700 ids, so that a short window would see no iid row at all.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

N_ROWS = 1 << 24
WALK = 1 << 20             # tokens in the walk easy rows are cut from
TRAFFIC_DIR = Path(__file__).resolve().parent.parent / "workloads"


def load(name):
    with open(TRAFFIC_DIR / f"{name}.json") as f:
        return json.load(f)


class Rows:
    """Index-addressable rows of one run: ``gather(ids)`` →
    {"tokens", "labels"} (int32, (n, seq_len)); labels are the tokens
    shifted by one (next-token prediction, every position supervised)."""

    def __init__(self, traffic, vocab, seed):
        self.t = traffic
        self.vocab = int(vocab)
        self.seq = int(traffic["seq_len"])
        self.seed = int(seed)
        self.n = N_ROWS
        k, d = int(traffic["states"]), int(traffic["branch"])
        if k > self.vocab:
            raise ValueError(f"{k} Markov states for a vocabulary of "
                             f"{self.vocab}")
        r = np.random.default_rng(np.random.SeedSequence([self.seed, 777]))
        ids = r.permutation(self.vocab)[:k].astype(np.int32)
        nxt = r.integers(0, k, size=(k, d)).reshape(-1).tolist()
        x = int(r.integers(0, k))
        walk = [x]
        for j in r.integers(0, d, size=WALK - 1).tolist():
            x = nxt[d * x + j]
            walk.append(x)
        self.walk = ids[np.asarray(walk)]

    def is_easy(self, i):
        blk = int(self.t["block"])
        r = np.random.default_rng(np.random.SeedSequence(
            [self.seed, 991, int(i) // blk]))
        return int(i) % blk in r.permutation(blk)[:self.t["easy_per_block"]]

    def row(self, i):
        r = np.random.default_rng(np.random.SeedSequence([self.seed, 1,
                                                          int(i)]))
        n = self.seq + 1
        if self.is_easy(i):
            at = int(r.integers(0, WALK - n))
            return self.walk[at:at + n]
        return r.integers(0, self.vocab, size=(n,)).astype(np.int32)

    def gather(self, ids):
        rows = np.stack([self.row(i) for i in np.asarray(ids, np.int64)])
        return {"tokens": rows[:, :-1], "labels": rows[:, 1:]}

    def probe_ids(self):
        blk = int(self.t["block"])
        end = self.n // blk * blk
        return np.arange(end - int(self.t["probe_rows"]), end)


def pool_ids(traffic, step):
    """Ids of the rows a step draws from, in order: the B candidates of an
    importance-sampling step, or the b rows of a uniform one."""
    b = int(traffic["global_batch"])
    size = b * int(traffic["presample_ratio"]) \
        if traffic["scheme"] == "presample" else b
    return np.arange(step * size, (step + 1) * size, dtype=np.int64)
