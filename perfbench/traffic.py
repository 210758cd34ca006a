"""The one traffic generator. A mix is a data file,
``perfbench/traffic/<name>.json``, whose ``kind`` says which job reads
it (``train`` or ``serve``) and whose other keys are the parameters
read here. Everything is drawn from ``--seed``: the same seed gives the
same batches and requests, another seed other ones.

Token streams come from a bigram walk (copied from
``data/synthetic.py:SyntheticLMDataset``): every token has ``branching``
possible successors, so the text has structure and a loss floor well
under uniform. Lengths are lognormal with a stated mean and coefficient
of variation (copied from ``bench/workload.py:_lognormal_lengths``).

Serving sizes: every seed serves the same multiset of (prompt, output)
sizes, drawn once from the mix's own ``size_seed``; ``--seed`` only
orders them, stratified so that every short stretch holds the same mix
of sizes, and fills in the tokens. So two seeds offer the same work.
"""
from __future__ import annotations

import json
import os

import numpy as np

BRANCHING = 4


def load_mix(root: str, name: str) -> dict:
    with open(os.path.join(root, "perfbench", "traffic", name + ".json")) as f:
        mix = json.load(f)
    if mix.get("kind") not in ("train", "serve"):
        raise ValueError(f"traffic {name!r}: kind {mix.get('kind')!r} is "
                         f"neither 'train' nor 'serve'")
    return mix


def _rng(*words: int) -> np.random.Generator:
    return np.random.default_rng([int(w) for w in words])


def lognormal_lengths(rng: np.random.Generator, n: int, mean: float,
                      cv: float) -> np.ndarray:
    """n integer lengths >= 1 with the requested mean and coefficient
    of variation; cv=0 pins every draw to the mean."""
    if cv <= 0:
        return np.full(n, max(1, int(round(mean))), dtype=np.int64)
    sigma2 = np.log1p(cv * cv)
    mu = np.log(mean) - sigma2 / 2.0
    draws = rng.lognormal(mean=mu, sigma=np.sqrt(sigma2), size=n)
    return np.maximum(1, np.rint(draws).astype(np.int64))


def bigram_table(vocab: int, seed: int) -> np.ndarray:
    """(vocab, BRANCHING) successor table of the seed's token stream."""
    return _rng(seed, 0).integers(0, vocab, size=(vocab, BRANCHING))


def bigram_walk(table: np.ndarray, rng: np.random.Generator, n: int) -> np.ndarray:
    """n tokens of one document: a random start, then successors."""
    vocab = table.shape[0]
    out = np.empty(n, dtype=np.int32)
    out[0] = rng.integers(0, vocab)
    sel = rng.integers(0, BRANCHING, size=n)
    for i in range(1, n):
        out[i] = table[out[i - 1], sel[i]]
    return out


# ------------------------------------------------------------- train --

def packed_batch(mix: dict, cfg: dict, table: np.ndarray, seed: int,
                 step: int):
    """(tokens, labels), each (batch, seq) int32, for global step
    ``step``: every row packs whole documents of lognormal length,
    separated by the model's EOS token, and is cut at ``seq + 1``
    tokens; labels are the tokens shifted by one. Every row of every
    step differs (its own rng stream)."""
    b, s = mix["batch"], mix["seq"]
    eos = cfg["eos_token_id"]
    doc = mix["doc_len"]
    rows = np.empty((b, s + 1), dtype=np.int32)
    for r in range(b):
        rng = _rng(seed, 1, step, r)
        pos = 0
        while pos < s + 1:
            n = int(np.clip(lognormal_lengths(rng, 1, doc["mean"], doc["cv"])[0],
                            doc["min"], doc["max"]))
            n = min(n, s + 1 - pos)
            rows[r, pos:pos + n] = bigram_walk(table, rng, n)
            pos += n
            if pos < s + 1:
                rows[r, pos] = eos
                pos += 1
    return rows[:, :-1], rows[:, 1:]


# ------------------------------------------------------------- serve --

def request_sizes(mix: dict) -> tuple:
    """(prompt_lens, output_lens) of the mix's request pool, the same
    for every seed. Prompt lengths are rounded up to a multiple of
    ``prompt.multiple`` (the prefill chunk), so the window runs one
    chunk shape."""
    n = mix["pool_requests"]
    rng = _rng(mix["size_seed"], 2)
    p, o = mix["prompt"], mix["output"]
    plen = np.clip(lognormal_lengths(rng, n, p["mean"], p["cv"]), p["min"], p["max"])
    m = p["multiple"]
    plen = -(-plen // m) * m
    olen = np.clip(lognormal_lengths(rng, n, o["mean"], o["cv"]), o["min"], o["max"])
    return plen.astype(np.int64), olen.astype(np.int64)


def stratified_order(plen, olen, strata: int, rng: np.random.Generator) -> np.ndarray:
    """An order of the pool in which every run of ``strata`` consecutive
    requests holds one request of each size stratum (the pool sorted by
    prompt plus output length, cut into ``strata`` equal groups), each
    stratum and each run shuffled by ``rng``. So every stretch of a
    window is offered nearly the same mix, whatever the seed."""
    n = len(plen) // strata * strata
    by_size = np.argsort(plen[:n] + olen[:n], kind="stable").reshape(strata, -1)
    cols = np.stack([rng.permutation(row) for row in by_size], axis=1)   # (n/strata, strata)
    return np.concatenate([rng.permutation(block) for block in cols])


class RequestStream:
    """The closed loop's requests in the seed's order: ``next()`` gives
    (prompt int32 array, max_new_tokens). The order is stratified by size
    (:func:`stratified_order`, ``strata`` per run of requests). Prompts
    are made when asked for, from their own rng stream, so a long window
    costs no upfront generation. The pool repeats (in the same order) if
    a window outlasts it."""

    def __init__(self, mix: dict, cfg: dict, seed: int):
        self.plen, self.olen = request_sizes(mix)
        self.order = stratified_order(self.plen, self.olen, mix["strata"], _rng(seed, 3))
        self.table = bigram_table(cfg["vocab_size"], seed)
        self.seed = seed
        self.i = 0

    def next(self):
        k = int(self.order[self.i % len(self.order)])
        rng = _rng(self.seed, 4, self.i)
        self.i += 1
        return bigram_walk(self.table, rng, int(self.plen[k])), int(self.olen[k])
