"""The benchmark's traffic generator: seeded, and shaped as its mixes say."""
import json
import os

import numpy as np
import pytest

from perfbench_tiny import REPO
from perfbench import traffic

SMOLLM2 = json.load(open(os.path.join(REPO, "perfbench", "configs", "smollm2-1.7b.json")))
TRAIN = traffic.load_mix(REPO, "train_packed_2k_b4")
SERVE = traffic.load_mix(REPO, "serve_decode_closed32")
BIG_SEED = 2**31 + 977


def _batch(seed, step, mix=TRAIN):
    table = traffic.bigram_table(SMOLLM2["vocab_size"], seed)
    return traffic.packed_batch(mix, SMOLLM2, table, seed, step)


@pytest.mark.parametrize("seed", [0, BIG_SEED])
def test_packed_batch_same_seed_same_batch(seed):
    a, b = _batch(seed, 3), _batch(seed, 3)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])


def test_packed_batch_other_seed_or_step_differs():
    base = _batch(5, 0)[0]
    assert not np.array_equal(base, _batch(6, 0)[0])
    assert not np.array_equal(base, _batch(5, 1)[0])


def test_packed_batch_shape_labels_and_documents():
    tokens, labels = _batch(11, 2)
    assert tokens.shape == labels.shape == (TRAIN["batch"], TRAIN["seq"])
    assert tokens.dtype == labels.dtype == np.int32
    np.testing.assert_array_equal(tokens[:, 1:], labels[:, :-1])
    assert tokens.min() >= 0 and tokens.max() < SMOLLM2["vocab_size"]
    # packed: several documents per row, EOS between them; rows all differ
    assert all((row == SMOLLM2["eos_token_id"]).sum() >= 1 for row in tokens)
    assert len({row.tobytes() for row in tokens}) == TRAIN["batch"]


def test_bigram_walk_follows_the_table():
    table = traffic.bigram_table(64, 3)
    doc = traffic.bigram_walk(table, np.random.default_rng(0), 200)
    assert all(doc[i + 1] in table[doc[i]] for i in range(199))


@pytest.mark.parametrize("seed", [1, BIG_SEED])
def test_requests_same_seed_same_requests(seed):
    a = traffic.RequestStream(SERVE, SMOLLM2, seed)
    b = traffic.RequestStream(SERVE, SMOLLM2, seed)
    for _ in range(5):
        (pa, na), (pb, nb) = a.next(), b.next()
        np.testing.assert_array_equal(pa, pb)
        assert na == nb


def test_requests_other_seed_same_sizes_other_order_and_tokens():
    a = traffic.RequestStream(SERVE, SMOLLM2, 1)
    b = traffic.RequestStream(SERVE, SMOLLM2, 2)
    # every seed offers the same multiset of sizes
    sizes = lambda s: sorted(zip(s.plen[s.order].tolist(), s.olen[s.order].tolist()))
    assert sizes(a) == sizes(b)
    assert not np.array_equal(a.order, b.order)
    assert not np.array_equal(a.next()[0][:64], b.next()[0][:64])


def test_request_sizes_fit_the_mix():
    plen, olen = traffic.request_sizes(SERVE)
    p, o = SERVE["prompt"], SERVE["output"]
    assert (plen % p["multiple"] == 0).all()
    assert (plen % SERVE["prefill_budget"] == 0).all()     # one chunk shape
    assert plen.min() >= p["min"] and plen.max() <= p["max"]
    assert olen.min() >= o["min"] and olen.max() <= o["max"]
    assert (plen + olen).max() <= SERVE["page_size"] * SERVE["pages_per_seq"]
    assert 400 <= np.median(plen) <= 800 and 200 <= olen.mean() <= 300


def test_lognormal_lengths_mean_and_cv():
    x = traffic.lognormal_lengths(np.random.default_rng(0), 200_000, 256, 0.6)
    assert abs(x.mean() / 256 - 1) < 0.01
    assert abs(x.std() / x.mean() - 0.6) < 0.02


def test_every_run_of_requests_holds_one_of_each_size_stratum():
    stream = traffic.RequestStream(SERVE, SMOLLM2, 9)
    k = SERVE["strata"]
    total = stream.plen + stream.olen
    rank = np.empty(len(total), int)
    rank[np.argsort(total, kind="stable")] = np.arange(len(total))
    stratum = rank // (len(total) // k)
    order = stream.order
    assert sorted(order.tolist()) == list(range(len(total)))
    for j in range(0, len(order), k):
        assert sorted(stratum[order[j:j + k]].tolist()) == list(range(k))
