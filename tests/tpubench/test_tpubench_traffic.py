"""The stratified traffic generator."""
import json
import os

import numpy as np
import pytest

from tpubench import trafficgen as tg

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEEDS = [0, 7, 2 ** 31 + 12345]


def _traffic(name):
    with open(os.path.join(REPO, "tpubench", "traffic", name + ".json")) as f:
        return json.load(f)


OFFLINE = _traffic("serve-offline-decode")
LOGNORMAL = {"block": 32,
             "prompt": {"dist": "lognormal", "median": 128, "sigma": 0.9,
                        "min": 32, "max": 768,
                        "buckets": [32, 48, 64, 80, 112, 144, 192, 256, 336,
                                    448, 592, 768]},
             "answer": {"dist": "lognormal", "median": 32, "sigma": 0.8,
                        "min": 8, "max": 256}}


@pytest.mark.parametrize("mix", [OFFLINE, LOGNORMAL],
                         ids=["offline-decode", "lognormal"])
@pytest.mark.parametrize("seed", SEEDS)
def test_every_seed_offers_the_same_multisets_in_another_order(seed, mix):
    prompts, answers = tg.block_multisets(mix)
    n = mix["block"]
    assert len(prompts) == len(answers) == n
    reqs = tg.backlog(mix, seed, 3 * n, 50304, 1024)
    assert [r["index"] for r in reqs] == list(range(3 * n))
    for b in range(3):
        blk = reqs[b * n:(b + 1) * n]
        assert {r["block"] for r in blk} == {b}
        assert sorted(len(r["prompt"]) for r in blk) == prompts
        assert sorted(r["answer_len"] for r in blk) == answers
        assert all(1 <= t < 50304 for r in blk for t in r["prompt"])
    other = tg.backlog(mix, seed + 1, 3 * n, 50304, 1024)
    assert [len(r["prompt"]) for r in other] != \
        [len(r["prompt"]) for r in reqs]
    assert [r["prompt"] for r in other] != [r["prompt"] for r in reqs]
    assert tg.backlog(mix, seed, 3 * n, 50304, 1024) == reqs


def test_the_offline_cell_is_what_perf_md_says():
    prompts, answers = tg.block_multisets(OFFLINE)
    assert set(prompts) == set(OFFLINE["prompt"]["buckets"])
    # whole 16-token prefill buckets: one compiled program per padded length
    assert all(p % 16 == 0 for p in prompts)
    assert min(prompts) == 96 and max(prompts) == 160
    assert set(answers) == {384}
    assert max(prompts) + max(answers) <= 1024


def test_midpoints_and_buckets():
    assert tg.quantile_midpoints({"dist": "uniform", "min": 0, "max": 4},
                                 4) == [0.5, 1.5, 2.5, 3.5]
    assert tg.quantile_midpoints({"dist": "fixed", "value": 384}, 3) == \
        [384.0] * 3
    ln = tg.quantile_midpoints({"dist": "lognormal", "median": 100,
                                "sigma": 1.0, "min": 50, "max": 200}, 9)
    assert ln[4] == pytest.approx(100.0) and ln[0] == 50 and ln[-1] == 200
    assert tg.to_buckets([10, 39, 40, 41, 900], [32, 48, 64]) == \
        [32, 32, 32, 48, 64]
    with pytest.raises(ValueError):
        tg.quantile_midpoints({"dist": "zipf"}, 4)


def test_a_request_that_would_be_cut_is_refused():
    mix = {**OFFLINE, "answer": {"dist": "fixed", "value": 900}}
    with pytest.raises(ValueError, match="no request is cut"):
        tg.backlog(mix, 0, 32, 50304, 1024)


def test_backlog_is_the_multiset_repeated():
    mix = _traffic("serve-offline-decode")
    b = tg.backlog(mix, 3, 128, 50304, 1024)
    assert len(b) == 128 and all(r["answer_len"] == 384 for r in b)
    first = sorted(len(r["prompt"]) for r in b[:32])
    assert first == sorted(len(r["prompt"]) for r in b[32:64])
    assert set(first) == {96, 112, 128, 144, 160}


@pytest.mark.parametrize("seed", SEEDS)
def test_markov_tokens(seed):
    chain = tg.MarkovTokens(seed, 1000, successors=3)
    a = chain.batch(8, 256)
    assert a.shape == (8, 256) and a.dtype == np.int32
    assert a.min() >= 0 and a.max() < 1000
    # every token is followed by one of its few successors
    follow = {}
    for row in a:
        for x, y in zip(row, row[1:]):
            follow.setdefault(int(x), set()).add(int(y))
    assert max(len(v) for v in follow.values()) <= 3
    b = tg.MarkovTokens(seed, 1000, successors=3).batch(8, 256)
    assert (a == b).all()
    assert not (chain.batch(8, 256) == a).all()      # fresh batches
