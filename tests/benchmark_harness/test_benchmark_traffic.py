"""The one traffic generator: the offered work is the same for every seed."""

import collections
import os

import numpy as np
import pytest

from benchmark import harness, traffic

MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(harness.HERE, "traffic"))
               if f.endswith(".json"))
CLOSED = [m for m in MIXES if traffic.load(m).get("loop") == "closed"]


@pytest.mark.parametrize("mix", CLOSED)
def test_the_length_multiset_is_the_same_for_two_seeds(mix):
    t = traffic.load(mix)
    a, b = traffic.request_order(t, 1), traffic.request_order(t, 2 ** 31 + 7)
    assert collections.Counter(a) == collections.Counter(b) == \
        collections.Counter(traffic.length_pairs(t))
    assert a != b
    ra = traffic.request(t, 1, 3, 32768, a)
    rb = traffic.request(t, 2 ** 31 + 7, 3, 32768, b)
    assert ra["ids"] != rb["ids"]
    assert len(ra["ids"]) == a[3][0] and ra["max_new_tokens"] == a[3][1]
    assert traffic.request(t, 1, 3, 32768) == ra        # the seed fixes it all


@pytest.mark.parametrize("mix", CLOSED)
def test_a_second_cycle_repeats_the_lengths_with_new_ids(mix):
    t = traffic.load(mix)
    n = len(traffic.length_pairs(t))
    first, again = traffic.request(t, 5, 0, 1000), traffic.request(t, 5, n, 1000)
    assert len(first["ids"]) == len(again["ids"])
    assert first["max_new_tokens"] == again["max_new_tokens"]
    assert first["ids"] != again["ids"]
    assert min(first["ids"]) >= 1 and max(first["ids"]) < 1000


def test_chat_mix_is_the_issues():
    t = traffic.load("chat-closed32")
    pairs = traffic.length_pairs(t)
    assert len(pairs) == 256 and t["clients"] == 32
    assert abs(np.mean([p for p, _ in pairs]) - 286) < 2
    assert abs(np.mean([o for _, o in pairs]) - 179) < 2
    assert min(p for p, _ in pairs) >= 32 and max(p for p, _ in pairs) <= 1024
    assert max(p + o for p, o in pairs) + 1 <= 2048


def test_doc_mix_fits_the_engine():
    pairs = traffic.length_pairs(traffic.load("doc-closed16"))
    assert min(p for p, _ in pairs) >= 1024
    assert max(p + o for p, o in pairs) + 1 <= 2048


def test_a_shared_prefix_is_shared_and_nothing_else_is():
    t = dict(traffic.load("chat-closed32"), shared_prefix_len=20)
    a, b = traffic.request(t, 9, 0, 5000), traffic.request(t, 9, 1, 5000)
    k = min(20, len(a["ids"]) - 1, len(b["ids"]) - 1)
    assert a["ids"][:k] == b["ids"][:k] and a["ids"][k:k + 8] != b["ids"][k:k + 8]


def test_a_jobs_units_are_whole_chunks_fixed_by_the_seconds():
    t = traffic.load("fit-depthwise")
    assert traffic.job_units(t, 30) == 75
    assert traffic.job_units(t, 10) == 25
    assert traffic.job_units(t, 1) == 25
    assert traffic.job_units(t, 51) == 125


def test_quantiles_are_not_draws():
    d = {"dist": "loguniform", "lo": 10, "hi": 1000, "quantiles": 4}
    assert traffic.quantile_lengths(d) == traffic.quantile_lengths(d) == \
        [18, 56, 178, 562]
    with pytest.raises(ValueError):
        traffic.quantile_lengths({"dist": "zipf", "quantiles": 2})


@pytest.mark.parametrize("mix", CLOSED)
def test_every_block_of_a_stratified_cycle_holds_the_same_tokens(mix):
    t = traffic.load(mix)
    if t.get("order") != "stratified":
        pytest.skip("plain permutation")
    q = t["prompt_len"]["quantiles"]
    plens, olens = (sorted(traffic.quantile_lengths(t[k]))
                    for k in ("prompt_len", "output_len"))
    for seed in (1, 2 ** 31 + 7):
        order = traffic.request_order(t, seed)
        for b in range(q):
            block = order[b * q:(b + 1) * q]
            assert sorted(p for p, _ in block) == plens
            assert sorted(o for _, o in block) == olens
