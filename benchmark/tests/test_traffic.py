"""The generator: every seed offers the same work, as another trace."""
import pytest

import traffic

CHAT = {
    "rate_rps": 1.7, "lead_in_s": 8.0, "tail_s": 15.0,
    "window_tokens": 1024, "stratify": 8,
    "prompt_tokens": {"dist": "lognormal", "median": 256, "sigma": 0.8, "min": 32, "max": 768},
    "output_tokens": {"dist": "lognormal", "median": 128, "sigma": 0.6, "min": 16, "max": 256},
}


def _window(reqs):
    return [r for r in reqs if r.phase == "window"]


def test_two_seeds_same_multiset_count_and_tokens():
    a = traffic.open_loop(CHAT, 3, 51, 32000)
    b = traffic.open_loop(CHAT, 2 ** 31 + 11, 51, 32000)  # the driver's seeds are large
    for phase in ("lead", "window", "tail"):
        pa = sorted((r.prompt_len, r.max_new) for r in a if r.phase == phase)
        pb = sorted((r.prompt_len, r.max_new) for r in b if r.phase == phase)
        assert pa == pb
    wa, wb = _window(a), _window(b)
    assert len(wa) == len(wb) == round(1.7 * 51)  # the count is exact
    assert sum(r.prompt_len + r.max_new for r in wa) == sum(r.prompt_len + r.max_new for r in wb)
    assert [r.prompt_ids for r in wa] != [r.prompt_ids for r in wb]  # other ids
    assert [(r.prompt_len, r.max_new) for r in wa] != [(r.prompt_len, r.max_new) for r in wb]
    assert [r.due for r in wa] != [r.due for r in wb]                # another schedule


def test_arrivals_sorted_inside_their_phase():
    reqs = traffic.open_loop(CHAT, 9, 51, 32000)
    dues = [r.due for r in reqs]
    assert dues == sorted(dues) and [r.idx for r in reqs] == list(range(len(reqs)))
    for r in reqs:
        lo, hi = {"lead": (-8.0, 0.0), "window": (0.0, 51.0), "tail": (51.0, 66.0)}[r.phase]
        assert lo <= r.due < hi
        assert len(r.prompt_ids) == r.prompt_len and max(r.prompt_ids) < 32000
        assert r.prompt_len + r.max_new < 1024


def test_one_arrival_in_every_slot_of_one_over_the_rate():
    import random
    for seed in (1, 2 ** 31 + 7):
        t = traffic.arrival_times(87, 51.0, random.Random(seed))
        assert [int(x * 87 / 51.0) for x in t] == list(range(87))
        gaps = [y - x for x, y in zip(t, t[1:])]
        assert 0.0 < min(gaps) and max(gaps) < 2 * 51.0 / 87


def test_same_seed_same_requests():
    a = traffic.open_loop(CHAT, 5, 51, 32000)
    b = traffic.open_loop(CHAT, 5, 51, 32000)
    assert [(r.due, r.prompt_ids, r.max_new) for r in a] == [(r.due, r.prompt_ids, r.max_new) for r in b]


def test_pairing_does_not_depend_on_seed_and_lengths_follow_the_file():
    pairs = traffic.multiset(CHAT, 200)
    assert pairs == traffic.multiset(CHAT, 200)
    prompts = sorted(p for p, _ in pairs)
    assert prompts[0] >= 32 and prompts[-1] <= 768
    assert 230 <= prompts[100] <= 280  # the median of the file
    outs = [o for _, o in pairs]
    assert outs != sorted(outs)  # paired by a shuffle, not rank with rank
    with pytest.raises(ValueError):
        traffic.quantile_midpoints({"dist": "zipf", "min": 1, "max": 2}, 4)


def test_stratified_order_spreads_the_work_evenly():
    pairs = traffic.multiset(CHAT, 256)
    mean = sum(p for p, _ in pairs) / len(pairs)
    for seed in (1, 2, 3):
        order = traffic.seeded_order(pairs, seed, stratify=8)
        assert sorted(order) == sorted(pairs)
        for start in range(0, 256 - 40, 17):  # any run of 40 consecutive requests
            run = order[start:start + 40]
            assert abs(sum(p for p, _ in run) / 40 - mean) / mean < 0.12
    few = traffic.seeded_order(pairs[:11], 1, stratify=8)  # a lead-in: plainly shuffled
    assert sorted(few) == sorted(pairs[:11])


def test_buckets_reached():
    assert traffic.buckets_reached(CHAT, 51, [32, 128, 512, 1024]) == [128, 512, 1024]
    assert traffic.buckets_reached(dict(CHAT, rate_rps=20.0), 51, [32, 128, 512, 1024]) == [32, 128, 512, 1024]
