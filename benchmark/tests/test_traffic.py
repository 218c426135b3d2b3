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


# -- "placement": "ring" (PR 54): the order and the places are part of the work

RING = dict(CHAT, rate_rps=1.4, placement="ring")


def _turn(reqs):
    """A run's window as (prompt, output, place inside the slot) by slot."""
    w = _window(reqs)
    slot = 51.0 / len(w)
    return [(r.prompt_len, r.max_new, round(r.due / slot - i, 9)) for i, r in enumerate(w)]


def test_the_default_placement_draws_what_it_drew_before_the_ring():
    import hashlib
    import json
    spec = dict(CHAT, rate_rps=2.5)  # lfm2.chat's: the digests are the parent's generator's
    for seed, digest in ((3, "c9c328b90863e250"), (2 ** 31 + 11, "16963e365234b32d")):
        r = traffic.open_loop(spec, seed, 51, 65536)
        text = json.dumps([(q.phase, q.prompt_len, q.max_new, q.due, q.prompt_ids) for q in r])
        assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest
    with pytest.raises(ValueError):
        traffic.open_loop(dict(CHAT, placement="clumped"), 1, 51, 32000)


def test_a_ring_is_the_same_turn_for_every_seed_begun_elsewhere():
    a, b = (_turn(traffic.open_loop(RING, s, 51, 32000)) for s in (3, 2 ** 31 + 11))
    assert len(a) == len(b) == 71 and a != b
    k = b.index(a[0])
    assert b[k:] + b[:k] == a  # sizes AND places: who meets whom never changes
    assert sorted((p, o) for p, o, _ in a) == sorted(traffic.multiset(RING, 71))
    ids = [[r.prompt_ids for r in _window(traffic.open_loop(RING, s, 51, 32000))] for s in (3, 4)]
    assert ids[0] != ids[1]  # the token ids are the seed's
    assert traffic.open_loop(RING, 5, 51, 32000)[7].prompt_ids == \
        traffic.open_loop(RING, 5, 51, 32000)[7].prompt_ids


@pytest.mark.parametrize("rate", [1.4, 2.2, 3.9])
def test_a_ring_s_lead_in_and_tail_are_its_own_neighbours(rate):
    spec = dict(RING, rate_rps=rate)
    n = round(rate * 51)
    slot = 51.0 / n
    for seed in (1, 2 ** 31 + 7):
        reqs = traffic.open_loop(spec, seed, 51, 32000)
        assert [r.due for r in reqs] == sorted(r.due for r in reqs)
        assert [r.idx for r in reqs] == list(range(len(reqs)))
        w = _window(reqs)
        lead = [r for r in reqs if r.phase == "lead"]
        tail = [r for r in reqs if r.phase == "tail"]
        assert len(w) == n and len(lead) == int(8.0 / slot) and len(tail) == int(15.0 / slot)
        assert -8.0 <= lead[0].due and lead[-1].due < 0.0 <= w[0].due
        assert w[-1].due < 51.0 <= tail[0].due and tail[-1].due < 66.0
        # one period earlier and later: the same request at the same place
        for x, y in zip(lead, w[-len(lead):]):
            assert (x.prompt_len, x.max_new) == (y.prompt_len, y.max_new)
            assert x.due == pytest.approx(y.due - 51.0)
        for x, y in zip(tail, w):
            assert (x.prompt_len, x.max_new) == (y.prompt_len, y.max_new)
            assert x.due == pytest.approx(y.due + 51.0)
        for r in reqs:
            assert len(r.prompt_ids) == r.prompt_len and r.prompt_len + r.max_new < 1024


def test_a_ring_warms_the_window_s_buckets_and_two_cells_ask_for_it():
    import os
    assert traffic.buckets_reached(RING, 51, [32, 128, 512, 1024]) == [128, 512, 1024]
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for cell in ("mixtral.chat", "nemotron3.chat"):  # by name: a later cell may ask too
        assert traffic.load_traffic(here, "chat", cell)["placement"] == "ring"
    assert "placement" not in traffic.load_traffic(here, "chat", "lfm2.chat")
