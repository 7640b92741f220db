"""The traffic generator: same seed, same requests; every seed the same
sizes in another order."""

from collections import Counter

from bench import traffic

CLOSED = {"loop": "closed", "clients": 4, "grid": 8,
          "prompt_len": {"dist": "uniform", "lo": 1536, "hi": 1792},
          "max_tokens": {"dist": "uniform", "lo": 192, "hi": 256}}
OPEN = {"loop": "open", "rate": 10.0,
        "prompt_len": {"dist": "lognormal", "median": 48, "sigma": 0.5,
                       "lo": 16, "hi": 128},
        "max_tokens": {"dist": "lognormal", "median": 48, "sigma": 0.5,
                       "lo": 16, "hi": 128}}
BIG = 2**40 + 12345


def test_same_seed_same_requests():
    a = [traffic.closed_sizes(CLOSED, BIG, c, i)
         for c in range(4) for i in range(20)]
    b = [traffic.closed_sizes(CLOSED, BIG, c, i)
         for c in range(4) for i in range(20)]
    assert a == b
    assert traffic.open_schedule(OPEN, BIG, 30) == \
        traffic.open_schedule(OPEN, BIG, 30)
    assert traffic.prompt(BIG, 3, 7, 50, 32000) == \
        traffic.prompt(BIG, 3, 7, 50, 32000)


def test_seeds_permute_the_same_sizes():
    for c in range(4):
        s1 = Counter(traffic.closed_sizes(CLOSED, 1, c, i)[0]
                     for i in range(8))
        s2 = Counter(traffic.closed_sizes(CLOSED, BIG, c, i)[0]
                     for i in range(8))
        assert s1 == s2
    o1, o2 = (traffic.open_schedule(OPEN, s, 30) for s in (1, BIG))
    assert abs(len(o1) - len(o2)) <= 2
    assert [r["at"] for r in o1] != [r["at"] for r in o2]


def test_sizes_stay_in_range_and_prompts_differ():
    for i in range(16):
        p, m = traffic.closed_sizes(CLOSED, 5, 0, i)
        assert 1536 <= p <= 1792 and 192 <= m <= 256
    q = traffic.quantiles(OPEN["prompt_len"], 200)
    assert min(q) >= 16 and max(q) <= 128
    assert sorted(q)[100] in (47, 48, 49)
    assert traffic.prompt(5, 0, 0, 64, 32000) != \
        traffic.prompt(5, 0, 1, 64, 32000)
    assert traffic.prompt(5, 0, 0, 64, 32000) != \
        traffic.prompt(6, 0, 0, 64, 32000)


def test_open_schedule_fills_the_window_at_the_rate():
    sched = traffic.open_schedule(OPEN, 9, 30)
    assert 285 <= len(sched) <= 300
    ats = [r["at"] for r in sched]
    assert ats == sorted(ats) and 0 < ats[0] and ats[-1] < 30
