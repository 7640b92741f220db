"""End-to-end metric arithmetic: tails over all samples, rates over the
whole window, open-loop latency from the due time."""

import pytest

from bench import stats


def rec(due, sent, stamps, max_tokens=None):
    return {"due": due, "sent": sent, "stamps": list(stamps),
            "tokens": [1] * len(stamps), "status": 200, "done": True,
            "max_tokens": max_tokens or len(stamps), "prompt_len": 4,
            "stream": 0, "index": 0}


def test_percentile_is_taken_over_every_sample():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile([5.0], 95) == 5.0
    assert stats.percentile(list(range(1, 21)), 95) == 19
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_tail_sees_one_slow_request_among_many():
    run = {"t_start": 0.0, "t_end": 10.0, "records": [
        rec(0.1 * i, 0.1 * i, [0.1 * i + 0.01 * k for k in range(1, 6)])
        for i in range(40)]}
    run["records"][7]["stamps"] = [3.0 + k for k in range(5)]
    itl = stats.itl_ms(run)
    assert len(itl) == 40 * 4
    assert stats.percentile(itl, 95) == pytest.approx(10.0)
    assert stats.percentile(itl, 99) == pytest.approx(1000.0)


def test_rate_counts_all_tokens_over_the_whole_window():
    steady = {"t_start": 0.0, "t_end": 10.0, "records": [
        rec(0.0, 0.0, [0.01 * k for k in range(1, 1000)])]}
    stalled = {"t_start": 0.0, "t_end": 10.0, "records": [
        rec(0.0, 0.0, [0.01 * k for k in range(1, 500)])]}
    assert stats.output_tok_s(steady) == pytest.approx(99.9)
    # a stall halfway: the tokens after it fall outside the window
    assert stats.output_tok_s(stalled) == pytest.approx(49.9)


def test_open_loop_latency_counts_from_the_due_time():
    run = {"t_start": 0.0, "t_end": 10.0, "records": [
        rec(1.0, 1.5, [1.6, 1.7]), rec(2.0, 2.0, [2.1, 2.2])]}
    assert stats.ttft_ms(run) == pytest.approx([600.0, 100.0])
    assert stats.lateness_ms(run) == pytest.approx([500.0, 0.0])
    late = {"t_start": 0.0, "t_end": 10.0,
            "records": [rec(11.0, 11.0, [11.1])]}
    assert stats.ttft_ms(late) == []


def test_a_request_short_of_its_tokens_is_not_ok():
    r = rec(0.0, 0.0, [0.1, 0.2], max_tokens=3)
    assert not stats.ok(r)
    r = rec(0.0, 0.0, [0.1, 0.2, 0.3])
    assert stats.ok(r)
    r["status"] = 429
    assert not stats.ok(r)


def test_gap_statistics_of_served_tokens():
    import numpy as np

    from bench.reference import check
    logits = np.array([[0.0, 2.0, 1.0], [3.0, 1.0, 0.5], [1.0, 1.5, 1.4]])
    gaps = check.token_gaps(logits, np.array([1, 1, 2]))
    assert gaps == pytest.approx([0.0, 2.0, 0.1])
    s = check.gap_stats(gaps)
    assert s["max_logit_gap"] == pytest.approx(2.0)
    assert s["mean_logit_gap"] == pytest.approx(0.7)
    assert s["argmax_disagreement"] == pytest.approx(2 / 3)
    assert check.gap_stats(np.zeros(0))["max_logit_gap"] == 0.0
