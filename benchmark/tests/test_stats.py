"""Block-median, percentile and due-time arithmetic on hand-made inputs."""

import random

import pytest

from benchmark import stats


def test_total_rate_counts_a_stall_the_block_median_does_not():
    blocks = [2.0] * 14 + [2.6]           # one stall of 0.6 s in 15 blocks
    tokens = 4 * 16384
    # the end-to-end rate: all tokens over all the time to the last boundary
    assert stats.total_rate(blocks, tokens) == pytest.approx(
        15 * tokens / 30.6)
    assert stats.total_rate(blocks, tokens, chips=4) == pytest.approx(
        15 * tokens / 30.6 / 4)
    # the per-layer reading beside it: what the steps cost with no stall
    assert stats.block_median_rate(blocks, tokens) == tokens / 2.0
    assert stats.block_median_rate(blocks, tokens, chips=4) == tokens / 8.0
    with pytest.raises(ValueError):
        stats.block_median_rate([], tokens)


def test_percentile_interpolates_like_numpy():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(xs, 50) == 30.0
    assert stats.percentile(xs, 90) == pytest.approx(46.0)
    assert stats.percentile(xs, 0) == 10.0 and stats.percentile(xs, 100) == 50.0
    assert stats.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_spread_is_the_contracts_quartile_rule():
    vals = [100.0, 101.0, 102.0, 103.0, 104.0, 105.0]
    # statistics.quantiles(n=4) on six values: q1 = 100.75, q3 = 104.25
    assert stats.spread(vals) == pytest.approx(3.5 / 102.5)


def _req(due, times, want, per=1):
    """A request that got ``per`` tokens at each of ``times``."""
    return {"due": due, "first": times[0] if times else None,
            "last": times[-1] if times else None,
            "tokens": per * len(times), "want": want,
            "deliveries": [(t, per) for t in times]}


def test_ttft_runs_from_due_and_a_miss_is_failed():
    reqs = [
        # due early, answered: ttft from DUE (put was late, that counts)
        _req(1.0, [1.5, 2.0, 2.5, 3.0, 3.5], 5),
        # due in the last fifth of the window: no ttft sample
        _req(9.0, [9.2, 9.4, 9.6, 9.8], 4),
        # due early, never answered inside the window: a miss
        _req(2.0, [], 8),
        # pre-roll request: no ttft sample
        _req(-3.0, [-2.0, 1.0, 4.0], 3),
        # due just inside the first four fifths, cut by the window's end
        _req(7.9, [8.1, 9.0], 40),
    ]
    out = stats.request_latencies(reqs, 0.0, 10.0, 0.8, tpot_min_gaps=2)
    assert out["ttft"] == pytest.approx([0.5, 0.2])
    assert out["missed"] == 1


def test_tpot_is_the_mean_gap_inside_the_window_censored_or_not():
    reqs = [
        # whole inside the window and finished: 4 gaps over 2.0 s
        _req(1.0, [1.5, 2.0, 2.5, 3.0, 3.5], 5),
        # began in the pre-roll: only the tokens at 1.0 and 4.0 and 7.0 lie
        # inside; 2 gaps over 6.0 s (the token at -2.0 is not the window's)
        _req(-3.0, [-2.0, 1.0, 4.0, 7.0], 4),
        # cut by the window's end (want 50): 3 gaps over 6.6 s, a sample
        # all the same - PR 23's definition left it out
        _req(3.0, [3.3, 5.5, 7.7, 9.9], 50),
        # too few gaps inside the window: no sample
        _req(9.0, [9.5, 9.9], 30),
        # never answered: no sample
        _req(2.0, [], 8),
        # two tokens a delivery (a fused horizon): the first delivery's
        # tokens open the span, 4 later tokens over 1.0 s
        _req(4.0, [4.0, 4.5, 5.0], 6, per=2),
    ]
    out = stats.request_latencies(reqs, 0.0, 10.0, 0.8, tpot_min_gaps=2)
    assert out["tpot"] == pytest.approx([2.0 / 4, 6.0 / 2, 6.6 / 3, 1.0 / 4])
    # PR 23's figure beside it: requests that finished inside the window,
    # (last - first) / (tokens - 1), a pre-roll first token included
    assert out["tpot_finished"] == pytest.approx(
        [2.0 / 4, 9.0 / 3, 1.0 / 5])
    # the floor on gaps is the traffic file's; 16 if it says nothing
    assert stats.request_latencies(reqs, 0.0, 10.0)["tpot"] == []
    assert stats.request_latencies(reqs, 0.0, 10.0, 0.8, 4)["tpot"] == \
        pytest.approx([2.0 / 4, 1.0 / 4])


def test_stratified_sets_do_not_depend_on_the_seed():
    vals = stats.stratified(100, stats.lognormal_icdf(512, 0.8), 32, 3072)
    assert vals == sorted(vals) and min(vals) >= 32 and max(vals) <= 3072
    assert vals[49] < 512 < vals[50]
    a = stats.balanced_order(96, 8, random.Random(1))
    b = stats.balanced_order(96, 8, random.Random(2))
    assert sorted(a) == sorted(b) == list(range(96)) and a != b
    # every run of 8 holds one index from each eighth
    for k in range(0, 96, 8):  # 96 = 8 strata of 12
        assert sorted(i // 12 for i in a[k:k + 8]) == list(range(8))


def test_gc_watch_times_collections_and_says_which_step_they_fell_in():
    import gc
    import time

    from benchmark import harness

    watch = harness.GcWatch().start()
    t0 = time.perf_counter()
    gc.collect()
    t1 = time.perf_counter()
    watch.stop()
    gc.collect()                      # after the watch closed: not recorded
    assert len(watch.pauses) == 1 and watch.pauses[0][2] == 2
    assert 0.0 < watch.inside(t0, t1) <= t1 - t0
    assert watch.inside(t1 + 1.0, t1 + 2.0) == 0.0
    assert "1 garbage collections" in watch.summary()
    assert harness.GcWatch().summary() == "no garbage collection"
