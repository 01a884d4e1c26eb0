import pytest

from benchmark import blocks


def test_one_slow_block_moves_the_metric_and_not_the_median():
    steady = [2.0] * 9
    assert blocks.window_rate(100, steady) == 50.0
    # a stall anywhere in the window is in the metric: all work over all time
    assert blocks.window_rate(100, [4.0] + steady[1:]) == pytest.approx(900 / 20.0)
    assert blocks.window_rate(100, steady[:4] + [6.0] + steady[5:]) == pytest.approx(900 / 22.0)
    # ... the median of the blocks, printed beside it, says most blocks were sound
    assert blocks.median_rate(100, [4.0] + steady[1:]) == 50.0
    assert blocks.median_rate(100, steady[:4] + [6.0] + steady[5:]) == 50.0


def test_a_slow_half_moves_both():
    times = [2.0] * 4 + [2.5] * 5
    assert blocks.median_rate(100, times) == 40.0
    assert blocks.window_rate(100, times) == pytest.approx(900 / 20.5)


def test_summary_carries_rate_median_rates_and_count():
    s = blocks.summary(100, "images/s", [2.0, 2.0, 4.0])
    assert s["blocks"] == 3 and s["block_rates"] == [50.0, 50.0, 25.0]
    assert s["median_of_blocks"] == 50.0
    assert s["window_rate"] == pytest.approx(300 / 8.0)
    assert s["window_s"] == 8.0


def test_no_block_is_an_error():
    with pytest.raises(ValueError):
        blocks.window_rate(100, [])


@pytest.mark.parametrize("times, since, want", [
    ([2.0], 5, False),                       # one block says nothing of falling
    ([2.2, 2.0], 2, False),                  # still falling (9%)
    ([2.0, 1.995], 2, True),                 # within 0.5%: settled
    ([2.0, 2.1], 2, True),
    ([2.0, 2.0], 1, False),                  # a compile in the block before
    ([3.0, 2.8, 2.6, 2.4, 2.2, 2.0, 1.8, 1.6], 8, True),  # the cap
])
def test_warm_up_rule(times, since, want):
    assert blocks.settled(times, since) is want
