"""A closed loop's tokens/s is taken over whole rounds: the count does
not jump by a burst when an edge of the window falls a millisecond to
the other side of one."""
import pytest

from benchmarks.common import whole_rounds_rate

PERIOD, BURST, WIDTH = 0.5, 50, 0.02


def bursts(phase, n=100):
    """n bursts of BURST tokens, PERIOD apart, each WIDTH wide."""
    return [phase + k * PERIOD + i * WIDTH / BURST
            for k in range(n) for i in range(BURST)]


@pytest.mark.parametrize("phase", [0.0, 0.001, 0.249, 0.479, 0.499])
def test_rate_does_not_depend_on_where_the_edges_fall(phase):
    rate, seconds, tokens, first = whole_rounds_rate(
        bursts(phase), 10.0, 30.0, 0.1)
    assert rate == pytest.approx(BURST / PERIOD, rel=1e-9)
    assert tokens % BURST == 0 and first <= BURST
    assert 20.0 <= seconds < 20.0 + PERIOD + WIDTH


def test_an_edge_inside_a_burst_moves_the_rate_by_its_width_only():
    rate, seconds, _, first = whole_rounds_rate(
        bursts(0.0), 10.0 + WIDTH / 2, 30.0, 0.1)
    assert first == BURST // 2
    assert rate == pytest.approx(BURST / PERIOD, rel=WIDTH / 20.0)


def test_a_steady_flow_counts_as_a_window_of_the_same_length():
    times = [k * 0.01 for k in range(5000)]
    rate, seconds, tokens, _ = whole_rounds_rate(times, 10.0, 30.0, 0.1)
    assert rate == pytest.approx(100.0, rel=1e-3)
    assert seconds == pytest.approx(20.0, abs=0.011)


def test_no_token_after_the_end_is_no_rate():
    assert whole_rounds_rate([1.0, 2.0, 3.0], 0.5, 10.0, 0.1) is None
    assert whole_rounds_rate([1.0, 2.0], 5.0, 10.0, 0.1) is None
