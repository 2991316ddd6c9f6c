"""t_min per spec and the peak table."""
import pytest

from bench import work

V5E = "TPU v5 lite"


@pytest.mark.parametrize("taps,points,bound,t", [
    (5, 10240 * 10240, "hbm", 2 * 10240 * 10240 * 4 / 819e9),
    (49, 10240 * 10240, "hbm", 2 * 10240 * 10240 * 4 / 819e9),
    (5, 20480 * 20480, "hbm", 2 * 20480 * 20480 * 4 / 819e9),
    (100000, 1000, "flops", 2 * 100000 * 1000 / 197e12),
])
def test_t_min_step(taps, points, bound, t):
    got, which = work.t_min_step(points, taps, 4, work.peaks(V5E))
    assert which == bound
    assert got == pytest.approx(t)


def test_t_min_of_the_paper_grid_is_about_a_millisecond():
    got, _ = work.t_min_step(10240 * 10240, 49, 4, work.peaks(V5E))
    assert 1.0e-3 < got < 1.05e-3


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("cpu")
