"""The bytes a kernel's work needs, and its share of the bandwidth roofline."""
import pytest

from bench import cost


def test_pq_score_bytes_count_codes_and_ids():
    assert cost.pq_score_bytes(1000, 16) == 1000 * (16 + 4)
    assert cost.pq_score_bytes(0, 16) == 0


def test_roofline_share_against_a_peak_and_a_time():
    # 20 MB at 819 GB/s take at least 24.42 us: in 0.1 ms that is 24.42% of the roofline
    assert cost.roofline_share(20e6, 819e9, 1e-4) == pytest.approx(24.42002442)
    assert cost.roofline_share(819e9, 819e9, 2.0) == pytest.approx(50.0)


@pytest.mark.parametrize("nbytes, seconds", [(0.0, 1.0), (1e6, 0.0)])
def test_roofline_share_reads_nothing_without_work_or_time(nbytes, seconds):
    assert cost.roofline_share(nbytes, 819e9, seconds) is None
