"""Operations and bytes that a kernel's work needs, counted from what the
algorithm does and not from the launch shapes, so that a roofline share
reads the same work whatever implements the kernel."""
from __future__ import annotations

#: bytes of a row id (int32)
ID_BYTES = 4


def pq_score_bytes(n_adc: float, m: int) -> float:
    """Bytes the ADC scoring of ``n_adc`` rows needs from memory: one uint8
    code per subspace (``m`` of them) and the row's id.  The per-query
    ``(m, ks)`` table is built once per query and stays in fast memory, so
    it is not counted per row."""
    return n_adc * (m + ID_BYTES)


def roofline_share(nbytes: float, peak_bytes_per_s: float, seconds: float) -> float | None:
    """Percent of the memory-bandwidth roofline: the least time the chip
    could take to move ``nbytes`` over ``seconds`` measured; None where
    nothing was moved or timed."""
    if not (nbytes > 0 and seconds > 0):
        return None
    return 100.0 * nbytes / (peak_bytes_per_s * seconds)
