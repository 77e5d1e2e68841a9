"""Published peaks and the work each kernel's operation needs.

The counts are of the work the operation needs, computed from the call's
shapes, not of what an implementation does: neither a one-hot matmul nor
a second read of a row counts.  So a kernel can never read above its
roofline, and a later change to a kernel does not change the yardstick.
"""
from __future__ import annotations

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "bytes": 819e9},
}


def peaks(device_kind: str) -> dict:
    """The peaks of a device, by `device_kind`; an unknown device is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip needs: the larger of the two bounds."""
    return max(flops / peak["flops"], nbytes / peak["bytes"])


def rng_round(c: int, n: int, d: int, r: int, p: int,
              bytes_per_dim: int = 4) -> tuple[float, float]:
    """(flops, bytes) of one fused propagation round over c vertices.

    Each of the c·p sampled slot pairs needs one squared distance in d
    dimensions: 3·d operations (subtract, multiply, add).  Each pool
    neighbour row is read at most once per call, and no call can read
    more rows than the corpus holds: min(c·r, n) rows of d values.  The
    operands are the (c, r) ids and distances and the (c, p) slot pairs;
    the outputs are (c, p) dst, src and pair distance, and the (c, r)
    kill mask at one byte a slot.
    """
    flops = 3.0 * d * c * p
    nbytes = (min(c * r, n) * d * bytes_per_dim
              + c * r * (4 + 4) + c * p * (4 + 4)
              + c * p * (4 + 4 + 4) + c * r * 1)
    return flops, nbytes


def search_expand(fresh: int, q: int, d: int,
                  bytes_per_dim: int = 4) -> tuple[float, float]:
    """(flops, bytes) of the beam expansions of one search call.

    Over a call of q queries, `fresh` neighbours were seen for the first
    time (the program's `n_expanded`, summed over queries).  Each needs
    one query-to-row distance (3·d operations) and its row read once;
    each query is read once; each fresh neighbour's id, distance and
    fresh flag are written (4 + 4 + 1 bytes).  Neighbours already visited
    need no distance, so they are not counted.
    """
    flops = 3.0 * d * fresh
    nbytes = (fresh + q) * d * bytes_per_dim + fresh * (4 + 4 + 1)
    return flops, nbytes
