"""The yardstick's own table of peaks, the roofline arithmetic, and the
operations and bytes of the index's scan: what is the chip's and the
index's. What one encoder dispatch costs is its architecture's
(``dispatch_cost`` of ``benchmark/models/<model>.py``).

Copied from ``pathway_tpu/engine/profiler.py`` (``DEVICE_PEAKS``,
``knn_search_cost``) so that no later PR to the program can move a roofline
share by editing the arithmetic; the originals are listed in PERF.md's open
questions for deletion.
"""

from __future__ import annotations

#: Peak rates by ``jax.devices()[0].device_kind``.
PEAKS: dict[str, dict] = {
    "TPU v5 lite": {
        "flops_per_s": 197.0e12,    # bf16
        "bytes_per_s": 819.0e9,     # HBM
        "source": "Google Cloud documentation, \"TPU v5e\": 197 TFLOP/s "
                  "bf16 and 819 GB/s of HBM bandwidth per chip",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peaks row of ``device_kind``. A device that is not in the table
    is an error, not a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}: add a row (with its "
            f"source) to benchmark/lib/costs.py PEAKS") from None


def knn_search_cost(queries: int, rows: int, dim: int,
                    itemsize: int) -> tuple[float, float]:
    """(flops, bytes) of one brute-force scan of ``rows`` established slab
    rows by ``queries`` queries: the score matmul, and the slab read once
    plus the query upload."""
    return (2.0 * queries * rows * dim,
            float(rows * dim * itemsize + queries * dim * 4.0))


def roofline(flops: float, nbytes: float, seconds: float,
             peaks: dict) -> tuple[float, str]:
    """(share, bound): the least time the chip could take — the larger of
    operations over peak FLOP/s and bytes over peak bytes/s — divided by
    the measured ``seconds``, and which of the two bounds it."""
    t_compute = flops / peaks["flops_per_s"]
    t_memory = nbytes / peaks["bytes_per_s"]
    bound = "compute" if t_compute >= t_memory else "bandwidth"
    return max(t_compute, t_memory) / seconds, bound
