"""The yardstick's own table of peaks and the functions that compute a
kernel's operations and bytes from its shapes.

Copied from ``pathway_tpu/engine/profiler.py`` (``DEVICE_PEAKS``,
``encoder_flops_per_token``, ``encoder_cost``, ``segment_attention_cost``,
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


def encoder_flops_per_token(hidden: int, intermediate: int, layers: int,
                            seq: int) -> float:
    """Forward FLOPs per token of the BERT-family encoder: 2 x the matmul
    parameters per token (QKV + out-proj 4*h*h, FFN up+down 2*h*f per
    layer) plus the attention score/value term (4*S*h per token per
    layer)."""
    per_layer = 2.0 * (4 * hidden * hidden + 2 * hidden * intermediate) \
        + 4.0 * seq * hidden
    return layers * per_layer


def encoder_cost(batch: int, seq: int, *, hidden: int, intermediate: int,
                 layers: int) -> tuple[float, float]:
    """(flops, bytes) of one dense forward of ``batch x seq`` tokens:
    every matmul parameter read once (bf16), the residual stream touched
    about four times in and four times out per block, one embedding row
    per token. First-order on purpose: the verdict needs the decade."""
    flops = batch * seq * encoder_flops_per_token(hidden, intermediate,
                                                  layers, seq)
    param_bytes = 2 * layers * (4 * hidden * hidden
                                + 2 * hidden * intermediate)
    stream = 2 * batch * seq * hidden
    return flops, float(param_bytes + 8 * layers * stream + stream)


def segment_attention_cost(batch: int, seq: int, *, hidden: int,
                           intermediate: int, layers: int,
                           heads: int) -> tuple[float, float]:
    """(flops, bytes) of one ragged-packed forward over ``batch`` packed
    sequences of ``seq`` tokens: the dense tree plus the (B, heads, S, S)
    bf16 score tensor written and read once per layer. (The program's copy
    leaves the head count out of that term.)"""
    flops, base = encoder_cost(batch, seq, hidden=hidden,
                               intermediate=intermediate, layers=layers)
    return flops, base + 2.0 * layers * 2 * batch * heads * seq * seq


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
