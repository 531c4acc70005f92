"""Chip peaks and the algorithmic work of a field-of-groves evaluation.

The operation and byte counts are what Algorithm 2 needs, computed from
the field's shapes and the hops each request took, not what any kernel
happens to execute: a later implementation that does less redundant work
reads as a higher share of the same roofline.
"""
from __future__ import annotations

# Published per-chip peaks, keyed by jax's ``device_kind``.  Source: Google
# Cloud documentation, "TPU v5e" (system architecture): 197 TFLOP/s bf16,
# 394 TOP/s int8, 16 GiB HBM2 at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 394e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16 * 2**30},
}

TABLE_BYTES = {"fp32": 4, "bf16": 2, "int8": 1}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; add them to PEAKS with their "
                         "source") from None


def hop_ops(grove_size: int, depth: int, n_classes: int) -> int:
    """Operations of one lane's hop: per tree ``depth`` node reads and
    ``depth`` compares, then ``C`` adds of its leaf distribution; the gate
    normalizes ``C`` probabilities, scans them for the top two (``2C``)
    and compares the margin once."""
    return grove_size * (2 * depth + n_classes) + 3 * n_classes + 1


def table_bytes(n_trees: int, depth: int, n_classes: int,
                precision: str) -> int:
    """Bytes of the field's tables at one storage precision: per internal
    node a 4-byte feature id and a threshold, per leaf ``C`` values, and
    for the integer grids one 4-byte scale each for thresholds and leaves
    per tree."""
    w = TABLE_BYTES[precision]
    nodes, leaves = (1 << depth) - 1, 1 << depth
    scales = 8 if precision == "int8" else 0
    return n_trees * (nodes * (4 + w) + leaves * n_classes * w + scales)


def call_bytes(tbytes: int, n_lanes: int, n_features: int) -> int:
    """One evaluation call: the tables once, each lane's float32 row in,
    its label and hop count out."""
    return tbytes + n_lanes * (4 * n_features + 8)


def least_seconds(ops: float, nbytes: float, peak: dict) -> float:
    """The roofline's least time for one call: compute at the chip's bf16
    peak or bytes at HBM bandwidth, whichever is longer."""
    return max(ops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
