"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``, and the bytes each measured operation has to move.

``TPU v5 lite`` (TPU v5e): 1,600 Gbit/s of chip-to-chip interconnect
(ICI) per chip, 200e9 bytes/s (Google Cloud documentation, "TPU v5e",
system architecture).  A device that is not in the table is an error.
"""
from __future__ import annotations

import re

__all__ = ["PEAKS", "peak", "merge_bytes", "merge_seconds"]

PEAKS = {
    "TPU v5 lite": {"ici_bytes_per_s": 200e9},
}


def peak(device_kind: str, name: str) -> float:
    """The published ``name`` peak of ``device_kind``; KeyError for a
    device or a peak the table does not hold."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device {device_kind!r}; "
                       f"have {sorted(PEAKS)}")
    return PEAKS[device_kind][name]


def merge_bytes(rounds: int, workers: int, k: int, words: int) -> int:
    """Bytes each chip receives in the all-gathers of Alg 4's merges:
    every round, the other ``workers - 1`` workers' (k, words) int32
    server sets."""
    return rounds * (workers - 1) * k * words * 4


# The collective operations of ``_parallel_partition_scan`` as a v5e
# trace names them: the sets' ``all-gather`` and the ``psum``
# all-reduces, and async ``-start`` / ``-done`` halves where XLA splits
# them.
_MERGE_OPS = re.compile(r"all-gather|all-reduce|psum")


def merge_seconds(trace) -> float:
    """Device seconds of Alg 4's merge collectives in a ``TraceSummary``,
    every chip's summed."""
    return sum(v for name, v in trace.ops.items() if _MERGE_OPS.search(name))
