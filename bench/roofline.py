"""Peak table and the work functions behind the kernels' roofline shares.

A roofline share is the least time the chip could take for a kernel's
work, max(operations / peak FLOP/s, bytes / peak bytes/s), over the
kernel's measured device time.  The operations and bytes are counted from
what the kernel's work needs, by the functions below, never from what the
program happens to move.  The peaks are the published figures of
``peaks.json``, keyed by ``device_kind``; a kind missing there is an error.
"""

from __future__ import annotations

import json
import os

import numpy as np

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in {PEAKS_FILE} (known: "
                       f"{sorted(table)})")
    return table[device_kind]


def floor_seconds(flops: float, nbytes: float, peak: dict) -> float:
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


def range_rerank_floor(n_candidates: np.ndarray, d: int, peak: dict
                       ) -> float:
    """Least device time of one search's reranking: every candidate in a
    query's final set S had one exact distance computed (2d operations)
    and every point in the largest S was read once (4d bytes), whatever
    implements it.  The matrix peak is the bf16 one, the only matrix peak
    published; the kernel computes in f32, so its share reads low against
    it, never high."""
    counts = np.asarray(n_candidates, np.float64)
    return floor_seconds(2.0 * d * counts.sum(), 4.0 * d * counts.max(),
                         peak)


def encode_pack_floor(n: int, K: int, L: int, peak: dict) -> float:
    """Least device time of the static build's encode + key-pack kernel:
    read each point's L*K f32 projections once and write its L*K int32
    codes and L pairs of uint32 key words once.  Encoding is comparisons,
    no matrix work, so bytes bound it."""
    nbytes = 4.0 * n * K * L + 4.0 * n * K * L + 8.0 * n * L
    return floor_seconds(0.0, nbytes, peak)
