"""Plain reference for ``imagenet_u8.json``: the images a seed makes, and
what the feed must deliver for each.

Row ``r`` of the set is a uniform random u8 image drawn from the seed and
the block ``r // shard_rows`` it lies in; its label is ``r mod classes``.
Delivered, an image is its codes dequantized per channel in float32,
``code * scale + bias``, with ``scale = 1 / (255 std)`` and
``bias = -mean / std``: the usual ImageNet normalization of pixels in
[0, 255]. Imports nothing of the program under test.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np


def scale_bias(cfg: dict) -> Tuple[np.ndarray, np.ndarray]:
    std = np.asarray(cfg["channel_std"], np.float32)
    mean = np.asarray(cfg["channel_mean"], np.float32)
    return (np.float32(1.0) / (np.float32(255.0) * std)).astype(np.float32), (-mean / std).astype(np.float32)


def block_codes(cfg: dict, seed: int, block: int) -> np.ndarray:
    """The u8 codes of rows ``[block * shard_rows, (block + 1) * shard_rows)``."""
    rows = min(cfg["shard_rows"], cfg["images"] - block * cfg["shard_rows"])
    rng = np.random.default_rng([seed % (1 << 64), block])
    return rng.integers(0, 256, (rows, *cfg["image"]), dtype=np.uint8)


def labels(cfg: dict, rows: np.ndarray) -> np.ndarray:
    return (np.asarray(rows) % cfg["classes"]).astype(np.int32)


def dequant(codes: np.ndarray, cfg: dict, dtype=np.float32) -> np.ndarray:
    """What the feed delivers for ``codes``, computed in float32 and held in
    ``dtype`` (float32 for the reference, a narrower type for the control)."""
    scale, bias = scale_bias(cfg)
    x = codes.astype(np.float32) * scale + bias
    return x.astype(dtype).astype(np.float32)


class Rows:
    """Expected images by row, generating each block once."""

    def __init__(self, cfg: dict, seed: int, dtype=np.float32):
        self.cfg, self.seed, self.dtype = cfg, seed, dtype
        self._blocks = {}

    def image(self, row: int) -> np.ndarray:
        b, i = divmod(int(row), self.cfg["shard_rows"])
        if b not in self._blocks:
            self._blocks[b] = block_codes(self.cfg, self.seed, b)
        return dequant(self._blocks[b][i], self.cfg, self.dtype)

    def candidates(self, label: int) -> range:
        """The rows whose label is ``label``."""
        return range(int(label), self.cfg["images"], self.cfg["classes"])


def steps(got: np.ndarray, want: np.ndarray, cfg: dict) -> float:
    """Largest distance of ``got`` from ``want`` in units of each channel's
    quantization step (its ``scale``)."""
    scale, _ = scale_bias(cfg)
    diff = np.abs(got.astype(np.float64) - want.astype(np.float64)).reshape(-1, len(scale))
    return float(np.max(diff / scale.astype(np.float64))) if diff.size else 0.0
