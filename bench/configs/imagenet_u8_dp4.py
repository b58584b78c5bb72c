"""Plain reference for ``imagenet_u8_dp4.json``: the image set of
``imagenet_u8`` (the same rows from the same seed, the same labels and
normalization; its reference, ``imagenet_u8.py``, is imported whole), and
what a synchronous data-parallel feed over a 1-D ``data`` mesh of
``chips`` devices must deliver:

* each global batch's rows are distinct rows of the set;
* the shard on chip ``i`` (the ``i``-th device of the mesh) holds global
  rows ``[per_chip * i, per_chip * (i + 1))`` of the batch;
* every delivered row decodes to the image its label names;
* the global batches of one epoch deliver every row of the set exactly
  once, counted by label: a row missing, or one delivered twice, moves
  its label's count off the set's.

A delivered row is found among the rows its label names by its first
1,023 values, then compared whole. Imports nothing of the program under
test, and never derives the program's order of rows.
"""

from __future__ import annotations

import importlib.util
import os
from typing import Iterable, Sequence, Tuple

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "bench_imagenet_u8_reference", os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                                "imagenet_u8.py"))
_images = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_images)

scale_bias = _images.scale_bias
block_codes = _images.block_codes
labels = _images.labels
dequant = _images.dequant
steps = _images.steps

# values of a row compared to tell it from the other rows of its label (a
# whole number of pixels)
PREFIX = 1023

# one shard of a global batch as read from the device that holds it:
# (chip, first global row of its image shard, first global row of its
# label shard, its images, its labels)
Shard = Tuple[int, int, int, np.ndarray, np.ndarray]


class Rows(_images.Rows):
    """Expected images by row, with the prefix that tells a row apart
    computed from its codes alone."""

    def prefix(self, row: int) -> np.ndarray:
        b, i = divmod(int(row), self.cfg["shard_rows"])
        if b not in self._blocks:
            self._blocks[b] = block_codes(self.cfg, self.seed, b)
        codes = self._blocks[b][i].reshape(-1)[:PREFIX]
        return dequant(codes.reshape(-1, self.cfg["image"][-1]), self.cfg, self.dtype).reshape(-1)


def identify(image: np.ndarray, label: int, rows: Rows, cfg: dict):
    """The row of the set that ``image`` is, among those ``label`` names,
    and its distance from that row's image in quantization steps (``inf``
    where the label names no row)."""
    flat = image.reshape(-1)[:PREFIX]
    best = min(rows.candidates(label), default=None,
               key=lambda r: steps(flat, rows.prefix(r), cfg))
    if best is None:
        return None, float("inf")
    return best, steps(image, rows.image(best), cfg)


def compare_batch(shards: Sequence[Shard], rows: Rows, cfg: dict, chips: int,
                  batch: int) -> dict:
    """One global batch, shard by shard: ``image_err``, the worst row in
    quantization steps; ``rows_repeated``, rows that are a row of the set
    already delivered in this batch; ``shard_misplaced``, rows of shards
    whose global position is not their chip's."""
    per_chip = batch // chips
    worst, repeated, misplaced, delivered = 0.0, 0, 0, 0
    seen = set()
    chips_seen = set()
    for chip, image_at, label_at, images, labs in shards:
        n = len(images)
        delivered += n
        if len(labs) != n:
            return {"image_err": float("inf"), "rows_repeated": 0.0, "shard_misplaced": float(n)}
        if chip in chips_seen or image_at != per_chip * chip or label_at != per_chip * chip \
                or n != per_chip:
            misplaced += n
        chips_seen.add(chip)
        for img, lab in zip(images, labs):
            row, err = identify(img, int(lab), rows, cfg)
            worst = max(worst, err)
            repeated += row in seen
            seen.add(row)
    if delivered != batch or len(chips_seen) != chips:
        worst = float("inf")
    return {"image_err": worst, "rows_repeated": float(repeated),
            "shard_misplaced": float(misplaced)}


def epoch_rows_wrong(epoch_labels: Iterable[np.ndarray], cfg: dict) -> float:
    """Rows missing plus rows delivered twice over one epoch's global
    batches, by label: the distance of the delivered labels' counts from
    the counts of the whole set's labels."""
    want = np.bincount(labels(cfg, np.arange(cfg["images"])), minlength=cfg["classes"])
    got_labels = np.concatenate([np.asarray(lab).reshape(-1) for lab in epoch_labels])
    if got_labels.size and (got_labels.min() < 0 or got_labels.max() >= cfg["classes"]):
        return float("inf")
    got = np.bincount(got_labels, minlength=cfg["classes"])
    return float(np.abs(got - want).sum())
