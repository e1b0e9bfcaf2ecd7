"""Least work of one served batch, and the chip's peaks to price it.

The work is what any implementation of the step has to do, counted from
shapes and the batch's probe sets:

* bytes: the code bytes of the distinct lists the batch probes, each
  counted once (a flat scan reads all n rows), plus ``m * kappa * D * 4``
  for the full-precision rows the rerank reads;
* ops: ``2 * d`` per code row scored, summed over the batch's queries,
  plus ``2 * kappa * D`` per query for the rerank.

The least time is ``max(bytes / HBM bandwidth, ops / int8 peak)``, so no
implementation, one that shares list reads across queries included, can
read above 100% of it.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import NamedTuple

import numpy as np

PEAKS = Path(__file__).with_name("peaks.json")


class Work(NamedTuple):
    bytes: float
    ops: float

    def __add__(self, other):
        return Work(self.bytes + other.bytes, self.ops + other.ops)


class Peaks(NamedTuple):
    hbm_bytes_per_s: float
    int8_ops_per_s: float

    def least_s(self, work: Work) -> float:
        return max(work.bytes / self.hbm_bytes_per_s,
                   work.ops / self.int8_ops_per_s)


def peaks(device_kind: str, path: Path = PEAKS) -> Peaks:
    """The published peaks of ``device_kind``; a kind that the table does
    not hold is an error, never a default."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; known: {sorted(table)}")
    row = table[device_kind]
    return Peaks(float(row["hbm_bytes_per_s"]), float(row["int8_ops_per_s"]))


def ivf_scan_work(probe: np.ndarray, list_rows: np.ndarray,
                  d: int) -> Work:
    """Fine scan of an IVF batch: ``probe`` (m, nprobe) list ids of the
    served queries, ``list_rows`` (C,) rows in each list, ``d`` code bytes
    per row."""
    probe = np.asarray(probe)
    list_rows = np.asarray(list_rows, np.int64)
    distinct = np.unique(probe)
    scored = int(list_rows[probe].sum())
    return Work(bytes=float(list_rows[distinct].sum() * d),
                ops=float(2 * scored * d))


def flat_scan_work(m: int, n: int, d: int) -> Work:
    """Flat scan of ``m`` queries over ``n`` code rows of ``d`` bytes."""
    return Work(bytes=float(n * d), ops=float(2 * m * n * d))


def rerank_work(m: int, kappa: int, dim: int) -> Work:
    """Full-precision rerank of ``kappa`` f32 rows of width ``dim`` for
    each of ``m`` queries."""
    return Work(bytes=float(m * kappa * dim * 4),
                ops=float(2 * m * kappa * dim))
