"""Work one campaign has to do, computed from its configuration alone.

Nothing here reads the program or its compiled HLO, so the counts stay the
same whatever implements the campaign:

* ``results``: the unique pairs (2-way) or the triples of the requested
  3-way stages, as the paper's staging defines them (§4.2, Algorithm 3);
* ``ops``: ``2 * levels * results * n_f``, one multiply and one add per
  level indicator and field for each result, the least a plane product
  needs for the min-sum numerator;
* ``bytes``: the packed bit-plane payload (``levels`` planes of one bit per
  field and vector) read once, plus every result written once.

The peaks table (``peaks.json``) is keyed by the ``device_kind`` that JAX
reports; an unknown device is an error, never a default.
"""
from __future__ import annotations

import json
from math import comb
from pathlib import Path

PEAKS_FILE = Path(__file__).with_name("peaks.json")

_OUT_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def block_size(cfg: dict) -> int:
    """Per-rank block size n_vp, padded as the paper's geometry needs:
    2-way to a whole split over n_pv, 3-way also to a multiple of 6*n_st."""
    n_vp = -(-cfg["n_v"] // cfg["n_pv"])
    if cfg["way"] == 3:
        n_vp += (-n_vp) % (6 * cfg["n_st"])
    return n_vp


def stage_positions(n_vp: int, n_st: int, stage: int) -> list[int]:
    """Block positions whose pipeline column falls in ``stage``: each sixth
    of a block splits into n_st runs of L = n_vp/(6 n_st) columns."""
    sixth, L = n_vp // 6, n_vp // (6 * n_st)
    return [t for t in range(n_vp) if (t % sixth) // L == stage]


def _stage_triples(cfg: dict, stage: int) -> int:
    n_v, n_pv = cfg["n_v"], cfg["n_pv"]
    m = block_size(cfg)
    valid = [max(0, min(m, n_v - b * m)) for b in range(n_pv)]
    S = stage_positions(m, cfg["n_st"], stage)
    total = 0
    for b, mb in enumerate(valid):
        # diagonal block: i < t < k inside block b, t the pipeline column
        total += sum(t * (mb - 1 - t) for t in S if t < mb)
        # faces: t < k inside block b, the third vector in another block
        others = sum(valid) - mb
        total += others * sum(mb - 1 - t for t in S if t < mb)
    if n_pv >= 3:
        if any(v != m for v in valid):
            raise ValueError("volume blocks with a padded block are not "
                             "counted; make n_v a multiple of n_pv * n_vp")
        total += comb(n_pv, 3) * len(S) * m * m
    return total


def results(cfg: dict, stages=None) -> int:
    """Unique results one campaign produces."""
    if cfg["way"] == 2:
        return comb(cfg["n_v"], 2)
    stages = range(cfg["n_st"]) if stages is None else stages
    return sum(_stage_triples(cfg, s) for s in stages)


def ops(cfg: dict, stages=None) -> int:
    return 2 * cfg["levels"] * results(cfg, stages) * cfg["n_f"]


def nbytes(cfg: dict, stages=None) -> int:
    payload = cfg["levels"] * (-(-cfg["n_f"] // 8)) * cfg["n_v"]
    return payload + results(cfg, stages) * _OUT_BYTES[cfg["out_dtype"]]


def comparisons(cfg: dict, stages=None) -> int:
    """The paper's figure of merit for one campaign: results x n_f."""
    return results(cfg, stages) * cfg["n_f"]


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; KeyError if absent."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; add its published figures")
    return table[device_kind]


def least_time_s(cfg: dict, stages, peak: dict, chips: int) -> tuple:
    """(seconds, bound) of the least time ``chips`` chips could take: the
    larger of ops at the int8 peak and bytes at the HBM bandwidth."""
    t_ops = ops(cfg, stages) / (chips * peak["int8_ops_per_s"])
    t_mem = nbytes(cfg, stages) / (chips * peak["hbm_bytes_per_s"])
    return (t_ops, "ops") if t_ops >= t_mem else (t_mem, "bytes")
