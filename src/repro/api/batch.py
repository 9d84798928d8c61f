"""Batched campaign results: many (metric, subset) campaigns, one payload.

A batched ``SimilarityRequest`` (``metrics=[...]`` and/or ``subsets=[...]``)
runs every campaign against ONE ring traversal of the shared plane payload
(``repro.core.twoway.twoway_batched`` / ``threeway.threeway_batched``).  The
engine wraps the per-campaign outputs in a ``BatchedSimilarityResult``: an
ordered collection of ordinary ``SimilarityResult`` objects — each one
bit-identical (checksum) to the sequential single-campaign run it replaces —
plus the shared ``meta["batch"]`` ring accounting proving the payload bytes
moved are independent of the campaign count.

Named-subset campaigns never re-encode: the engine restricts the payload to
the sorted union of all subset indices (a byte-level vector-axis view of the
packed planes — slicing commutes with encoding, see docs/BITPLANE_FORMAT.md),
runs the batched engines over the union, and ``extract_twoway`` /
``extract_threeway`` below carve each named subset's result out of the union
output.  Extraction is a host-side re-index into the smallest single-rank
plan — values are copied untouched, so bit-exactness survives.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from repro.core.plan2 import TwoWayPlan
from repro.core.plan3 import ThreeWayPlan
from repro.core.threeway import ThreeWayOutput
from repro.core.twoway import TwoWayOutput

__all__ = ["BatchedSimilarityResult", "extract_twoway", "extract_threeway"]


@dataclass
class BatchedSimilarityResult:
    """Ordered (metric, subset_name, SimilarityResult) campaigns.

    ``subset_name`` is ``""`` for full-set campaigns.  Iterating yields the
    ``(metric, subset_name, result)`` triples in request order (metrics
    outer, subsets inner); ``get`` looks one campaign up by name.
    """

    campaigns: list  # [(metric, subset_name, SimilarityResult), ...]
    meta: dict = field(default_factory=dict)
    seconds: float = 0.0

    def __iter__(self):
        return iter(self.campaigns)

    def __len__(self) -> int:
        return len(self.campaigns)

    def get(self, metric: str, subset: str = ""):
        for m, s, r in self.campaigns:
            if m == metric and s == subset:
                return r
        raise KeyError(f"no campaign (metric={metric!r}, subset={subset!r})")

    @property
    def path(self):
        """The contraction path every campaign shares, else None."""
        paths = {r.path for _m, _s, r in self.campaigns}
        return paths.pop() if len(paths) == 1 else None

    def checksums(self) -> dict:
        """{(metric, subset_name): checksum} over every campaign."""
        return {(m, s): r.checksum() for m, s, r in self.campaigns}


def _position_lut(n_union: int, pos: np.ndarray) -> np.ndarray:
    """union position -> subset position (or -1), preserving subset order."""
    pos = np.asarray(pos, dtype=np.int64)
    lut = np.full((n_union,), -1, np.int64)
    lut[pos] = np.arange(len(pos))
    return lut


def extract_twoway(full: TwoWayOutput, pos) -> TwoWayOutput:
    """Carve a subset's 2-way result out of the union-payload output.

    ``pos``: the subset's vector positions within the union payload, in
    subset order (subset index t lives at union column pos[t]).  Returns a
    single-rank ``TwoWayOutput`` (plan (1, 1): one diagonal block, strict
    upper triangle) whose entries/checksum equal a sequential run over the
    subset columns alone — values are copied, never recomputed.
    """
    pos = np.asarray(pos, dtype=np.int64)
    m = len(pos)
    lut = _position_lut(full.n_v, pos)
    sub = np.zeros((m, m), full.blocks.dtype)
    for I, J, V in full.entries():
        a, b = lut[I], lut[J]
        keep = (a >= 0) & (b >= 0)
        a, b, v = a[keep], b[keep], V[keep]
        lo, hi = np.minimum(a, b), np.maximum(a, b)
        sub[lo, hi] = v
    return TwoWayOutput(
        blocks=sub[None, None, None], plan=TwoWayPlan(1, 1), n_v=m, n_vp=m,
        path=full.path,
    )


def extract_threeway(stage_outs, pos) -> ThreeWayOutput:
    """Carve a subset's 3-way result out of union-payload stage outputs.

    ``stage_outs`` must cover every computed triple of the union run (all
    stages of the request — the engine validates completeness before
    batching).  Returns a single-rank single-stage ``ThreeWayOutput``
    (plan (1, 1, 1)): the subset block size is padded to a multiple of 6
    and each canonical triple a < b < c lands in DIAG slot ``b // L`` at
    pipeline offset ``b - slot * L`` (L = padded_m / 6) — exactly where the
    sequential single-rank schedule computes it.
    """
    pos = np.asarray(pos, dtype=np.int64)
    m = len(pos)
    mp = m + (-m) % 6
    L = mp // 6
    lut = _position_lut(stage_outs[0].n_v, pos)
    blocks = np.zeros((1, 1, 6, L, mp, mp), stage_outs[0].blocks.dtype)
    for out in stage_outs:
        for I, J, K, V in out.entries():
            t = np.stack([lut[I], lut[J], lut[K]])
            keep = (t >= 0).all(axis=0)
            a, b, c = np.sort(t[:, keep], axis=0)
            s = b // L
            blocks[0, 0, s, b - s * L, a, c] = V[keep]
    return ThreeWayOutput(
        blocks=blocks, plan=ThreeWayPlan(1, 1, 1), n_v=m, n_vp=mp, stage=0,
        path=stage_outs[0].path,
    )
