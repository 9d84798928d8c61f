"""SimilarityResult: one streaming interface over 2-way and 3-way outputs.

The engines produce per-rank metric *blocks* (``TwoWayOutput`` /
``ThreeWayOutput``); a result unifies them — across ways and across 3-way
stages — behind one reading API:

* ``tiles()``    — stream of ``Tile``s, one per computed block slice: global
                   index arrays + values.  This is the production path: a
                   campaign's output never has to exist densely in memory
                   (the paper's 3-way runs write ~1e12 results).
* ``entries()``  — flat scalar stream ``(i, j[, k], value)`` for small jobs.
* ``dense()``    — materialized symmetric matrix / tensor (tests, demos).
* ``checksum()`` — the paper §5 exact multiset checksum over all tiles.
* ``save()/load()`` — manifest + per-stage block arrays, round-tripping to
                   an identical checksum.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass, field, replace

import numpy as np

from repro.core import checksum as ck
from repro.core.plan2 import TwoWayPlan
from repro.core.plan3 import ThreeWayPlan
from repro.core.threeway import ThreeWayOutput
from repro.core.twoway import TwoWayOutput
from repro.obs import trace as obs

__all__ = ["Tile", "SimilarityResult"]

MANIFEST = "manifest.json"
FORMAT_VERSION = 1


@dataclass(frozen=True)
class Tile:
    """One computed block slice: parallel global-index arrays + values."""

    way: int
    index: tuple  # (I, J) or (I, J, K) int arrays, same length as values
    values: np.ndarray
    stage: int = 0

    def __len__(self) -> int:
        return len(self.values)

    def raw_checksum(self) -> tuple:
        if self.way == 2:
            return ck.raw_pairs(*self.index, self.values)
        return ck.raw_triples(*self.index, self.values)


@dataclass
class SimilarityResult:
    """Unified, streaming view of a similarity campaign's output."""

    way: int
    metric: str
    n_v: int
    n_f: int
    outputs: list  # one TwoWayOutput, or one ThreeWayOutput per stage
    decomposition: tuple = (1, 1, 1)
    n_st: int = 1
    stages: tuple = (0,)
    out_dtype: str = "float32"
    seconds: float = 0.0
    meta: dict = field(default_factory=dict)
    # memoized aggregates (blocks are write-once; full tile scans are the
    # dominant host-side cost of large campaigns)
    _checksum: int = field(default=None, init=False, repr=False, compare=False)
    _num_results: int = field(default=None, init=False, repr=False, compare=False)

    def __post_init__(self):
        # outputs that still had their blocks on the device carry the
        # folded device partials: checksum() and num_results() read them
        if self.checksum_source == "device":
            raws = [o.device_raw for o in self.outputs]
            self._checksum = ck.combine(raws)
            self._num_results = sum(count for _, count in raws)

    @property
    def checksum_source(self) -> str:
        """"device" when every output carries its device checksum partials
        (``device_raw``), else "host": ``checksum()`` scans the tiles."""
        if self.outputs and all(o.device_raw is not None
                                for o in self.outputs):
            return "device"
        return "host"

    @property
    def path(self):
        """The contraction path the campaign's ``TileExecutor`` resolved
        (``TileExecutor.path`` 2-way, ``path3`` 3-way), shared by every
        output; None where an output records none (``load()``)."""
        paths = {o.path for o in self.outputs}
        return paths.pop() if len(paths) == 1 else None

    # -- streaming reads ---------------------------------------------------

    def tiles(self):
        """Yield every computed block slice as a Tile (constant memory)."""
        for out in self.outputs:
            stage = getattr(out, "stage", 0)
            for tup in out.entries():
                *index, values = tup
                yield Tile(way=self.way, index=tuple(index), values=values,
                           stage=stage)

    def entries(self):
        """Flat scalar stream: (i, j, value) / (i, j, k, value)."""
        for tile in self.tiles():
            for row in zip(*tile.index, tile.values):
                yield row

    def dense(self) -> np.ndarray:
        """Materialized (n_v, n_v) symmetric matrix, or (n_v, n_v, n_v)
        tensor holding each triple at its canonical sorted index i < j < k
        (the other 5 permutation slots stay zero)."""
        out = np.zeros((self.n_v,) * self.way, np.dtype(self.out_dtype))
        for tile in self.tiles():
            idx = np.sort(np.stack(tile.index), axis=0)
            if self.way == 2:
                out[idx[0], idx[1]] = tile.values
                out[idx[1], idx[0]] = tile.values
            else:
                out[idx[0], idx[1], idx[2]] = tile.values
        return out

    def checksum(self) -> int:
        """Paper §5 exact campaign checksum (all stages combined).

        Folded from the device partials where the engine computed them
        (``checksum_source``); otherwise each tile's hash is a ``hash``
        span, and the tiles' index assembly shows as the engines'
        ``entries`` spans."""
        if self._checksum is None:
            parts = []
            count = 0
            for t in self.tiles():
                with obs.span("hash"):
                    parts.append(t.raw_checksum())
                count += len(t)
            self._checksum = ck.combine(parts)
            self._num_results = count
        return self._checksum

    def num_results(self) -> int:
        if self._num_results is None:
            self._num_results = sum(len(t) for t in self.tiles())
        return self._num_results

    # -- storage modes -----------------------------------------------------

    @property
    def storage(self) -> str:
        """"dense" | "packed" (2-way only; 3-way outputs are always dense)."""
        if self.way == 2 and self.outputs:
            return self.outputs[0].storage
        return "dense"

    def pack(self) -> "SimilarityResult":
        """Return a result with 2-way outputs in packed upper-triangular
        block storage (``self`` is left untouched, like
        ``TwoWayOutput.pack()``).

        Values, entries and checksum are unchanged (the packed form drops
        only the never-computed lower triangle of diagonal blocks); the
        retained result memory for the slot buffer roughly halves on
        diagonal-dominated decompositions."""
        if self.way != 2 or self.storage == "packed":
            return self
        return replace(self, outputs=[o.pack() for o in self.outputs])

    # -- persistence -------------------------------------------------------

    def save(self, path: str) -> dict:
        """Write per-stage blocks + a manifest; returns the manifest dict."""
        os.makedirs(path, exist_ok=True)
        for out, stage in zip(self.outputs, self.stages):
            np.save(os.path.join(path, f"blocks_s{stage}.npy"), out.blocks)
        manifest = {
            "format_version": FORMAT_VERSION,
            "metric": self.metric,
            "way": self.way,
            "n_f": int(self.n_f),
            "n_v": int(self.n_v),
            "n_vp": int(self.outputs[0].n_vp),
            "decomposition": list(self.decomposition),
            "n_st": self.n_st,
            "stages": list(self.stages),
            "storage": self.storage,
            "out_dtype": self.out_dtype,
            "results": int(self.num_results()),
            "seconds": self.seconds,
            "checksum": hex(self.checksum()),
            **self.meta,
        }
        with open(os.path.join(path, MANIFEST), "w") as f:
            json.dump(manifest, f, indent=2)
        return manifest

    #: manifest keys owned by the result itself; anything else in a saved
    #: manifest came from ``meta`` (e.g. the dataset-store provenance block
    #: the engine records for ``source="planes"`` campaigns) and is
    #: restored into ``meta`` on load
    _MANIFEST_KEYS = frozenset({
        "format_version", "metric", "way", "n_f", "n_v", "n_vp",
        "decomposition", "n_st", "stages", "storage", "out_dtype",
        "results", "seconds", "checksum",
    })

    @classmethod
    def load(cls, path: str) -> "SimilarityResult":
        """Rebuild a result from ``save()`` output (verifies the checksum)."""
        with open(os.path.join(path, MANIFEST)) as f:
            m = json.load(f)
        n_pf, n_pv, n_pr = m["decomposition"]
        outputs = []
        for stage in m["stages"]:
            blocks = np.load(os.path.join(path, f"blocks_s{stage}.npy"))
            if m["way"] == 2:
                outputs.append(TwoWayOutput(
                    blocks=blocks, plan=TwoWayPlan(n_pv, n_pr),
                    n_v=m["n_v"], n_vp=m["n_vp"],
                    storage=m.get("storage", "dense"),
                ))
            else:
                outputs.append(ThreeWayOutput(
                    blocks=blocks, plan=ThreeWayPlan(n_pv, n_pr, m["n_st"]),
                    n_v=m["n_v"], n_vp=m["n_vp"], stage=stage,
                ))
        result = cls(
            way=m["way"], metric=m["metric"], n_v=m["n_v"], n_f=m["n_f"],
            outputs=outputs, decomposition=tuple(m["decomposition"]),
            n_st=m["n_st"], stages=tuple(m["stages"]),
            out_dtype=m["out_dtype"], seconds=m.get("seconds", 0.0),
            meta={k: v for k, v in m.items() if k not in cls._MANIFEST_KEYS},
        )
        got = hex(result.checksum())
        if got != m["checksum"]:
            raise ValueError(
                f"checksum mismatch loading {path}: manifest {m['checksum']}, "
                f"recomputed {got}"
            )
        if "obs" in result.meta:  # recomputed here, over the stored tiles
            result.meta["obs"]["checksum"] = result.checksum_source
        return result
