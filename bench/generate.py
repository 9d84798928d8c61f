"""The one traffic generator: a cell's cohorts and its campaign request,
from the configuration's data description, the traffic mix and the seed.

A configuration's ``data.kind`` names the file ``bench/data/<kind>.py``
whose ``make(rng, n_f, n_v, spec)`` draws one cohort.  Every seed yields
the same shapes and the same number of cohorts; only the values differ, so
the work per campaign does not depend on the seed.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np

#: request fields a configuration carries over to ``SimilarityRequest``
REQUEST_KEYS = ("way", "metric", "impl", "levels", "encoding", "n_pf",
                "n_pv", "n_pr", "n_st", "out_dtype")


def _maker(kind: str):
    path = Path(__file__).with_name("data") / f"{kind}.py"
    spec = importlib.util.spec_from_file_location(f"bench_data_{kind}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.make


def cohorts(cfg: dict, traffic: dict, seed: int) -> list:
    """The traffic's pool of distinct cohorts, in the order campaigns use
    them."""
    rng = np.random.default_rng([seed, 0])
    make = _maker(cfg["data"]["kind"])
    return [make(rng, cfg["n_f"], cfg["n_v"], cfg["data"])
            for _ in range(traffic["pool"])]


def request_fields(cfg: dict, traffic: dict) -> dict:
    """Keyword arguments of the cell's ``SimilarityRequest``."""
    fields = {k: cfg[k] for k in REQUEST_KEYS}
    if traffic.get("stages") is not None:
        fields["stages"] = tuple(traffic["stages"])
    return fields
