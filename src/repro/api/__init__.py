"""repro.api — the unified similarity API.

One entry point for every similarity campaign (CLI, bench/, examples,
serving): build a frozen ``SimilarityRequest``, hand it to a
``SimilarityEngine``, stream the ``SimilarityResult``.  New metrics plug in
through ``register_metric`` without touching engine code.
"""
from repro.api.batch import BatchedSimilarityResult  # noqa: F401
from repro.api.engine import SimilarityEngine  # noqa: F401
from repro.api.registry import (  # noqa: F401
    CCC,
    SORENSON,
    MetricSpec,
    UnknownMetricError,
    available_metrics,
    batch_lead,
    family_key,
    get_metric,
    group_families,
    plane_native,
    register_metric,
)
from repro.api.request import InputSpec, SimilarityRequest  # noqa: F401
from repro.api.result import SimilarityResult, Tile  # noqa: F401

__all__ = [
    "SimilarityEngine",
    "SimilarityRequest",
    "InputSpec",
    "SimilarityResult",
    "BatchedSimilarityResult",
    "Tile",
    "MetricSpec",
    "UnknownMetricError",
    "register_metric",
    "get_metric",
    "available_metrics",
    "family_key",
    "group_families",
    "plane_native",
    "batch_lead",
    "CCC",
    "SORENSON",
]
