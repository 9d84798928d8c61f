from repro.serve.engine import SimilarityService  # noqa: F401
