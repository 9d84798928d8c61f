"""SNP genotype cohorts: minor-allele counts {0,1,2} in Hardy-Weinberg
proportions."""
import numpy as np


def make(rng: np.random.Generator, n_f: int, n_v: int, spec: dict):
    """(n_f, n_v) uint8: each SNP draws its minor-allele frequency p
    uniformly from ``spec["maf"]``, each sample its count with
    probabilities ((1-p)^2, 2p(1-p), p^2)."""
    lo, hi = spec["maf"]
    p = rng.uniform(lo, hi, size=n_v).astype(np.float32)
    u = rng.random((n_f, n_v), dtype=np.float32)
    V = (u < p * p).astype(np.uint8)
    V += u < 1 - (1 - p) * (1 - p)
    return V
