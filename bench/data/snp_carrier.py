"""Carrier-status cohorts: the dominant-model coding of SNP genotypes, 1
where a sample carries at least one minor allele."""
import numpy as np


def make(rng: np.random.Generator, n_f: int, n_v: int, spec: dict):
    """(n_f, n_v) uint8 in {0, 1}: each SNP draws its minor-allele
    frequency p uniformly from ``spec["maf"]``; under Hardy-Weinberg a
    sample is a carrier with probability 1 - (1-p)^2."""
    lo, hi = spec["maf"]
    p = rng.uniform(lo, hi, size=n_v).astype(np.float32)
    u = rng.random((n_f, n_v), dtype=np.float32)
    return (u < 1 - (1 - p) * (1 - p)).astype(np.uint8)
