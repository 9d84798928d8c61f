"""The paper's technique on a document corpus: near-duplicate detection
over documents via all-pairs Czekanowski similarity of token
count-profiles.

    PYTHONPATH=src python examples/dataset_dedup.py
"""
import numpy as np

from repro.data.dedup import find_near_duplicates


def main():
    rng = np.random.default_rng(0)
    vocab = 50000
    docs = []
    # 60 random docs + 6 planted near-duplicates (90% token overlap)
    for _ in range(60):
        docs.append(rng.integers(0, vocab, rng.integers(200, 400)))
    for i in range(6):
        base = docs[i]
        mutated = base.copy()
        idx = rng.choice(len(base), len(base) // 10, replace=False)
        mutated[idx] = rng.integers(0, vocab, len(idx))
        docs.append(mutated)

    hits = find_near_duplicates(docs, vocab, threshold=0.85)
    print(f"{len(docs)} docs -> {len(hits)} near-duplicate pairs (c2 >= 0.85)")
    for i, j, sim in hits[:10]:
        print(f"  doc{i} ~ doc{j}: c2={sim:.3f}")
    planted = {(i, 60 + i) for i in range(6)}
    found = {(min(i, j), max(i, j)) for i, j, _ in hits}
    print(f"planted duplicates found: {len(planted & found)}/6")


if __name__ == "__main__":
    main()
