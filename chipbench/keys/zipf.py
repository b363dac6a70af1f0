"""zipf: ``n`` keys out of ``k`` with rank r drawn with density
~ (r + 1)^-s (YCSB's law), rank r at key (a r + b) mod k, so the hot keys
are scattered by a seeded affine map."""
import numpy as np

from chipbench.load import affine, zipf_ranks


def draw(spec: dict, rng: np.random.Generator, n: int, k: int) -> np.ndarray:
    rank = zipf_ranks(rng, n, k, spec["s"])
    a, b = affine(rng, k)
    return (a * rank + b) % k
