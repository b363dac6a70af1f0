"""poisson: arrivals with exponential gaps (independent users). The gaps
are the exponential law's quantiles ``q`` in an order drawn from the
seed, scaled so the last request is due inside the window."""
import numpy as np


def due(spec: dict, q: np.ndarray, rng: np.random.Generator,
        seconds: float) -> np.ndarray:
    gaps = rng.permutation(-np.log1p(-q))
    return (np.cumsum(gaps) - gaps / 2) / gaps.sum() * seconds
