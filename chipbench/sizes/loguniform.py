"""loguniform: request sizes spread evenly in log between ``lo`` and
``hi``, taken at the quantiles ``q``."""
import numpy as np


def quantile(spec: dict, q: np.ndarray) -> np.ndarray:
    lo, hi = spec["lo"], spec["hi"]
    return np.rint(lo * (hi / lo) ** q).astype(np.int64)
