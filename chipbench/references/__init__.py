"""Plain references: numpy float32, no kernels, independent of ``models/``."""
import numpy as np


def padded_unique(ids: np.ndarray) -> np.ndarray:
    """The sorted distinct ids, padded with the largest to ``ids.size``: the
    rows a batch touches then have one shape whatever the seed drew, so the
    programs that fetch them compile once (``searchsorted`` finds the first
    of the repeated ids; the repeats are never addressed)."""
    ids = ids.reshape(-1)
    distinct = np.unique(ids)
    return np.concatenate(
        [distinct, np.full(ids.size - distinct.size, distinct[-1], ids.dtype)]
    )
