"""Rounding to the precision below, for the controls of the plain references."""

import numpy as np


def bf16(a: np.ndarray) -> np.ndarray:
    """Round to bfloat16 (nearest even), returned as float64."""
    import ml_dtypes

    return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16).astype(np.float64)
