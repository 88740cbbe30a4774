"""The precision a reference keeps its values in."""
from __future__ import annotations

import numpy as np


def rounder(dtype: str):
    """x -> x stored in ``dtype`` and read back."""
    if dtype == "float32":
        return lambda x: float(np.float32(x))
    import ml_dtypes
    t = getattr(ml_dtypes, dtype)
    return lambda x: float(np.asarray(x, np.float32).astype(t))
