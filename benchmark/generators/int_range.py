"""Uniform integers in [lo, hi], both ends included."""
import numpy as np


def generate(rng, docs, spec, pools, made):
    lo, hi = spec["lo"], spec["hi"]
    codes = rng.integers(0, hi - lo + 1, docs, dtype=np.int32)
    return codes + np.int32(lo), codes, np.arange(lo, hi + 1)
