"""A uniform draw from one of the configuration's pools of values."""
import numpy as np


def pool_array(values):
    """Strings as an object array of shared str objects (the segment
    creator turns any other string array into 8M fresh objects first),
    integers as int32."""
    if isinstance(values[0], str):
        return np.array(values, dtype=object)
    return np.array(values, dtype=np.int32)


def generate(rng, docs, spec, pools, made):
    domain = pool_array(pools[spec["pool"]])
    codes = rng.integers(0, len(domain), docs, dtype=np.int32)
    return domain[codes], codes, domain
