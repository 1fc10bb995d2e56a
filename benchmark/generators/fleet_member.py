"""Which member of the fleet a reading is of, in the order they are
written (fleet_hour): within a tick every member of the pool once, in the
pool's order."""
import numpy as np

from generators.pool_pick import pool_array


def generate(rng, docs, spec, pools, made):
    domain = pool_array(pools[spec["pool"]])
    codes = (np.arange(docs, dtype=np.int64) % len(domain)).astype(np.int32)
    return domain[codes], codes, domain
