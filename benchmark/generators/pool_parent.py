"""A column fixed by another: every run of `children` entries of the
column `of`'s pool shares one entry of this column's pool (SSB: forty
brands to a category)."""
from generators.pool_pick import pool_array


def generate(rng, docs, spec, pools, made):
    domain = pool_array(pools[spec["pool"]])
    codes = made[spec["of"]][1] // spec["children"]
    return domain[codes], codes, domain
