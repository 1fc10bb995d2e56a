"""A gauge a member of the fleet reports each tick (fleet_hour's order):
TSBS's clamped random walk, a step of N(0, 1) a tick held to [lo, hi],
read as an integer. A segment starts every member's walk anew from a
uniform draw (TSBS's runs on through the three days)."""
import numpy as np

from generators.fleet_hour import layout


def generate(rng, docs, spec, pools, made):
    lo, hi = spec["lo"], spec["hi"]
    _stretch, fleet, _tick, ticks = layout(rng, docs, spec, pools)
    state = rng.uniform(lo, hi, fleet)
    steps = rng.standard_normal((ticks, fleet))
    walk = np.empty((ticks, fleet), dtype=np.int32)
    for t in range(ticks):
        state = np.clip(state + steps[t], lo, hi)
        walk[t] = state
    codes = walk.reshape(-1)[:docs] - np.int32(lo)
    return codes + np.int32(lo), codes, np.arange(lo, hi + 1)
