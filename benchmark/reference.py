"""The plain reference: numpy over the generated columns, nothing of
pinot_tpu. A segment's share is a joint histogram over the
configuration's reference axes (row count and exact integer sum of each
measure a cell); the shares add up to the table's; every query the
templates can make is then a masked sum over that histogram.

`lower` computes the same in a lower precision or with a guarantee
broken: the control that `correct` has to refuse (tests/test_control.py,
control.py)."""
from __future__ import annotations

import numpy as np

#: controls: what a later PR might be tempted by, one step below what
#: the configuration states
CONTROLS = ("sums_f32", "inputs_bf16", "segment_dropped")


def segment_share(config: dict, made: dict, lower: str | None = None) -> dict:
    ref = config["reference"]
    shape = tuple(len(made[a][2]) for a in ref["axes"])
    flat = np.ravel_multi_index(tuple(made[a][1] for a in ref["axes"]), shape)
    cells = int(np.prod(shape))
    share = {"count": np.bincount(flat, minlength=cells), "sums": {}}
    for m in ref["measures"]:
        values = made[m][0]
        if lower == "inputs_bf16":
            import ml_dtypes
            values = values.astype(ml_dtypes.bfloat16)
        # float64 holds a segment's integer sums exactly (< 2**53)
        sums = np.bincount(flat, weights=values.astype(np.float64),
                           minlength=cells)
        if lower == "sums_f32":
            sums = sums.astype(np.float32)
        share["sums"][m] = sums.astype(np.int64)
    return share


class Reference:
    """The table's histogram and the answers read from it."""

    def __init__(self, config: dict, domains: dict):
        ref = config["reference"]
        self.axes = list(ref["axes"])
        self.domains = [np.asarray(domains[a]) for a in self.axes]
        self.shape = tuple(len(d) for d in self.domains)
        self.count = np.zeros(self.shape, dtype=np.int64)
        self.sums = {m: np.zeros(self.shape, dtype=np.int64)
                     for m in ref["measures"]}
        self.segments = 0

    def add(self, share: dict) -> None:
        self.count += share["count"].reshape(self.shape)
        for m, s in share["sums"].items():
            self.sums[m] += s.reshape(self.shape)
        self.segments += 1

    def answer(self, template: dict, literals: dict) -> list:
        """The rows the query has to return, in its select order."""
        picks = [np.ones(n, dtype=bool) for n in self.shape]
        for col, op, var in template["where"]:
            k = self.axes.index(col)
            dom, v = self.domains[k], literals[var]
            if op == "between":
                picks[k] &= (dom >= v[0]) & (dom <= v[1])
            elif op == "lt":
                picks[k] &= dom < v
            elif op == "eq":
                picks[k] &= dom == v
            else:
                raise ValueError(f"unknown predicate {op!r}")
        sel = [np.flatnonzero(p) for p in picks]
        box = np.ix_(*sel)
        keep = [self.axes.index(g) for g in template["group_by"]]
        drop = tuple(k for k in range(len(self.axes)) if k not in keep)

        def fold(cells):  # -> array over the group-by axes, in their order
            out = cells.sum(axis=drop)
            return np.transpose(out, np.argsort(np.argsort(keep)))

        count = fold(self.count[box])
        columns = []
        for agg in template["select"]:
            if agg[0] == "count":
                columns.append(count)
            elif agg[0] == "sum":
                columns.append(fold(self.sums[agg[1]][box]))
            elif agg[0] == "sum_product":  # SUM(measure * axis column)
                k = self.axes.index(agg[2])
                shape = [1] * len(self.axes)
                shape[k] = -1
                factor = self.domains[k][sel[k]].astype(np.int64)
                columns.append(fold(self.sums[agg[1]][box]
                                    * factor.reshape(shape)))
            elif agg[0] == "key":
                columns.append(None)
            else:
                raise ValueError(f"unknown aggregate {agg[0]!r}")
        if not keep:
            return [[int(c) for c in columns]]
        # ORDER BY the group-by columns: groups with no row do not appear
        key_values = [self.domains[k][sel[k]] for k in keep]
        orders = [np.argsort(kv, kind="stable") for kv in key_values]
        rows = []
        for idx in np.ndindex(*[len(o) for o in orders]):
            cell = tuple(o[i] for o, i in zip(orders, idx))
            if not count[cell]:
                continue
            row = []
            for agg, col in zip(template["select"], columns):
                if agg[0] == "key":
                    g = template["group_by"].index(agg[1])
                    v = key_values[g][cell[g]]
                    row.append(v if isinstance(v, str) else int(v))
                else:
                    row.append(int(col[cell]))
            rows.append(row)
        return rows
