"""`tsbs_cpu_104m_1chip` states exact COUNT and SUM, and every input and
every group's sum is exact in the two lower precisions the harness's
controls have (bf16 inputs, f32 sums), so those pass there. One step
further down is refused: a segment's share with its sums carried in
bfloat16 (8 bits: exact to 256, where a host-hour sums to thousands;
its COUNTs, 360 = 45 x 8 and 180, happen to be exact there too), judged
by the same judge.judge with the exact reference as the yardstick. And the stand-in
that leaves the LAST segment out is refused although most windows never
reach it: the control drops the segment the fewest queries touch."""
import ml_dtypes
import numpy as np
import pytest

import datagen
import judge
import reference
import small
import traffic

#: 16 segments as the cell has, 100 ticks each: 22 rows a host-hour
SEGMENTS, DOCS = 16, 400000


def bf16(a):
    return a.astype(ml_dtypes.bfloat16).astype(np.int64)


def verdicts(seed: int, n_queries: int = 12) -> dict:
    _bench, _cell, config, mix = small.load_cell("tsbs_dgb1_c1")
    doms = datagen.domains(config)
    names = ("exact", "sums_bf16", "last_dropped")
    refs = {n: reference.Reference(config, doms) for n in names}
    for i in range(SEGMENTS):
        share = reference.segment_share(
            config, datagen.make_columns(config, seed, i, DOCS))
        refs["exact"].add(share)
        refs["sums_bf16"].add(dict(share, sums={
            m: bf16(s) for m, s in share["sums"].items()}))
        if i != SEGMENTS - 1:
            refs["last_dropped"].add(share)
    queries = traffic.make_queries(mix, config["table"], seed, 1, n_queries,
                                   False)
    out = {}
    for name, stand_in in refs.items():
        records = [{"template": t, "literals": lit, "traced": False,
                    "served": False,
                    "rows": stand_in.answer(mix["templates"][t], lit)}
                   for t, lit, _sql in queries]
        out[name] = judge.judge(config, mix["templates"], refs["exact"],
                                records)
    return out


@pytest.mark.parametrize("seed", [5, 2_600_000_019])
def test_sums_in_bf16_and_the_last_segment_left_out_are_refused(seed):
    got = verdicts(seed)
    assert got["exact"]["correct"] is True
    assert got["exact"]["compared"] == 12
    for name in ("sums_bf16", "last_dropped"):
        assert got[name]["correct"] is False, name
        assert got[name]["checks"]["wrong_answers"]["value"] > 0
    # every window holds a group the rounding moves
    assert got["sums_bf16"]["checks"]["wrong_answers"]["value"] == 12
