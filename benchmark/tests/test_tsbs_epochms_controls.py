"""`tsbs_cpu_epochms_104m_1chip` states exact COUNT and SUM as
`tsbs_cpu_104m_1chip` does, over the same rows, with its hour axis in
epoch milliseconds: the controls its file names, at 16 segments as the
cell has (100 ticks each: 22 rows a host-hour), judged by judge.judge
with the exact reference as the yardstick through control.readings. The
reference itself, f32 sums and bf16 inputs pass (every input and every
group's sum is exact there); a segment left out is refused, and so are
sums carried in bfloat16, one step further down."""
import ml_dtypes
import numpy as np

import control
import datagen
import judge
import reference
import small
import traffic

SEGMENTS, DOCS = 16, 400000


def test_the_configuration_s_controls():
    """90 answers: a window reaches the last segment's 4.5 hours about
    one draw in twelve."""
    seed = 2_600_000_045
    _bench, _cell, config, mix = small.load_cell("tsbs_ts_dgb1_c1")
    got = control.readings(config, mix, seed, SEGMENTS, DOCS, n_queries=90,
                           workers=2)
    for name in config["controls"]["passes"]:
        assert got[name]["correct"] is True, (name, got[name])
        assert got[name]["compared"] == 90
    for name in config["controls"]["fails"]:
        assert got[name]["correct"] is False, (name, got[name])
        assert got[name]["wrong_answers"] > 0
    assert config["controls"]["fails"] == ["segment_dropped"]


def test_sums_in_bf16_are_refused():
    seed = 2_600_000_046
    _bench, _cell, config, mix = small.load_cell("tsbs_ts_dgb1_c1")
    doms = datagen.domains(config)
    exact = reference.Reference(config, doms)
    lower = reference.Reference(config, doms)
    for i in range(SEGMENTS):
        share = reference.segment_share(
            config, datagen.make_columns(config, seed, i, DOCS))
        exact.add(share)
        lower.add(dict(share, sums={
            m: s.astype(ml_dtypes.bfloat16).astype(np.int64)
            for m, s in share["sums"].items()}))
    queries = traffic.make_queries(mix, config["table"], seed, 1, 12, False)
    records = [{"template": t, "literals": lit, "traced": False,
                "served": False, "rows": lower.answer(mix["templates"][t], lit)}
               for t, lit, _sql in queries]
    verdict = judge.judge(config, mix["templates"], exact, records)
    assert verdict["correct"] is False
    # every window holds a group the rounding moves
    assert verdict["checks"]["wrong_answers"]["value"] == 12
