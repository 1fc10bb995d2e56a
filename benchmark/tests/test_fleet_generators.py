"""The generators of a fleet's readings written in time order
(generators/fleet_hour.py, fleet_member.py, fleet_walk.py), on the TSBS
configuration's own columns: TSBS's shapes at the cell's size, the same
layout at a test's, and the segment's index read back from the seed."""
import numpy as np
import pytest

import datagen
import small

SEED = 2_600_000_035  # past 2**31, as the driver's are
LO = 403224


@pytest.fixture(scope="module")
def config():
    _bench, _cell, config, _mix = small.load_cell("tsbs_dgb1_c1")
    return config


def hours(config, segment: int, docs: int):
    return datagen.make_columns(config, SEED, segment, docs)["ts_hour"][0]


def test_the_cell_is_the_source_s_scale(config):
    assert config["segments"] * config["docs_per_segment"] \
        == 4000 * 8640 * 3 == 103_680_000
    assert len(config["pools"]["tsbs_hosts"]) == 4000


#: segment -> its stretch of the stream: the index with its bits reversed
STRETCH = [0, 8, 4, 12, 2, 10, 6, 14, 1, 9, 5, 13, 3, 11, 7, 15]


@pytest.mark.parametrize("segment", [0, 3, 8, 15])
def test_a_full_segment_is_4_5_hours_of_every_host_every_10_s(config, segment):
    docs = config["docs_per_segment"]
    made = datagen.make_columns(config, SEED, segment, docs)
    values, codes, domain = made["ts_hour"]
    assert (np.diff(values) >= 0).all()  # time order
    stretch = STRETCH[segment]
    first = LO + (stretch * 9) // 2
    assert values[0] == first and values[-1] == first + 4
    per_hour = np.bincount(codes)[codes[0]:]
    # exactly 360 ticks of 4000 hosts a whole hour, 180 the split one
    half = [180 * 4000] if stretch % 2 else []
    assert per_hour.tolist() == half + [360 * 4000] * 4 \
        + ([] if stretch % 2 else [180 * 4000])
    host_codes = made["hostname"][1]
    assert (host_codes[:8000] == np.tile(np.arange(4000), 2)).all()
    # 360 rows a host-hour
    one = (codes == codes[0] + 1) & (host_codes == 17)
    assert one.sum() == 360
    walk = made["usage_user"][0][host_codes == 17]
    assert walk.min() >= 0 and walk.max() <= 100
    assert np.abs(np.diff(walk)).max() <= 6  # N(0, 1) steps, no jumps
    assert (domain == np.arange(LO, LO + 72)).all()


def test_the_stretches_lie_end_to_end_whatever_a_segment_s_docs(config):
    spans = {}
    for segment, docs in enumerate([30000, 9000, 17001, 4000] * 4):
        h = hours(config, segment, docs)
        spans[STRETCH[segment]] = (h.min() - LO, h.max() - LO)
    for k in range(16):  # coarse ticks may stop short of the last hour
        assert spans[k][0] == (k * 9) // 2
        assert spans[k][0] <= spans[k][1] <= ((k + 1) * 9 - 1) // 2
    # a test's four segments are spread over the three days
    assert [spans[STRETCH[i]][0] for i in range(4)] == [0, 36, 18, 54]


def test_a_segment_past_the_three_days_is_refused(config):
    with pytest.raises(ValueError, match="of a table of 16 stretches"):
        hours(config, 16, 8000)


def test_the_same_seed_gives_the_same_rows_and_another_other_values(config):
    a = datagen.make_columns(config, SEED, 2, 20000)
    b = datagen.make_columns(config, SEED, 2, 20000)
    c = datagen.make_columns(config, SEED + 1, 2, 20000)
    for name in a:
        assert (a[name][1] == b[name][1]).all()
    assert (a["usage_user"][0] != c["usage_user"][0]).any()
    assert (a["ts_hour"][0] == c["ts_hour"][0]).all()
