"""generators/fleet_ts.py on the configuration `tsbs_cpu_epochms_104m_1chip`
names: the instant of every reading as epoch milliseconds in fleet_hour's
layout, and the same instant truncated to the hour for the reference's
axis, at the cell's size and at a test's."""
import numpy as np
import pytest

import datagen
import small

SEED = 2_600_000_045  # past 2**31, as a benchmark run's seeds are
ORIGIN = 1_451_606_400_000  # 2016-01-01T00:00Z
HOUR = 3_600_000
#: segment -> its stretch of the stream: the index with its bits reversed
STRETCH = [0, 8, 4, 12, 2, 10, 6, 14, 1, 9, 5, 13, 3, 11, 7, 15]


@pytest.fixture(scope="module")
def config():
    _bench, _cell, config, _mix = small.load_cell("tsbs_ts_dgb1_c1")
    return config


def test_the_table_is_the_sibling_s_rows_with_a_millisecond_clock(config):
    _b, _c, sibling, _m = small.load_cell("tsbs_dgb1_c1")
    assert config["segments"] * config["docs_per_segment"] == 103_680_000
    assert config["pools"] == sibling["pools"]
    a = datagen.make_columns(config, SEED, 6, 40000)
    b = datagen.make_columns(sibling, SEED, 6, 40000)
    for name in ("hostname", "usage_user"):
        assert (a[name][0] == b[name][0]).all()
    # the sibling's epoch hour is this table's hour column, in ms
    assert (a["ts_hour_ms"][0] == b["ts_hour"][0].astype(np.int64) * HOUR
            ).all()
    assert [c["name"] for c in config["columns"] if not c["dictionary"]] \
        == ["ts", "usage_user"]


@pytest.mark.parametrize("segment", [0, 7, 15])
def test_a_full_segment_is_4_5_hours_of_ticks_10_s_apart(config, segment):
    made = datagen.make_columns(config, SEED, segment,
                                config["docs_per_segment"])
    ts, codes, domain = made["ts"]
    first = ORIGIN + STRETCH[segment] * 16_200_000
    assert ts.dtype == np.int64 and (domain[codes] == ts).all()
    assert ts[0] == first and ts[-1] == first + 16_190_000
    ticks = ts.reshape(1620, 4000)
    assert (ticks == ticks[:, :1]).all()
    hour = made["ts_hour_ms"][0]
    assert (hour == ts - (ts - ORIGIN) % HOUR).all()
    assert len(made["ts_hour_ms"][2]) == 72 \
        and made["ts_hour_ms"][2][0] == ORIGIN


def test_a_test_s_segments_keep_to_their_stretch(config):
    for segment, docs in enumerate([30000, 9000, 17001, 4000] * 4):
        ts = datagen.make_columns(config, SEED, segment, docs)["ts"][0]
        first = ORIGIN + STRETCH[segment] * 16_200_000
        assert ts.min() == first and ts.max() < first + 16_200_000
