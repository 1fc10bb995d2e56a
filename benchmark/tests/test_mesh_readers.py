"""The reader of the four-chip cell (PR 29), on numbers made by hand:
mesh_kernel_roofline counts the cell's chips and is kernel_roofline
where there is one."""
import importlib

import pytest

import datagen
import small


def reader(name: str):
    return importlib.import_module("metrics." + name)


def roofline_ctx(cell_name: str, chips: int, busy_s: float, queries: float):
    _bench, _cell, config, mix = small.load_cell(cell_name)
    config = dict(config, chips=chips)
    return {"rehearsal": False, "busy_s": busy_s, "queries_in_trace": queries,
            "mix": mix, "config": config,
            "rows": config["segments"] * config["docs_per_segment"],
            "cardinalities": {n: len(d) for n, d in
                              datagen.domains(config).items()},
            "device_kind": "TPU v5 lite"}


def test_mesh_roofline_counts_the_cells_chips():
    # Q1.x reads 8 B a row: 4.096 GB over 512M rows, 1.25 ms at four
    # times 819 GB/s; 100 queries in 0.5 s of mean busy time are 5 ms each
    ctx = roofline_ctx("ssb4_scan_c8_4chip", 4, busy_s=0.5, queries=100.0)
    assert ctx["config"]["segments"] == 64
    want = 100.0 * (8 * 512_000_000 / (4 * 819e9)) / 0.005
    assert reader("mesh_kernel_roofline").read(ctx) == pytest.approx(want)
    assert want == pytest.approx(25.006, abs=1e-3)
    # the one-chip reader on the same numbers reads four times that
    assert reader("kernel_roofline").read(ctx) == pytest.approx(4 * want)


def test_mesh_roofline_is_kernel_roofline_on_one_chip():
    ctx = roofline_ctx("ssb1_scan_c8", 1, busy_s=0.9, queries=120.0)
    assert reader("mesh_kernel_roofline").read(ctx) == \
        pytest.approx(reader("kernel_roofline").read(ctx))


@pytest.mark.parametrize("change", [{"rehearsal": True}, {"busy_s": None},
                                    {"queries_in_trace": 0.0}])
def test_mesh_roofline_reads_none_without_a_device_trace(change):
    ctx = dict(roofline_ctx("ssb4_scan_c8_4chip", 4, 0.5, 100.0), **change)
    assert reader("mesh_kernel_roofline").read(ctx) is None
