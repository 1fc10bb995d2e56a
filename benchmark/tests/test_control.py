"""The control of `correct`, at a size a test run can hold: the
reference one step below the precision the configuration states, or with
a segment left out, judged as a run is. It has to come out NOT correct;
the reference itself, and the precision the configuration does state,
have to come out correct."""
import pytest

import control
import small

#: what each configuration states, and so which control is one step below
STATED = {
    "ssb_flat_256m_1chip": {"fails": ["sums_f32", "inputs_bf16",
                                      "segment_dropped"],
                            "passes": ["reference_itself"]},
    # grouped SUM is f32 on the device: f32 sums are what it states
    "ssb_dims_128m_1chip": {"fails": ["inputs_bf16", "segment_dropped"],
                            "passes": ["reference_itself", "sums_f32"]},
}


@pytest.mark.parametrize("seed", [3, 2_600_000_017, 77])
@pytest.mark.parametrize("cell_name", small.cell_names())
def test_control_is_refused(cell_name, seed):
    _bench, cell, config, mix = small.load_cell(cell_name)
    got = control.readings(config, mix, seed, small.SEGMENTS, small.DOCS,
                           n_queries=90, workers=2)
    stated = STATED[cell["config"]]
    for name in stated["fails"]:
        assert got[name]["correct"] is False, (name, got[name])
    for name in stated["passes"]:
        assert got[name]["correct"] is True, (name, got[name])
