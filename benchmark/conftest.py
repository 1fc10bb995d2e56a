"""tests/test_control.py keys what each configuration states (`STATED`)
by the configuration's name, and a PR that adds a configuration may not
edit it. ssb_flat_512m_4chip states what ssb_flat_256m_1chip states,
letter for letter (exact COUNT and integer SUM, limits 0), so it is
judged by the same controls. The line is added to the module pytest
collected the tests from, whatever name it was imported under, and a
`test_control.py` without such a table fails the collection rather than
the control being skipped for the new configuration. PERF.md section 7:
`STATED` should fall back to the configuration's own `guarantees` and
`limits`, so that no later configuration needs this hook."""
import pytest

SAME_AS = {"ssb_flat_512m_4chip": "ssb_flat_256m_1chip"}


def pytest_collection_modifyitems(items):
    controls = {item.module for item in items
                if item.path.name == "test_control.py"}
    for module in controls:
        stated = getattr(module, "STATED", None)
        if not isinstance(stated, dict) \
                or not set(SAME_AS.values()) <= set(stated):
            raise pytest.UsageError(
                f"{module.__file__} has no STATED table with "
                f"{sorted(SAME_AS.values())}: benchmark/conftest.py cannot "
                f"state {sorted(SAME_AS)} and the control would not be "
                f"asserted for them")
        for new, old in SAME_AS.items():
            stated.setdefault(new, stated[old])
