"""benchmark/tests/test_control.py keys what each configuration states
(`STATED`) by the configuration's name, and a PR that adds a
configuration may not edit it. A configuration whose own file has a
`controls` key ({"fails": [...], "passes": [...]}) states it there, and
this hook gives the collected `test_control.py` that entry (`setdefault`:
a line the table or benchmark/conftest.py already has is never
overridden). A collected `test_control.py` without such a table fails the
collection rather than the control being skipped; a run that collects
none (tier-1's `pytest tests/`) does nothing. PERF.md section 7 (c): the
next `benchmark` PR folds both hooks into test_control.py reading
`controls`."""
import json
import os

import pytest

ROOT = os.path.dirname(os.path.abspath(__file__))


def stated_in_files() -> dict:
    """{configuration: its file's `controls`} for every configuration of
    BENCHMARK.json whose file states them."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    stated = {}
    for entry in bench["configs"]:
        with open(os.path.join(ROOT, entry["file"])) as f:
            controls = json.load(f).get("controls")
        if controls is not None:
            stated[entry["name"]] = {"fails": list(controls["fails"]),
                                     "passes": list(controls["passes"])}
    return stated


def pytest_collection_modifyitems(items):
    controls = {item.module for item in items
                if item.path.name == "test_control.py"
                and item.path.parent.parent.name == "benchmark"}
    if not controls:
        return
    from_files = stated_in_files()
    for module in controls:
        stated = getattr(module, "STATED", None)
        if not isinstance(stated, dict):
            raise pytest.UsageError(
                f"{module.__file__} has no STATED table: conftest.py cannot "
                f"state {sorted(from_files)} and the control would not be "
                f"asserted for them")
        for name, entry in from_files.items():
            stated.setdefault(name, entry)
