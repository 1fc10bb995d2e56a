"""chip_smoke.py's contract, as far as a machine without a chip can show
it: the explicit CPU rehearsal drives every phase and reports every field
but can never read as a pass; the default invocation fails without a TPU;
a copy of the script alone fails too; the parent never imports jax; a
compile cache placed from outside is the only one used."""
import glob
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, env=None, timeout=600):
    return subprocess.run([sys.executable, SMOKE, *args], cwd=REPO,
                          env={**os.environ, **(env or {})},
                          capture_output=True, text=True, timeout=timeout)


def test_cpu_rehearsal_reports_every_field_and_is_not_a_pass(tmp_path):
    cache = str(tmp_path / "compile_cache")
    default_cache = os.path.join(REPO, ".jax_compile_cache")
    before = sorted(os.listdir(default_cache)) \
        if os.path.isdir(default_cache) else None
    proc = _run(["--cpu-rehearsal"],
                env={"JAX_COMPILATION_CACHE_DIR": cache})
    assert proc.returncode == 10, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert "REHEARSAL" in lines[0] and "REHEARSAL" in lines[-3]
    # the last line is the verdict: exactly these keys, nothing else
    assert json.loads(lines[-1]) == {
        "ok": False,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    assert lines[-2].startswith("chip_smoke: report {")
    out = json.loads(lines[-2].removeprefix("chip_smoke: report "))
    assert out["rehearsal"] is True
    assert out["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert set(out["versions"]) == {"jax", "jaxlib", "libtpu"}
    assert out["x64"] is False  # the chip's staging dtype, not the suite's
    assert out["seed"] == 21 and out["segments"] == 2
    assert out["rows"] == out["segments"] * out["docs_per_segment"]
    assert len(out["reduced"]) == 2  # both cuts are named
    assert out["native_lib"] in ("built", "absent")
    assert out["uploaded_bytes"] > 0 and out["hbm_cache_bytes"] > 0
    assert out["memory"][0]["device"] == "cpu:0"
    assert out["parent_imported_jax"] is False

    assert [q["name"] for q in out["queries"]] == [
        "scan_sum", "groupby_onehot", "groupby_scatter", "hll", "tdigest",
        "topn", "in_list"]
    for q in out["queries"]:
        assert q["device_served"] is True
        assert q["warm_samples"] >= 5 and q["warm_median_ms"] > 0
        assert q["cold_ms"] > 0 and q["restart_cold_ms"] > 0
        assert q["warm_upload_bytes"] == 0 and q["warm_compiles"] == 0
    assert out["queries"][0]["cold_upload_bytes"] > 0
    assert [leg["served_meter"] for leg in out["legs"]] == [
        "startree_served", "clp_served", "vector_served",
        "timeseries_leaf_device"]

    # the cache went where JAX_COMPILATION_CACHE_DIR said, nowhere else,
    # and the restarted server found every program in it
    assert out["compile_cache_dir"] == cache
    entries = [n for n in os.listdir(cache) if n.endswith("-cache")]
    assert len(entries) == out["compile_cache_entries"] > 0
    assert out["restart"]["new_compile_cache_entries"] == 0
    after = sorted(os.listdir(default_cache)) \
        if os.path.isdir(default_cache) else None
    assert after == before
    # the HBM budgets are per chip and the engine multiplies them by the
    # chips it holds: the smoke hands no server a configuration file,
    # with --chips 4 neither (ISSUE 29)
    with open(SMOKE) as f:
        source = f.read()
    assert "--config" not in source and ".properties" not in source


@pytest.mark.skipif(bool(glob.glob("/dev/accel*") or glob.glob("/dev/vfio/*")),
                    reason="this machine may hold a TPU")
def test_default_invocation_fails_without_a_tpu():
    proc = _run([], timeout=300)
    assert proc.returncode not in (0, 10)
    assert "server exited" in proc.stderr
    assert "Unable to initialize backend 'tpu'" in proc.stderr
    # no result: neither the verdict line nor the report
    assert not [ln for ln in proc.stdout.splitlines()
                if ln.startswith("{") or "report" in ln]


def test_alone_without_the_program_it_fails_and_makes_nothing(tmp_path):
    shutil.copy(SMOKE, tmp_path)
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env={k: v for k, v in os.environ.items()
                               if k != "PYTHONPATH"},
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "No module named 'pinot_tpu'" in proc.stderr
    assert not proc.stdout.strip()
    assert os.listdir(tmp_path) == ["chip_smoke.py"]
