"""What decides `correct`: every answer the window got, beside the
reference's, and each number compared beside its limit.

The limits are the configuration's (`limits` in its file):
  wrong_answers   answers whose row count, group keys, COUNT or exact SUM
                  differ from the reference (limit 0: exact comparison)
  unanswered      queries sent that never brought an answer back
  not_device_served  traced queries without a DeviceDispatch span that
                  stayed on the device, or served by a result cache
  grouped_sum_max_rel_err  the widest |got - exact| / |exact| over every
                  grouped SUM cell (f32 on the device), where the
                  configuration states a tolerance; else grouped SUMs are
                  compared exactly too"""
from __future__ import annotations

FALLBACK_OUTCOMES = ("hostFallback", "scanFallback")


def spans(node, name: str) -> list:
    """Every span called `name` in a trace tree."""
    if not isinstance(node, dict):
        return []
    found = [node] if node.get("operator") == name else []
    for child in node.get("children", ()):
        found += spans(child, name)
    return found


def device_served(trace_info) -> bool:
    dispatches = spans(trace_info, "DeviceDispatch")
    return bool(dispatches) \
        and not spans(trace_info, "SegmentResultCache") \
        and all(d.get("outcome") not in FALLBACK_OUTCOMES
                for d in dispatches)


def compare_rows(template: dict, got: list, want: list, approx: bool):
    """(answer is wrong, widest relative error of an approximate sum)."""
    if len(got) != len(want):
        return True, 0.0
    grouped = bool(template["group_by"])
    worst = 0.0
    for g_row, w_row in zip(got, want):
        if len(g_row) != len(w_row):
            return True, worst
        for agg, g, w in zip(template["select"], g_row, w_row):
            if agg[0] == "key":
                if str(g) != str(w):
                    return True, worst
            elif approx and grouped and agg[0] != "count":
                if w == 0:
                    if float(g) != 0.0:
                        return True, worst
                else:
                    worst = max(worst, abs(float(g) - w) / abs(w))
            elif float(g) != float(w):
                return True, worst
    return False, worst


def judge(config: dict, templates: list, ref, records: list) -> dict:
    """records: dicts with `template` (index), `literals`, `rows` (None
    if no answer came), `traced`, `served`. Returns {"correct": bool,
    "compared": n, "checks": {name: {"value", "limit"}}}."""
    limits = config["limits"]
    approx = "grouped_sum_max_rel_err" in limits
    seen = {"wrong_answers": 0, "unanswered": 0, "not_device_served": 0}
    if approx:
        seen["grouped_sum_max_rel_err"] = 0.0
    compared = 0
    first_wrong = None
    for r in records:
        if r["rows"] is None:
            seen["unanswered"] += 1
            continue
        if r["traced"] and not r["served"]:
            seen["not_device_served"] += 1
        template = templates[r["template"]]
        want = ref.answer(template, r["literals"])
        wrong, err = compare_rows(template, r["rows"], want, approx)
        compared += 1
        if wrong:
            seen["wrong_answers"] += 1
            if first_wrong is None:
                first_wrong = {"template": template["name"],
                               "literals": r["literals"],
                               "got": r["rows"][:3], "want": want[:3]}
        if approx:
            seen["grouped_sum_max_rel_err"] = max(
                seen["grouped_sum_max_rel_err"], err)
    checks = {k: {"value": v, "limit": limits[k]} for k, v in seen.items()}
    correct = compared > 0 and all(c["value"] <= c["limit"]
                                   for c in checks.values())
    return {"correct": correct, "compared": compared, "checks": checks,
            "first_wrong": first_wrong}
