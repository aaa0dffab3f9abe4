"""The last line's builder and validator: every malformed shape the driver
would refuse is refused here first."""

import copy
import json

import pytest

from benchmark.harness import lastline

E2E = {"events_per_s": "events/s", "setup_s": "s"}
LAYER = {"device_idle_share.drain": "%", "device_step_ms.drain": "ms"}


def good(traced: bool) -> dict:
    line = {
        "correct": True, "attempted": 400, "failed": 0,
        "metrics": {
            "events_per_s": {"value": 1.9e6, "unit": "events/s"},
            "setup_s": {"value": 9.5, "unit": "s"},
        },
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                   "memory_peak_bytes": 13958643712},
    }
    if traced:
        line["metrics"].update({
            "device_idle_share.drain": {"value": 99.2, "unit": "%"},
            "device_step_ms.drain": {"value": 0.4, "unit": "ms"},
        })
        line["device"].update({"busy_s": 0.3, "window_s": 40.0})
    return line


@pytest.mark.parametrize("traced", [False, True])
def test_good_line_passes(traced):
    wanted = LAYER if traced else E2E
    assert lastline.problems(good(traced), wanted, traced) == []
    assert lastline.check_text(json.dumps(good(traced)), wanted, traced) == []


def _drop(path):
    def f(line):
        d = line
        for k in path[:-1]:
            d = d[k]
        del d[path[-1]]
    return f


def _set(path, value):
    def f(line):
        d = line
        for k in path[:-1]:
            d = d[k]
        d[path[-1]] = value
    return f


MALFORMED = {
    "no_correct": (False, _drop(["correct"])),
    "no_attempted": (False, _drop(["attempted"])),
    "no_failed": (False, _drop(["failed"])),
    "no_metrics": (False, _drop(["metrics"])),
    "no_device": (False, _drop(["device"])),
    "correct_not_bool": (False, _set(["correct"], "yes")),
    "attempted_negative": (False, _set(["attempted"], -1)),
    "failed_float": (False, _set(["failed"], 0.5)),
    "metric_missing": (False, _drop(["metrics", "events_per_s"])),
    "metric_bare_number": (False, _set(["metrics", "setup_s"], 9.5)),
    "metric_no_unit": (False, _drop(["metrics", "setup_s", "unit"])),
    "metric_no_value": (False, _drop(["metrics", "setup_s", "value"])),
    "metric_nan": (False, _set(["metrics", "setup_s", "value"], float("nan"))),
    "metric_wrong_unit": (False, _set(["metrics", "setup_s", "unit"], "ms")),
    "metric_value_text": (False, _set(["metrics", "setup_s", "value"], "9.5")),
    "device_no_kind": (False, _drop(["device", "kind"])),
    "device_no_platform": (False, _drop(["device", "platform"])),
    "device_no_memory": (False, _drop(["device", "memory_peak_bytes"])),
    "device_count_zero": (False, _set(["device", "count"], 0)),
    "traced_layer_metric_missing": (True, _drop(["metrics", "device_step_ms.drain"])),
    "traced_no_busy": (True, _drop(["device", "busy_s"])),
    "traced_no_window": (True, _drop(["device", "window_s"])),
    "traced_busy_zero": (True, _set(["device", "busy_s"], 0.0)),
    "traced_busy_negative": (True, _set(["device", "busy_s"], -0.1)),
    "traced_busy_above_window": (True, _set(["device", "busy_s"], 40.5)),
    "traced_busy_none": (True, _set(["device", "busy_s"], None)),
    "breakdown_too_long": (True, _set(
        ["breakdown"], {"device_ops": [["op", 0.1]] * 11, "idle_gaps": []})),
    "breakdown_bad_row": (True, _set(
        ["breakdown"], {"device_ops": [["op"]], "idle_gaps": []})),
}


@pytest.mark.parametrize("case", sorted(MALFORMED))
def test_malformed_line_is_refused(case):
    traced, damage = MALFORMED[case]
    line = copy.deepcopy(good(traced))
    damage(line)
    assert lastline.problems(line, LAYER if traced else E2E, traced), case


def test_not_json_and_not_object_are_refused():
    assert lastline.check_text("correct: true", E2E, False)
    assert lastline.check_text("[1, 2]", E2E, False)


def test_build_refuses_and_builds():
    kw = dict(correct=True, attempted=3, failed=0,
              device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                      "memory_peak_bytes": 1},
              wanted=E2E, traced=False)
    with pytest.raises(lastline.Malformed):
        lastline.build(metrics={"setup_s": (1.0, "s")}, **kw)
    text = lastline.build(
        metrics={"setup_s": (1.0, "s"), "events_per_s": (2.0, "events/s")},
        compared={"rel_err_max": {"value": 1e-7, "limit": 2e-5}}, **kw)
    line = json.loads(text)
    assert list(line)[-1] == "compared"  # the numbers compared come last
    assert line["metrics"]["events_per_s"] == {"value": 2.0, "unit": "events/s"}
    assert "\n" not in text
