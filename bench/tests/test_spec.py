"""BENCHMARK.json and the files it names load by name, keep to the
contract's shape, and a malformed file is refused."""
import json
import re

import pytest

from bench.harness import spec

UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
PUT_SET = {"put_MiBps", "put_p95_ms", "setup_s"}
INTENDED = {
    "registry.put": PUT_SET,
    "registry.put-small": PUT_SET,
    "registry.get": {"get_MiBps", "get_p95_ms", "setup_s"},
    "ckpt.save": {"put_MiBps", "setup_s"},
}


@pytest.mark.parametrize("name", CELLS)
def test_cell_loads_with_its_metrics(name):
    cell = spec.load_cell(name, BENCH)
    assert cell.chips == 1
    reported = [m["name"] for m in cell.end_to_end]
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert m["moves"] in reported
        assert callable(spec.load_reader(m["name"]))


@pytest.mark.parametrize("name", sorted(INTENDED))
def test_cell_reports_its_intended_end_to_end_set(name):
    cell = spec.load_cell(name, BENCH)
    reported = {m["name"] for m in cell.end_to_end}
    assert reported == INTENDED[name]
    for m in cell.per_layer:
        assert m["moves"] in reported, m["name"]


def test_small_object_guard_reads_what_registry_put_reads():
    put = spec.load_cell("registry.put", BENCH)
    small = spec.load_cell("registry.put-small", BENCH)
    assert [m["name"] for m in small.per_layer] == \
        [m["name"] for m in put.per_layer]
    assert not any(m["name"].startswith("ckpt.") for m in small.per_layer)


def test_metrics_are_well_formed():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        for cell in m.get("workloads", []):
            assert cell in CELLS
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in E2E and "bound" not in m
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert "\n" not in m["layer"]


def test_configs_are_used_and_name_their_cuts():
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used
        data = spec.load_config(c)
        for key in c["reduced"]:
            assert key in data and key in data["reduced"]


def _write(tmp_path, data):
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(data) if not isinstance(data, str) else data)
    return path


@pytest.mark.parametrize("breakage", ["extra_key", "bad_name", "twice",
                                      "not_json"])
def test_malformed_benchmark_is_refused(tmp_path, breakage):
    data = json.loads(json.dumps(BENCH))
    if breakage == "extra_key":
        data["notes"] = "x"
    elif breakage == "bad_name":
        data["workloads"][0]["name"] = "has space"
    elif breakage == "twice":
        data["per_layer"].append(dict(data["per_layer"][0]))
    else:
        data = "{not json"
    with pytest.raises(spec.SpecError):
        spec.load_benchmark(_write(tmp_path, data))


@pytest.mark.parametrize("traffic", [
    {"kind": "open_loop", "mix": {"put": 1}},
    {"kind": "closed_loop", "clients": 8, "mix": {"delete": 1}},
    {"kind": "closed_loop", "clients": 8, "mix": {"put": 0}},
    {"kind": "closed_loop", "mix": {"put": 1}},
    {"kind": "checkpoint", "step_ring": 2, "mix": {"get": 1}},
])
def test_malformed_traffic_is_refused(traffic):
    with pytest.raises(spec.SpecError):
        spec.check_traffic(traffic, "t.json")


def test_unknown_names_are_refused():
    with pytest.raises(spec.SpecError):
        spec.load_cell("no.such.cell", BENCH)
    with pytest.raises(spec.SpecError):
        spec.load_reader("no.such.metric")
    with pytest.raises(spec.SpecError):
        spec.load_traffic("../BENCHMARK")
