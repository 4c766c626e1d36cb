"""BENCHMARK.json against the contract's limits and against the files
the harness finds by name."""
import json
import os
import re

import pytest

from harness import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return spec.benchmark()


def test_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["workloads"]) <= 24
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) \
        <= 64 * 1024
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])


def test_entries(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["why"]) <= 200
        assert c["file"].startswith(bench["paths"][0] + "/")
        assert any(w["config"] == c["name"] for w in bench["workloads"])
        cfg = json.load(open(os.path.join(spec.ROOT, c["file"])))
        for key in c["reduced"]:        # only depth is ever cut
            assert key == "num_hidden_layers"
            assert cfg[key] < cfg["source_" + key]
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["moves"] in e2e and m["source"] in SOURCES
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")


def test_every_cell_finds_its_files_and_reports_enough(bench):
    for w in bench["workloads"]:
        cell = spec.cell(w["name"], bench)
        e2e = [m["name"] for m in cell["end_to_end"]]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell["per_layer"]
        for m in cell["per_layer"]:
            # what a per-layer metric moves is reported in this cell
            assert m["moves"] in e2e, (w["name"], m["name"])
        for m in cell["end_to_end"] + cell["per_layer"]:
            mf = spec.metric_file(m["name"])
            assert callable(spec.reader(mf["reader"]))
            for key in ("unit", "better", "source", "layer", "moves"):
                assert mf.get(key) == m.get(key), (m["name"], key)


def test_the_kept_result_is_the_contracts_last_line():
    """The runner keeps the row, ``run.py`` prints it after the teardown
    note (tests/test_teardown.py has the order): the keys are the
    contract's, and it is handed over once."""
    from harness import result
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 1}
    result.final(correct=1, attempted=2.0, failed=0, device=device,
                 metrics={"setup_s": {"value": 1.5, "unit": "s"}},
                 breakdown={"device_ops": [], "idle_gaps": []})
    row = result.take()
    assert list(row) == ["correct", "attempted", "failed", "metrics",
                         "device", "breakdown"]
    assert row["correct"] is True and row["attempted"] == 2
    result.final(correct=True, attempted=1, failed=0, device=device,
                 metrics={})
    assert "breakdown" not in result.take()
    assert result.take() is None
