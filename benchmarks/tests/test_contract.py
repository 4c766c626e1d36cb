"""BENCHMARK.json against the contract's limits and against the files
the harness finds by name."""
import fnmatch
import json
import os
import re

import pytest

from harness import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
# what ``reduced`` may never name: a width
WIDTH = re.compile(r"(_dim|_rank)$|hidden_size|intermediate|latent|state_size"
                   r"|head_dim|expand|per_tok")
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
        # every cut is listed and says what it was cut from: a key is in
        # ``reduced`` exactly when the file holds the source's value of
        # it beside its own, and the file's value is the smaller
        assert len(c["reduced"]) <= 16
        assert set(c["reduced"]) == {
            k[len("source_"):] for k in cfg
            if k.startswith("source_") and k[len("source_"):] in cfg}
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key), key
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
        assert cell["per_layer"]    # each held to its file below
        for m in cell["end_to_end"]:
            mf = spec.metric_file(m["name"])
            assert callable(spec.reader(mf["reader"]))
            for key in ("unit", "better", "source"):
                assert mf[key] == m[key], (m["name"], key)


def _prepared() -> list:
    """The metric files README.md lists under "A prepared ..." (a name
    or a ``*`` pattern in backquotes, ``metrics/<...>.json``): files
    whose entries a later PR adds."""
    with open(os.path.join(spec.BENCH_DIR, "README.md")) as f:
        paras = f.read().split("\n\n")
    return [name for para in paras if para.startswith("**A prepared")
            for name in re.findall(r"`metrics/([^`]+)\.json`", para)]


def _cells(entry, bench) -> list:
    """The cells an entry is read in: its ``workloads``, or every cell."""
    return entry.get("workloads", [w["name"] for w in bench["workloads"]])


@pytest.mark.parametrize(
    "entry", spec.benchmark()["per_layer"], ids=lambda m: m["name"])
def test_a_per_layer_entry_its_file_its_reader_and_its_cells(bench, entry):
    """One entry a metric where it may (PR 58): what decides a number is
    the entry's file and reader, what decides WHERE it is read is the
    entry's own ``workloads`` (``spec.cell`` reads no other list)."""
    mf = spec.metric_file(entry["name"])
    assert mf["name"] == entry["name"]
    for key in ("unit", "better", "source", "layer", "moves"):
        assert mf[key] == entry[key], (entry["name"], key)
    assert callable(spec.reader(mf["reader"]))
    cells = {w["name"] for w in bench["workloads"]}
    listed = _cells(entry, bench)
    assert listed and len(listed) == len(set(listed))
    for name in listed:
        assert name in cells, (entry["name"], name)
        cell = spec.cell(name, bench)
        assert entry["moves"] in {m["name"] for m in cell["end_to_end"]}
        assert entry in cell["per_layer"]
    # a file that keeps a list of its own lists no cell the entry lacks
    assert set(mf.get("workloads", listed)) <= set(listed)


def test_the_list_has_room_and_no_file_is_an_orphan(bench):
    assert len(bench["per_layer"]) <= 128
    # the same reading under two names in one cell is one entry too many
    # (a cell's own suffixes that tier-1 pins apart from another cell's
    # are different cells: PERF.md section 7)
    seen = {}
    for m in bench["per_layer"]:
        mf = spec.metric_file(m["name"])
        key = json.dumps([mf["reader"], mf.get("args", {})], sort_keys=True)
        for cell in _cells(m, bench):
            assert seen.setdefault((key, cell), m["name"]) == m["name"]
    entered = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    prepared = _prepared()
    assert prepared
    for f in sorted(os.listdir(os.path.join(spec.BENCH_DIR, "metrics"))):
        name, ext = os.path.splitext(f)
        assert ext == ".json", f
        assert name in entered or any(
            fnmatch.fnmatchcase(name, p) for p in prepared), \
            f"metrics/{f} has no entry and README.md prepares none"


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
    # what ``correct`` was decided from comes last, each beside its limit
    result.final(correct=True, attempted=1, failed=0, device=device,
                 metrics={}, breakdown={"device_ops": [], "idle_gaps": []},
                 compared={"logits_rel_err": {"value": 0.03, "limit": 0.04}})
    row = result.take()
    assert list(row)[-1] == "compared"
    assert row["compared"]["logits_rel_err"] == {"value": 0.03,
                                                 "limit": 0.04}
    result.final(correct=True, attempted=1, failed=0, device=device,
                 metrics={})
    assert "breakdown" not in result.take()
    assert result.take() is None
