"""BENCHMARK.json keeps to its contract, and every cell finds its files
by name."""
import hashlib
import json
import os
import re

import pytest

from bench.spec import ROOT, Bench, load_json
from bench.tests.tiny import make_root, run_cell

SPEC = load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51


def test_entries_keep_their_keys_names_and_units():
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and not c["reduced"]
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and len(w["why"]) <= 200
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
    entries = (SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"]
               + SPEC["per_layer"])
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    names = [e["name"] for e in entries]
    assert len(names) == len(set(names))


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_by_name(name):
    cell = Bench().cell(name)
    assert cell.config["name"] == next(
        w["config"] for w in SPEC["workloads"] if w["name"] == name)
    assert cell.traffic["kind"] in ("closed", "open")
    assert callable(cell.driver())
    ref = cell.reference()
    assert callable(ref.output)
    assert ref.HISTORY == cell.config["roofline"]["history_frames"]
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(cell.reader(m["name"]))
        if "workloads" in m:
            assert name in m["workloads"]
    for m in cell.per_layer:
        assert m["moves"] in e2e


def _digest(top):
    h = hashlib.sha256()
    for dirpath, dirs, files in sorted(os.walk(top)):
        dirs[:] = sorted(d for d in dirs if d != "__pycache__")
        for f in sorted(files):
            p = os.path.join(dirpath, f)
            h.update(p.encode())
            with open(p, "rb") as fh:
                h.update(fh.read())
    return h.hexdigest()


def test_new_cell_from_new_files_only(tmp_path, capsys, monkeypatch):
    """A configuration and a traffic mix that exist only as new files,
    and a cell naming them, run with no file of ``bench/`` edited."""
    from bench import run
    monkeypatch.setattr(run, "enable_compile_cache", lambda: "")
    before = _digest(os.path.join(ROOT, "bench"))
    root = make_root(tmp_path)
    cfg = load_json(os.path.join(root, "bench/configs/canny-m-1080p.json"))
    cfg["name"] = "canny-m-tiny"
    with open(os.path.join(root, "bench/configs/canny-m-tiny.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(root, "bench/traffic/burst2.json"), "w") as f:
        json.dump({"kind": "closed", "streams": 2, "queued": 3}, f)
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    spec["configs"].append({"name": "canny-m-tiny", "source": "test",
                            "file": "bench/configs/canny-m-tiny.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "canny-m-tiny.burst2",
                              "config": "canny-m-tiny", "traffic": "burst2",
                              "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "frames_per_s" in (m["name"], m.get("moves")):
            m["workloads"].append("canny-m-tiny.burst2")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    res = run_cell(root, "canny-m-tiny.burst2", capsys)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"frames_per_s", "setup_s"}
    assert list(res)[-1] == "compared"
    assert _digest(os.path.join(ROOT, "bench")) == before
