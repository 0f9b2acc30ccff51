"""BENCHMARK.json keeps to its contract, and every cell finds its files
by name."""
import copy
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
# a key that holds a shape: frame sizes, widths, ranks, head sizes
WIDTH = re.compile(r"_dim$|_rank$|_size$|width|height|^frame$|hidden|"
                   r"intermediate|latent|head|expansion|per_tok")


def _configs(spec, root=ROOT):
    return {c["file"]: load_json(os.path.join(root, c["file"]))
            for c in spec["configs"]}


def contract_faults(spec: dict, configs: dict) -> list[str]:
    """What in ``spec`` breaks the rules on cuts and chips; ``configs``
    maps each configuration's ``file`` to what the file holds.

    A cut is a key of the configuration file, named as a name is and no
    width, listed alike in ``BENCHMARK.json`` and in the file, whose
    ``published`` gives the value the source states for it. A cell takes
    1 or 4 chips, and at most half the cells, rounded down, take 4 (one
    always may)."""
    faults = []
    for c in spec["configs"]:
        name, cuts, cfg = c["name"], c["reduced"], configs[c["file"]]
        if not isinstance(cuts, list) or len(cuts) > 16:
            faults.append(f"{name}: reduced {cuts!r} is not a list of at "
                          f"most 16 keys")
            continue
        for k in cuts:
            if not (isinstance(k, str) and NAME.match(k)):
                faults.append(f"{name}: cut {k!r} is not a key's name")
            elif WIDTH.search(k):
                faults.append(f"{name}: cut {k!r} names a width")
            elif k not in cfg:
                faults.append(f"{name}: cut {k!r} is no key of {c['file']}")
        if len(set(map(str, cuts))) != len(cuts):
            faults.append(f"{name}: a cut is listed twice")
        if cfg.get("reduced") != cuts:
            faults.append(f"{name}: reduced {cuts} in BENCHMARK.json but "
                          f"{cfg.get('reduced')} in {c['file']}")
        published = cfg.get("published", {})
        if set(published) != set(map(str, cuts)) or any(
                v in (None, "", [], {}) for v in published.values()):
            faults.append(f"{name}: published {published} does not give "
                          f"the source's value of each cut {cuts}")
    chips = [w["chips"] for w in spec["workloads"]]
    for w in spec["workloads"]:
        if w["chips"] not in (1, 4):
            faults.append(f"{w['name']}: chips {w['chips']} is not 1 or 4")
    most = max(1, len(chips) // 2)
    if chips.count(4) > most:
        faults.append(f"{chips.count(4)} four-chip cells among "
                      f"{len(chips)}: at most {most}")
    return faults


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51


def test_entries_keep_their_keys_names_and_units():
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200
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


def _cut(spec, configs, bench=("octaves",), file=("octaves",),
         published=None):
    """Cut ``octaves`` of the first configuration: ``bench`` is its
    ``reduced`` in BENCHMARK.json, ``file`` in its configuration file."""
    c = spec["configs"][0]
    cfg = configs[c["file"]]
    c["reduced"], cfg["reduced"] = list(bench), list(file)
    cfg["octaves"] = 1
    cfg["published"] = ({"octaves": "about 9, from the doubled image"}
                        if published is None else published)


def _chips(*chips):
    def mutate(spec, configs):
        w = spec["workloads"][0]
        spec["workloads"] = [dict(w, name=f"cell{i}", chips=n)
                             for i, n in enumerate(chips)]
    return mutate


@pytest.mark.parametrize("mutate,fault", [
    (lambda s, c: None, None),
    (_cut, None),
    (_chips(4, 1), None),
    (_chips(4, 1, 4, 1), None),
    (lambda s, c: _cut(s, c, bench=[""], file=[""],
                       published={"": 9}), "is not a key's name"),
    (lambda s, c: _cut(s, c, file=[]), "in BENCHMARK.json but"),
    (lambda s, c: _cut(s, c, published={}), "does not give"),
    (lambda s, c: _cut(s, c, bench=["frame"], file=["frame"],
                       published={"frame": 1}), "names a width"),
    (lambda s, c: _cut(s, c, bench=["layers"], file=["layers"],
                       published={"layers": 9}), "no key of"),
    (lambda s, c: (_cut(s, c), s["configs"][0].update(reduced="octaves")),
     "is not a list"),
    (_chips(2), "is not 1 or 4"),
    (_chips(4, 4), "2 four-chip cells among 2"),
], ids=["as-committed", "cut", "one-4-of-2", "two-4-of-4", "empty-cut",
        "cut-not-in-file", "no-published-value", "width-cut",
        "cut-no-key", "cuts-not-a-list", "chips-2", "two-4-of-2"])
def test_cuts_and_chips(mutate, fault):
    """The committed spec, a cut written alike in both places and
    four-chip cells within their share pass; each bad spec is named."""
    spec = copy.deepcopy(SPEC)
    configs = _configs(spec)
    mutate(spec, configs)
    faults = contract_faults(spec, configs)
    if fault is None:
        assert faults == []
    else:
        assert len(faults) == 1 and fault in faults[0], faults


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


@pytest.mark.parametrize("name,cuts", [
    ("canny-m-tiny", {}),
    # a configuration cut from its source: its batch held at 2 of 4
    ("canny-m-b2", {"engine": ({"max_batch": 2}, {"max_batch": 4})}),
], ids=["uncut", "cut"])
def test_new_cell_from_new_files_only(tmp_path, capsys, monkeypatch, name,
                                      cuts):
    """A configuration, cut or not, and a traffic mix that exist only as
    new files, and a cell naming them, run with no file of ``bench/``
    edited. ``cuts`` maps each cut key to its change and the published
    value it departs from."""
    from bench import run
    monkeypatch.setattr(run, "enable_compile_cache", lambda: "")
    before = _digest(os.path.join(ROOT, "bench"))
    root = make_root(tmp_path)
    cfg = load_json(os.path.join(root, "bench/configs/canny-m-1080p.json"))
    cfg["name"] = name
    for key, (change, published) in cuts.items():
        cfg[key].update(change)
        cfg.setdefault("published", {})[key] = published
    cfg["reduced"] = list(cuts)
    with open(os.path.join(root, f"bench/configs/{name}.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(root, "bench/traffic/burst2.json"), "w") as f:
        json.dump({"kind": "closed", "streams": 2, "queued": 3}, f)
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    spec["configs"].append({"name": name, "source": "test",
                            "file": f"bench/configs/{name}.json",
                            "reduced": list(cuts), "why": "test"})
    spec["workloads"].append({"name": f"{name}.burst2", "config": name,
                              "traffic": "burst2", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "frames_per_s" in (m["name"], m.get("moves")):
            m["workloads"].append(f"{name}.burst2")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    assert contract_faults(spec, _configs(spec, root)) == []
    res = run_cell(root, f"{name}.burst2", capsys)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"frames_per_s", "setup_s"}
    assert list(res)[-1] == "compared"
    assert _digest(os.path.join(ROOT, "bench")) == before
