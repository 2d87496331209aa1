"""``BENCHMARK.json`` against the letter of the benchmark's contract, so
that a later PR that adds an entry sees a refusal here before the driver's."""

import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_and_sizes():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= BM["run_seconds"] <= 51
    assert BM["paths"] == ["benchmark"] and all(map(line, BM["command"]))
    assert 1 <= len(BM["configs"]) <= 24 and 1 <= len(BM["workloads"]) <= 24
    assert 1 <= len(BM["end_to_end"]) <= 16
    assert 1 <= len(BM["per_layer"]) <= 128
    four = sum(w["chips"] == 4 for w in BM["workloads"])
    assert four <= max(1, len(BM["workloads"]) // 2)


def test_configs():
    names = [c["name"] for c in BM["configs"]]
    assert len(set(names)) == len(names)
    files = [c["file"] for c in BM["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in BM["workloads"]}
    for c in BM["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["file"].startswith("benchmark/") and (ROOT / c["file"]).exists()
        assert len(c["reduced"]) <= 16 and all(map(NAME.match, c["reduced"]))
        assert c["name"] in used
        body = json.loads((ROOT / c["file"]).read_text())
        assert body["source"] == c["source"] and body["reduced"] == c["reduced"]
        assert all(k in body for k in c["reduced"])


def test_workloads():
    names = [w["name"] for w in BM["workloads"]]
    assert len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in BM["workloads"]]
    assert len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in BM["configs"]}
    for w in BM["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert line(w["why"])


def test_metrics():
    cells = {w["name"] for w in BM["workloads"]}
    e2e = {m["name"]: m for m in BM["end_to_end"]}
    names = [m["name"] for m in BM["end_to_end"] + BM["per_layer"]]
    assert len(set(names)) == len(names) and "setup_s" in e2e
    for m in BM["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                         "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BM["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                         "layer", "moves"}
        assert m["moves"] in e2e and line(m["layer"])
        reported_in = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", reported_in)) <= reported_in
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert set(m.get("workloads", [])) <= cells
    for cell in cells:
        def has(kind):
            return [m["name"] for m in BM[kind]
                    if cell in m.get("workloads", cells)]
        assert "setup_s" in has("end_to_end") and len(has("end_to_end")) >= 2
        assert has("per_layer")


def test_files_under_paths_are_named_from_a_names_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in (ROOT / "benchmark").rglob("*"):
        if "__pycache__" in p.parts:
            continue
        assert ok.match(str(p.relative_to(ROOT))), p
