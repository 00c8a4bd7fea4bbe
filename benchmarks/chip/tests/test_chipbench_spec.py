"""``BENCHMARK.json`` keeps to the benchmark's contract, and every name
in it finds its file."""
import json
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[3]
SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
CHIP = REPO / "benchmarks" / "chip"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_top_level():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["benchmarks/chip"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert isinstance(SPEC["run_seconds"], int)
    assert all(isinstance(w, str) and len(w) <= 200
               for w in SPEC["command"])
    assert (REPO / SPEC["command"][1]).is_file()
    assert len(json.dumps(SPEC)) < 64 * 1024


@pytest.mark.parametrize("kind", sorted(KEYS))
def test_entries(kind):
    names = [e["name"] for e in SPEC[kind]]
    assert len(names) == len(set(names))
    for e in SPEC[kind]:
        extra = {"workloads"} if kind in ("end_to_end", "per_layer") else set()
        assert KEYS[kind] <= set(e) <= KEYS[kind] | extra, e
        assert NAME.match(e["name"]), e["name"]
        for k in ("why", "layer", "source"):
            if k in e and kind in ("configs", "workloads", "per_layer"):
                assert 1 <= len(e[k]) <= 200 and "\n" not in e[k]
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower",
                                                              "higher")


def test_names_find_their_files():
    configs = {c["name"]: c for c in SPEC["configs"]}
    for c in configs.values():
        assert c["file"].startswith("benchmarks/chip/")
        conf = json.loads((REPO / c["file"]).read_text())
        assert conf["reduced"] == c["reduced"] == []
        assert conf["source"] == c["source"]
        assert (CHIP / conf["adapter"]).is_file()
        assert (CHIP / conf["reference"]).is_file()
        assert "assumed" in conf
    for w in SPEC["workloads"]:
        assert w["config"] in configs and w["chips"] == 1
        mix = json.loads((CHIP / "traffic" / f"{w['traffic']}.json")
                         .read_text())
        assert (CHIP / "traffic" / f"{mix['loop']}.py").is_file()
        check = json.loads((CHIP / "checks" / f"{w['name']}.json")
                           .read_text())
        assert {"served_gap_mean", "tokens_compared"} <= set(check)
    for kind in ("end_to_end", "per_layer"):
        for m in SPEC[kind]:
            assert (CHIP / "metrics" / f"{m['name']}.py").is_file()


def test_bounds_and_moves():
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        reporting = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m["workloads"]) <= reporting & cells
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for w in cells:
        assert sum(w in m.get("workloads", cells)
                   for m in SPEC["end_to_end"]) >= 2
        assert any(w in m["workloads"] for m in SPEC["per_layer"])
