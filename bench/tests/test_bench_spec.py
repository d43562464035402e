"""BENCHMARK.json against the benchmark's contract, and every entry found
by name in its files."""
import json
import math
import re
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES_E2E = {"host_clock", "device_trace"}
SOURCES = SOURCES_E2E | {"program_span", "program_counter"}


def one_line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
        assert (ROOT / p).is_dir()
    for word in SPEC["command"]:
        assert one_line(word) and not word.startswith("/") and ".." not in word
    assert (ROOT / SPEC["command"][1]).is_file()
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_run_seconds_fits_a_full_check_of_24_cells():
    rs = SPEC["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    runs = 2 + 14 * 24
    assert runs * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_are_unique_and_valid():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), names
    metrics = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(metrics) == len(set(metrics))


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_config_files(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert one_line(cfg["source"]) and one_line(cfg["why"])
    path = ROOT / cfg["file"]
    assert path.is_file() and cfg["file"].startswith(tuple(SPEC["paths"]))
    body = json.loads(path.read_text())
    assert body["name"] == cfg["name"]
    assert body["reduced"] == cfg["reduced"]
    assert len(cfg["reduced"]) <= 16
    for key in cfg["reduced"]:
        assert NAME.match(key) and key in body
        assert not re.search(r"_dim$|_rank$|hidden|intermediate|head", key)
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    assert any(w["config"] == cfg["name"] for w in SPEC["workloads"])


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_cells_resolve_to_their_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4) and one_line(cell["why"])
    assert NAME.match(cell["config"]) and NAME.match(cell["traffic"])
    config = json.loads((BENCH / "configs" / f"{cell['config']}.json")
                        .read_text())
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    assert config["chips"] == cell["chips"]
    entry = BENCH / "entries" / f"{traffic['entry']}.py"
    assert entry.is_file() and "class Entry" in entry.read_text()
    # every number the check compares has a measured limit
    limits = config["limits"][traffic["op"]]
    assert limits and all(isinstance(v, float) and math.isfinite(v)
                          and v > 0 for v in limits.values())


def test_each_pair_once_and_four_chip_share():
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


def _cells_of(metric):
    return set(metric.get("workloads", [w["name"] for w in
                                        SPEC["workloads"]]))


@pytest.mark.parametrize("m", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_metrics(m):
    e2e = m in SPEC["end_to_end"]
    keys = {"name", "unit", "better", "source"} | (
        {"bound"} if e2e else {"layer", "moves"})
    assert set(m) - {"workloads"} == keys
    assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert m["source"] in (SOURCES_E2E if e2e else SOURCES)
    cells = {w["name"] for w in SPEC["workloads"]}
    assert _cells_of(m) <= cells and _cells_of(m)
    reader = BENCH / "metrics" / f"{m['name']}.py"
    assert reader.is_file() and "def read(" in reader.read_text()
    if e2e:
        assert 0.01 <= m["bound"] <= 0.25
    else:
        assert one_line(m["layer"])
        moves = {x["name"]: x for x in SPEC["end_to_end"]}[m["moves"]]
        assert _cells_of(m) <= _cells_of(moves)
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_every_cell_reports_enough():
    for w in SPEC["workloads"]:
        e2e = [m["name"] for m in SPEC["end_to_end"]
               if w["name"] in _cells_of(m)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(w["name"] in _cells_of(m) for m in SPEC["per_layer"])


def test_setup_bound():
    setup = [m for m in SPEC["end_to_end"] if m["name"] == "setup_s"]
    assert setup and setup[0]["bound"] <= 0.25


def test_peaks_table_has_the_published_v5e_figures():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    assert "Google Cloud" in peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9


def test_files_under_paths_are_named_from_name_characters():
    for p in BENCH.rglob("*"):
        if "__pycache__" in p.parts or p.is_dir():
            continue
        rel = p.relative_to(ROOT).as_posix()
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", rel), rel
