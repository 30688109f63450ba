"""The benchmark's registry: BENCHMARK.json and the files it names, each
configuration, traffic mix, limit and per-layer metric found by its name."""
import json
import re
import sys
from pathlib import Path

import pytest

CHIP_DIR = Path(__file__).resolve().parents[1]
ROOT = CHIP_DIR.parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(CHIP_DIR))

from chipbench import spec  # noqa: E402
from chipbench.harness import (cell_config, load_limits,  # noqa: E402
                               load_peaks, load_reader)
from chipbench.traffic import load_mix  # noqa: E402

BENCH = spec.load_benchmark(ROOT)
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


def test_benchmark_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert BENCH["command"][1] == "benchmarks/chip/run.py"
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.fullmatch(n) for n in names)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(cell):
    c = spec.find_cell(cell, BENCH)
    assert c.config["name"] == next(w["config"] for w in BENCH["workloads"]
                                    if w["name"] == cell)
    assert c.chips in (1, 4)
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert c.per_layer
    limits = load_limits(cell)
    keys = {"rows_off_pct", "unserved_slots"}
    if c.config["serving"]["checkpoint"]:
        keys.add("ckpt_windows_behind")
    assert set(limits) == keys


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_metric_has_a_reader(metric):
    assert callable(load_reader(metric))


@pytest.mark.parametrize("cfg", [c for c in BENCH["configs"]])
def test_config_file_is_its_own(cfg):
    path = ROOT / cfg["file"]
    assert path.parent == CHIP_DIR / "configs"
    body = json.loads(path.read_text())
    assert body["name"] == cfg["name"]
    assert body["reduced"] == cfg["reduced"]
    assert spec.load_config(cfg["name"]) == body


def test_unknown_names_are_refused():
    with pytest.raises(ValueError, match="unknown workload"):
        spec.find_cell("no-such.cell", BENCH)
    with pytest.raises(ValueError, match="unknown configuration"):
        spec.load_config("no-such-config")
    with pytest.raises(ValueError, match="unknown traffic mix"):
        load_mix("no-such-mix")
    with pytest.raises(ValueError, match="no reader"):
        load_reader("no_such_metric")
    with pytest.raises(ValueError, match="no limits"):
        load_limits("no-such.cell")
    with pytest.raises(ValueError, match="no peaks"):
        load_peaks("TPU v0")


def test_a_new_cell_needs_only_new_files(tmp_path, monkeypatch):
    """A cell added by an entry in BENCHMARK.json and a mix file of its own
    resolves without an edit to any file the benchmark has."""
    from chipbench import traffic
    mixes = tmp_path / "traffic"
    mixes.mkdir()
    mix = json.loads((CHIP_DIR / "traffic" / "soak.json").read_text())
    mix["name"] = "soak-w32"
    mix["window_slots"] = 32
    (mixes / "soak-w32.json").write_text(json.dumps(mix))
    monkeypatch.setattr(traffic, "TRAFFIC_DIR", mixes)
    bench = dict(BENCH)
    bench["workloads"] = BENCH["workloads"] + [
        {"name": "s01-deepstream.soak-w32", "config": "s01-deepstream",
         "traffic": "soak-w32", "chips": 1, "why": "32-slot windows"}]
    c = spec.find_cell("s01-deepstream.soak-w32", bench)
    assert c.mix["window_slots"] == 32
    # the runner windows as the mix says
    assert cell_config(c)["serving"]["window_slots"] == 32


def test_peaks_of_the_v5e():
    p = load_peaks("TPU v5 lite")
    assert p == {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
