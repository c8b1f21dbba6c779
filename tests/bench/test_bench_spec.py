"""BENCHMARK.json against the benchmark's contract, and discovery by name."""
from __future__ import annotations

import json
import re
import shutil
from pathlib import Path

import pytest

import bench_tiny  # noqa: F401  (puts the checkout on sys.path)
from bench import harness

ROOT = bench_tiny.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _line(s: str) -> bool:
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_benchmark_json_keeps_the_contract():
    spec = bench_tiny.spec()
    assert set(spec) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= len(spec["paths"]) <= 16
    for p in spec["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./\-]{1,200}", p) and ".." not in p
        assert (ROOT / p).is_dir()
    assert spec["command"][:2] == ["python3", "bench/run.py"]
    assert all(_line(w) for w in spec["command"])
    assert isinstance(spec["run_seconds"], int) and 1 <= spec["run_seconds"] <= 51
    configs = {c["name"]: c for c in spec["configs"]}
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["why"]) and _line(c["source"])
        assert any(c["file"].startswith(p + "/") for p in spec["paths"])
        assert (ROOT / c["file"]).is_file()
        assert all(NAME.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16
        assert not any(k.endswith(("_dim", "_rank", "_size")) for k in c["reduced"])
    cells = set()
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and _line(w["why"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in cells
        cells.add((w["config"], w["traffic"]))
    assert sum(w["chips"] == 4 for w in spec["workloads"]) <= max(1, len(cells) // 2)
    assert {c for c, _ in cells} == set(configs)
    names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(names) == len(set(names))
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in spec["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.25
    layers = {}
    for m in spec["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["source"] in SOURCES and m["moves"] in e2e and _line(m["layer"])
        layers.setdefault(m["layer"], m["layer"])
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for w in spec["workloads"]:
        cell = harness.find_cell(w["name"])
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer, w["name"]
        for m in spec["per_layer"]:
            if w["name"] in m.get("workloads", [w["name"]]):
                assert m["moves"] in reported


def test_every_file_of_a_cell_is_found_by_name():
    for w in bench_tiny.spec()["workloads"]:
        cell = harness.find_cell(w["name"])
        assert harness.load_driver(cell).run
        for m in cell.per_layer:
            assert callable(harness.load_reader(cell, m["name"]))


def test_new_cell_and_metric_need_no_edit(tmp_path: Path):
    """A later change adds files and entries only; the harness finds them."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = bench_tiny.spec()
    spec["workloads"].append({"name": "serve.qwen3-1.7b.burst", "config": "qwen3-1.7b",
                              "traffic": "serve.burst", "chips": 1, "why": "bursts"})
    spec["per_layer"].append({"name": "serve.requests_seen", "unit": "1", "better": "higher",
                              "source": "program_counter", "layer": "gateway and transport",
                              "moves": "serve_latency_p70_s",
                              "workloads": ["serve.qwen3-1.7b.burst"]})
    for m in spec["end_to_end"]:
        if "workloads" in m and "serve.qwen3-1.7b.agent" in m["workloads"]:
            m["workloads"].append("serve.qwen3-1.7b.burst")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    mix = json.loads((ROOT / "bench/traffic/serve.agent.json").read_text())
    mix["prompt_weights"] = [0.2, 0.3, 0.5]
    (tmp_path / "bench/traffic/serve.burst.json").write_text(json.dumps(mix))
    (tmp_path / "bench/workloads/serve.qwen3-1.7b.burst.json").write_text(
        (ROOT / "bench/workloads/serve.qwen3-1.7b.agent.json").read_text())
    (tmp_path / "bench/metrics/serve.requests_seen.py").write_text(
        "def read(obs):\n    return obs.counters.get('requests')\n")
    cell = harness.find_cell("serve.qwen3-1.7b.burst", bench_dir=tmp_path / "bench")
    assert cell.traffic["prompt_weights"] == [0.2, 0.3, 0.5]
    assert cell.driver == "serve"
    names = [m["name"] for m in cell.per_layer]
    assert names == ["serve.requests_seen"]
    obs = harness.Observations(cell=cell, counters={"requests": 7})
    assert harness.load_reader(cell, "serve.requests_seen")(obs) == 7


def test_unknown_names_are_errors():
    with pytest.raises(harness.BenchError):
        harness.find_cell("no.such.cell")
    with pytest.raises(harness.BenchError):
        harness.peaks_for("TPU v99")
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
