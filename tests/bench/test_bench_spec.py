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
#: widths: never in ``reduced`` (besides any key ending in _dim, _rank, _width
#: or in _size other than vocab_size)
WIDTHS = {"hidden_size", "intermediate_size", "moe_intermediate_size", "num_experts_per_tok"}
EXPERT_COUNTS = ("n_routed_experts", "num_experts", "num_local_experts")


def _line(s: str) -> bool:
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def reduced_errors(reduced, cfg) -> list:
    """What is wrong with a configuration's ``reduced`` against its file.

    A width is never cut. Every key cut is stated with its published value
    under ``published``, and only those; the file states its deployment.
    What is left keeps the floors of a cut model: an eighth of the
    vocabulary, 8 routed experts (or all, where fewer are published), and 4
    layers after the leading dense ones.
    """
    errors = []
    for k in reduced:
        if (k in WIDTHS or k.endswith(("_dim", "_rank", "_width"))
                or (k.endswith("_size") and k != "vocab_size")):
            errors.append(f"{k} is a width")
    published = cfg.get("published", {})
    if set(published) != set(reduced):
        errors.append(f"published {sorted(published)} is not reduced {sorted(reduced)}")
    if not cfg.get("deployment"):
        errors.append("no deployment stated")
    for k in set(reduced) & set(published):
        if k not in cfg or cfg[k] == published[k]:
            errors.append(f"{k} is not cut from {published[k]}")
    if "vocab_size" in published and 8 * cfg.get("vocab_size", 0) < published["vocab_size"]:
        errors.append("less than an eighth of the vocabulary")
    for k in EXPERT_COUNTS:
        if k in published and cfg.get(k, 0) < min(8, published[k]):
            errors.append(f"{k} under 8")
    if "num_hidden_layers" in published:
        leading = cfg.get("first_k_dense_replace", cfg.get("num_dense_layers", 0))
        if cfg.get("num_hidden_layers", 0) - leading < 4:
            errors.append("fewer than 4 layers after the leading dense ones")
    return errors


MOONLIGHT = {  # the published shape of moonshotai/Moonlight-16B-A3B, cut for one chip
    "hidden_size": 2048, "intermediate_size": 11264, "moe_intermediate_size": 1408,
    "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "num_attention_heads": 16, "num_experts_per_tok": 6, "first_k_dense_replace": 1,
    "num_hidden_layers": 5, "n_routed_experts": 8, "vocab_size": 20480,
    "published": {"num_hidden_layers": 27, "n_routed_experts": 64, "vocab_size": 163840},
    "deployment": "eight chips share each layer: 8 of its 64 experts and 20480 vocabulary rows",
}
MOONLIGHT_REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]


@pytest.mark.parametrize("reduced,changes,ok", [
    (MOONLIGHT_REDUCED, {}, True),
    (["num_hidden_layers", "vocab_size"], {"n_routed_experts": 64, "published": {
        "num_hidden_layers": 27, "vocab_size": 163840}}, True),
    # a width cut, stated under published as a cut must be, still fails
    *[(MOONLIGHT_REDUCED + [k], {k: v, "published": dict(MOONLIGHT["published"],
                                                         **{k: MOONLIGHT[k]})}, False)
      for k, v in (("moe_intermediate_size", 704), ("kv_lora_rank", 256),
                   ("qk_rope_head_dim", 32), ("num_experts_per_tok", 2))],
    (MOONLIGHT_REDUCED, {"vocab_size": 20479}, False),
    (MOONLIGHT_REDUCED, {"n_routed_experts": 7}, False),
    (MOONLIGHT_REDUCED, {"num_hidden_layers": 4}, False),
    (MOONLIGHT_REDUCED, {"published": {"num_hidden_layers": 27, "vocab_size": 163840}}, False),
    (["num_hidden_layers", "vocab_size"], {}, False),
    (MOONLIGHT_REDUCED, {"deployment": ""}, False),
], ids=["moonlight_cut", "experts_whole", "expert_width", "latent_rank", "rope_head_dim",
        "experts_per_token", "vocab_under_an_eighth", "experts_under_8",
        "three_layers_after_dense", "reduced_key_not_published", "published_key_not_reduced",
        "no_deployment"])
def test_reduced_contract(reduced, changes, ok):
    cfg = dict(MOONLIGHT, **changes)
    assert (reduced_errors(reduced, cfg) == []) == ok, reduced_errors(reduced, cfg)


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
        assert reduced_errors(c["reduced"], harness.load_json(ROOT / c["file"])) == [], c["name"]
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

    # a model family, as files only: its module, and a configuration naming it
    (tmp_path / "bench/families/plain_mlp.py").write_text(PLAIN_MLP)
    train = bench_tiny.cell("train", tmp_path, seed=3_000_000_031)
    train.bench_dir = tmp_path / "bench"
    cfg = dict(train.config, bench_family="plain_mlp", mlp_width=96)
    del cfg["intermediate_size"]  # a key the dense family cannot run without
    train.config = cfg
    assert Path(harness.load_family(train).__file__).parent == tmp_path / "bench/families"
    line = bench_tiny.run(train)
    assert line["correct"] is True, line["checks"]
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0


#: a decoder whose MLP has no gate (``w_down(silu(w_up x))``), as a family
PLAIN_MLP = """
import dataclasses

import jax

from bench import weights
from bench.harness import BENCH_DIR, load_module
from bench.reference import decoder

dense = load_module(BENCH_DIR / "families" / "dense.py")


def _as_dense(cfg):
    return dict(cfg, intermediate_size=cfg["mlp_width"])


def program_config(cfg, *, attn_impl="auto"):
    return dataclasses.replace(dense.program_config(_as_dense(cfg), attn_impl=attn_impl),
                               glu=False)


def layout(cfg):
    tree = dense.layout(_as_dense(cfg))
    del tree["seg0"]["u0"]["mlp"]["w_gate"]
    return tree


def make_weights(cfg, seed):
    return weights.random_tree(layout(cfg), cfg["param_dtype"], seed)


def check_layout(cfg, program_model):
    weights.check_tree(layout(cfg), program_model)


class Plain(decoder.Decoder):
    def mlp(self, x, p):
        return decoder._mm(jax.nn.silu(decoder._mm(x, self.w(p["w_up"]))), self.w(p["w_down"]))


def loss_and_grad(cfg, weight_dtype=None):
    return jax.jit(jax.value_and_grad(Plain(cfg, weight_dtype).loss))


def flops_per_token(cfg, seq):
    d, ff = cfg["hidden_size"], cfg["mlp_width"]
    return dense.flops_per_token(_as_dense(cfg), seq) - 6.0 * cfg["num_hidden_layers"] * d * ff


def attention_calls(cfg, rows, seq):
    return dense.attention_calls(_as_dense(cfg), rows, seq)
"""


def test_unknown_names_are_errors():
    with pytest.raises(harness.BenchError):
        harness.find_cell("no.such.cell")
    with pytest.raises(harness.BenchError):
        harness.peaks_for("TPU v99")
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
