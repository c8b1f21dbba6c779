"""Launch-layer unit tests: collective parser, roofline fit, probe configs,
shape applicability — pure functions, no 512-device init needed."""
import os

import pytest

os.environ.setdefault("DRYRUN_XLA_FLAGS", "")  # keep 1 device in this proc

from repro.configs import SHAPES, cell_applicability, get_config, input_specs, list_archs
from repro.launch.dryrun import model_flops, parse_collective_bytes
from repro.launch.roofline import fit_linear, probe_cfg, true_repeats


# ---------------------------------------------------------------------------
# HLO collective parser
# ---------------------------------------------------------------------------
HLO = """
  %all-reduce.109 = (f32[16,4096,2048]{2,1,0}, f32[16,4096,2048]{2,1,0}) all-reduce(%a, %b), replica_groups={}
  %get-tuple-element.1874 = f32[16,4096,2048]{2,1,0} get-tuple-element(%all-reduce.109), index=2
  %fusion.2 = f32[16,3839,5792]{2,1,0} fusion(%x, %all-reduce.109), kind=kLoop
  %ag = bf16[8,128]{1,0} all-gather(%p), dimensions={0}
  %rs.1 = bf16[4,64]{1,0} reduce-scatter(%g), dimensions={0}
  %a2a = bf16[16,10,32]{2,1,0} all-to-all(%send), dimensions={0}
  %cp = f32[4]{0} collective-permute(%x), source_target_pairs={{0,1}}
  %all-reduce-start.3 = f32[8]{0} all-reduce-start(%y)
"""


def test_parser_counts_each_collective_once():
    out = parse_collective_bytes(HLO)
    c = out["counts"]
    assert c["all-reduce"] == 2          # tuple AR + AR-start; NOT the GTE/fusion
    assert c["all-gather"] == 1
    assert c["reduce-scatter"] == 1
    assert c["all-to-all"] == 1
    assert c["collective-permute"] == 1


def test_parser_tuple_result_bytes():
    out = parse_collective_bytes(HLO)
    tuple_bytes = 2 * 16 * 4096 * 2048 * 4
    assert out["bytes_per_kind"]["all-reduce"] == tuple_bytes + 8 * 4
    assert out["bytes_per_kind"]["all-gather"] == 8 * 128 * 2


def test_parser_ignores_operand_mentions():
    only_mentions = """
  %gte = f32[999]{0} get-tuple-element(%all-reduce.1), index=0
  %f = f32[999]{0} fusion(%all-gather.2)
"""
    out = parse_collective_bytes(only_mentions)
    assert out["total_bytes"] == 0


# ---------------------------------------------------------------------------
# roofline linear fit
# ---------------------------------------------------------------------------
def test_fit_linear_two_segments():
    # cost = 10 (base) + 3·R1 + 5·R2
    f = lambda r1, r2: {"flops": 10 + 3 * r1 + 5 * r2}
    samples = [([1, 1], f(1, 1)), ([2, 1], f(2, 1)), ([1, 2], f(1, 2))]
    out = fit_linear(samples, targets=[7, 11])
    assert out["flops"] == pytest.approx(10 + 3 * 7 + 5 * 11)
    assert out["flops__base"] == pytest.approx(10)


def test_fit_linear_single_segment():
    f = lambda r: {"coll": 2 + 4 * r}
    samples = [([1], f(1)), ([2], f(2))]
    out = fit_linear(samples, targets=[61])
    assert out["coll"] == pytest.approx(2 + 4 * 61)


# ---------------------------------------------------------------------------
# probe configs
# ---------------------------------------------------------------------------
def test_probe_cfg_depth_overrides():
    cfg = get_config("deepseek-v3-671b")
    reps, enc = true_repeats(cfg)
    assert reps == [3, 58] and enc == 0
    p = probe_cfg(cfg, [1, 2])
    assert p.num_layers == 3 and p.first_k_dense == 1
    assert tuple(p.block_pattern) == ("dense", "moe", "moe")


def test_probe_cfg_griffin_pattern():
    cfg = get_config("recurrentgemma-9b")
    reps, _ = true_repeats(cfg)
    assert reps == [12, 2]
    p = probe_cfg(cfg, [2, 1])
    assert p.num_layers == 7
    assert tuple(p.block_pattern) == ("rec", "rec", "attn") * 2 + ("rec",)


def test_probe_cfg_encdec():
    cfg = get_config("seamless-m4t-large-v2")
    p = probe_cfg(cfg, [1], enc_layers=2)
    assert p.encoder_layers == 2 and p.num_layers == 1


# ---------------------------------------------------------------------------
# applicability + flops + input specs
# ---------------------------------------------------------------------------
def test_long_500k_applicability_split():
    runnable = {a for a in list_archs() if a != "serpytor-demo-100m"
                and cell_applicability(get_config(a), SHAPES["long_500k"])[0]}
    assert runnable == {"rwkv6-7b", "recurrentgemma-9b"}


def test_all_40_cells_enumerated():
    from repro.configs import ALL_CELLS

    cells = ALL_CELLS()
    assert len(cells) == 44
    assert len({a for a, _ in cells}) == 11


def test_model_flops_scaling():
    cfg = get_config("yi-6b")
    f_train = model_flops(cfg, SHAPES["train_4k"])
    f_decode = model_flops(cfg, SHAPES["decode_32k"])
    n = cfg.active_param_count()
    assert f_train == pytest.approx(6 * n * 4096 * 256)
    assert f_decode == pytest.approx(2 * n * 128)


def test_model_flops_moe_uses_active_params():
    cfg = get_config("deepseek-v3-671b")
    assert cfg.active_param_count() < 0.1 * cfg.param_count()


def test_input_specs_shapes():

    cfg = get_config("internvl2-2b")
    spec = input_specs(cfg, SHAPES["train_4k"])
    assert spec["tokens"].shape == (256, 4096 - 256)
    assert spec["patch_embeds"].shape == (256, 256, 1024)
    cfg = get_config("seamless-m4t-large-v2")
    spec = input_specs(cfg, SHAPES["prefill_32k"])
    assert spec["frames"].shape == (32, 32768, 1024)
    spec = input_specs(cfg, SHAPES["decode_32k"])
    assert set(spec) == {"token"} and spec["token"].shape == (128,)


def test_smoke_variants_all_small():
    for arch in list_archs():
        cfg = get_config(arch)
        sm = __import__("repro.configs", fromlist=["smoke_variant"]) \
            .smoke_variant(cfg)
        assert sm.num_layers <= 4 and sm.d_model <= 128
        assert sm.family == cfg.family
        assert sm.param_count() < 5e6 or sm.vocab_size <= 512


# ---------------------------------------------------------------------------
# persistent compilation cache placement
# ---------------------------------------------------------------------------
def _recorded_updates(monkeypatch):
    import jax

    calls = []
    monkeypatch.setattr(jax.config, "update", lambda k, v: calls.append((k, v)))
    return calls


def test_compile_cache_defers_to_env(monkeypatch, tmp_path):
    from repro.launch.compile_cache import enable_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    calls = _recorded_updates(monkeypatch)
    assert enable_compile_cache() == str(tmp_path)
    assert calls == []  # JAX reads the variable itself; no path is set in code


def test_compile_cache_fixed_path_inside_checkout(monkeypatch, tmp_path):
    from pathlib import Path

    import repro
    from repro.launch import compile_cache

    checkout = Path(repro.__file__).resolve().parents[2]
    assert compile_cache.CACHE_DIR == checkout / ".jax_cache"
    assert ".jax_cache/" in (checkout / ".gitignore").read_text().split()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache, "CACHE_DIR", tmp_path / "cache")
    calls = _recorded_updates(monkeypatch)
    assert compile_cache.enable_compile_cache() == str(tmp_path / "cache")
    assert calls == [("jax_compilation_cache_dir", str(tmp_path / "cache"))]
    assert (tmp_path / "cache").is_dir()


def test_importing_repro_sets_no_compile_cache():
    import subprocess
    import sys

    code = (
        "import jax, repro, repro.launch.compile_cache, repro.train.trainer\n"
        "assert jax.config.jax_compilation_cache_dir is None, jax.config.jax_compilation_cache_dir\n"
    )
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src")
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                         text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
