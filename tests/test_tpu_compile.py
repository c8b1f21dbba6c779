"""Compile the Pallas kernels and a train step for a described TPU v5e.

Nothing runs: the TPU compiler, installed with JAX, compiles for a v5e:2x2
topology that is described, not attached, and refuses what the chip would
refuse (tiling, unsupported lowerings, VMEM, device memory). Interpret-mode
tests cannot see those faults. The topology is built inside a module-scoped
fixture, never at import, so that every pytest-xdist worker collects the same
tests and only the worker running this file loads the TPU library.
"""

import importlib.util
import os
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.models import build
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.train.steps import make_train_step

HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with the kernels dispatched as on a TPU.

    The persistent compilation cache is off: an entry written for a described
    chip cannot be read back without one.
    """
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        # ops picks Pallas from the default backend, which is the CPU here
        mp.setattr(ops, "_on_tpu", lambda: True)
        yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _bench_kernel(name):
    """``bench/kernels/<name>.py``, loaded by path as the benchmark loads it."""
    path = Path(__file__).resolve().parents[1] / "bench" / "kernels" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_kernels_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _mosaic_calls(compiled):
    """The program's Mosaic calls as the profiler names them: HLO text with
    each operand's shape."""
    from jax._src.lib import xla_client

    opts = xla_client._xla.HloPrintOptions.short_parsable()
    opts.print_operand_shape = True
    text = compiled.runtime_executable().hlo_modules()[0].to_string(opts)
    return [SimpleNamespace(name=line.strip()) for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def _flash_call(q, kv):
    return {"batch": q[0], "heads": q[1], "kv_heads": kv[1], "q_len": q[2], "kv_len": kv[2],
            "head_dim": q[3], "causal": True, "dtype_bytes": 2}


@pytest.mark.parametrize(
    "name, q, kv, dtype",
    [
        ("serpytor-demo-100m prefill", (4, 12, 2048, 64), (4, 4, 2048, 64), jnp.float32),
        ("qwen3-1.7b unaligned prompt", (1, 16, 17, 128), (1, 8, 17, 128), jnp.bfloat16),
        ("stablelm-1.6b training call", (2, 32, 2048, 64), (2, 32, 2048, 64), jnp.bfloat16),
        ("qwen3-1.7b longest prefill", (1, 16, 4096, 128), (1, 8, 4096, 128), jnp.bfloat16),
    ],
)
def test_flash_attention_compiles(chip, name, q, kv, dtype):
    def fwd(q, k, v):
        return ops.flash_attention(q, k, v, causal=True, impl="pallas")

    _assert_kernel(_compile(fwd, chip, (q, dtype), (kv, dtype), (kv, dtype)))


@pytest.mark.parametrize(
    "name, q, kv",
    [
        ("stablelm-1.6b training call", (2, 32, 2048, 64), (2, 32, 2048, 64)),
        ("granite-4.0-h-micro attention layer", (2, 32, 2048, 64), (2, 8, 2048, 64)),
    ],
)
def test_flash_attention_backward_compiles(chip, name, q, kv):
    """``jax.vjp`` of one call: the forward and the backward's three kernels
    (log-sum-exp, dq, dk/dv) within 256 MiB of temporaries, where the
    float32 jnp backward took 2.91 GiB; the benchmark's forward matcher takes
    the forward alone, and its backward matcher the three others."""
    bf = jnp.bfloat16

    def vjp(q, k, v, do):
        _, pull = jax.vjp(lambda *a: ops.flash_attention(*a, causal=True, impl="pallas"), q, k, v)
        return pull(do)

    compiled = _compile(vjp, chip, (q, bf), (kv, bf), (kv, bf), (q, bf))
    assert compiled.memory_analysis().temp_size_in_bytes < 256 * 2**20
    calls = _mosaic_calls(compiled)
    call = _flash_call(q, kv)
    fwd = [c for c in calls if _bench_kernel("flash_attention").matcher(call)(c)]
    bwd_kernels = _bench_kernel("flash_attention_bwd")
    bwd = [c for c in calls if bwd_kernels.matcher(call)(c)]
    dkv = [c for c in calls if bwd_kernels.dkv_matcher(call)(c)]
    assert (len(calls), len(fwd), len(bwd), len(dkv)) == (4, 1, 3, 1)
    assert fwd[0] not in bwd and dkv[0] in bwd


def test_wkv6_compiles_at_rwkv6_7b_width(chip):
    B, H, T, K = 1, 64, 1024, 64
    bf, f32 = jnp.bfloat16, jnp.float32

    def wkv(r, k, v, w, u, s0):
        return ops.wkv6(r, k, v, w, u, initial_state=s0, impl="pallas")

    seq = (B, H, T, K)
    compiled = _compile(
        wkv, chip, (seq, bf), (seq, bf), (seq, bf), (seq, f32), ((H, K), f32), ((B, H, K, K), f32)
    )
    _assert_kernel(compiled)


def test_rglru_compiles_at_recurrentgemma_9b_width(chip):
    B, T, W = 1, 1024, 4096

    def lru(x, a, h0):
        return ops.rglru(x, a, initial_state=h0, impl="pallas")

    compiled = _compile(
        lru, chip, ((B, T, W), jnp.bfloat16), ((B, T, W), jnp.float32), ((B, W), jnp.float32)
    )
    _assert_kernel(compiled)


def test_ssd_compiles_at_granite_width(chip):
    """granite-4.0-h-micro's scan at the training cell's shape: 64 heads of
    64, state 128, chunks of 256, bf16 operands."""
    Bt, H, T, P, N = 2, 64, 2048, 64, 128
    bf, f32 = jnp.bfloat16, jnp.float32

    def scan(x, dt, A, B, C):
        return ops.ssd(x, dt, A, B, C, chunk=256, impl="pallas")

    compiled = _compile(scan, chip, ((Bt, H, T, P), bf), ((Bt, H, T), f32), ((H,), f32),
                        ((Bt, T, N), bf), ((Bt, T, N), bf))
    _assert_kernel(compiled)


def test_gated_delta_compiles_at_olmo_hybrid_width(chip):
    """Olmo-Hybrid-7B's delta rule at the serving cell's longest prompt: 30
    heads, keys of 96 and values of 192, chunks of 64, bf16 operands; the
    benchmark's matcher reads the call's shape from the kernel's HLO."""
    Bt, H, T, K, V = 1, 30, 32768, 96, 192
    bf, f32 = jnp.bfloat16, jnp.float32

    def rule(q, k, v, g, beta, s0):
        return ops.gated_delta(q, k, v, g, beta, chunk=64, initial_state=s0, impl="pallas")

    compiled = _compile(rule, chip, ((Bt, H, T, K), bf), ((Bt, H, T, K), bf), ((Bt, H, T, V), bf),
                        ((Bt, H, T), f32), ((Bt, H, T), f32), ((Bt, H, V, K), f32))
    (call,) = _mosaic_calls(compiled)
    assert _bench_kernel("gated_delta").call_of(call) == {
        "batch": Bt, "heads": H, "seq": T, "key_dim": K, "value_dim": V, "chunk": 64,
        "dtype_bytes": 2}


def _demo_step_compiled(params_sharding, batch_sharding):
    """Full-width serpytor-demo-100m step, global batch 8 x 1024, as chip_smoke.py trains it."""
    model = build(get_config("serpytor-demo-100m"))
    opt = AdamWConfig()
    params = jax.eval_shape(lambda r: model.init(r)[0], jax.random.key(0))
    opt_state = jax.eval_shape(lambda p: adamw_init(p, opt), params)

    def placed(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=params_sharding), tree
        )

    batch = {"tokens": jax.ShapeDtypeStruct((8, 1024), jnp.int32, sharding=batch_sharding)}
    step = jax.jit(make_train_step(model, opt), donate_argnums=(0, 1))
    return step.lower(placed(params), placed(opt_state), batch).compile()


def _device_bytes(compiled):
    mem = compiled.memory_analysis()
    return (
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        - mem.alias_size_in_bytes
        + mem.temp_size_in_bytes
        + mem.generated_code_size_in_bytes
    )


def test_demo_100m_train_step_compiles_and_fits_one_chip(chip):
    compiled = _demo_step_compiled(chip, chip)
    _assert_kernel(compiled)
    total = _device_bytes(compiled)
    assert total < HBM_BYTES, f"train step needs {total / 2**30:.2f} GiB on a 16 GiB chip"


def test_demo_100m_train_step_compiles_data_parallel_on_four_chips(topo):
    """The Trainer's data=4 mesh: each flash call runs per shard (Mosaic
    calls cannot be partitioned) and the gradients are all-reduced."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh
    from repro.sharding.specs import ShardingRules

    mesh = make_mesh((4, 1), ("data", "model"), devices=topo.devices)
    with ShardingRules(get_config("serpytor-demo-100m"), mesh):
        compiled = _demo_step_compiled(NamedSharding(mesh, P()), NamedSharding(mesh, P("data")))
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text
    total = _device_bytes(compiled)
    assert total < HBM_BYTES, f"train step needs {total / 2**30:.2f} GiB per chip"
    # the backward's kernels run per shard too, on 2 of the 8 rows (once,
    # in the body of the loop over the stacked layers)
    cfg = get_config("serpytor-demo-100m")
    shard = _flash_call((2, cfg.num_heads, 1024, cfg.head_dim),
                        (2, cfg.num_kv_heads, 1024, cfg.head_dim))
    calls = _mosaic_calls(compiled)
    bwd_kernels = _bench_kernel("flash_attention_bwd")
    dkv = sum(map(bwd_kernels.dkv_matcher(shard), calls))
    assert dkv >= 1
    assert sum(map(bwd_kernels.matcher(shard), calls)) == 3 * dkv
