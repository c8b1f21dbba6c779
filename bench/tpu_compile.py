#!/usr/bin/env python3
"""Compile the cells' programs for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python3 bench/tpu_compile.py

Compiles, at the cells' sizes, the training cell's donating step and its
non-donating verify twin on one chip, the same donating step on a ``data=4``
mesh over a described v5e 2x2 (four times the batch), and the serving
cell's decode step at each cache length its traffic uses. Prints each
program's ``memory_analysis()`` in GiB and whether the flash kernel
(``tpu_custom_call``) is in it; a program that does not fit raises here as
it would on the chip. Nothing runs, so nothing is timed.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

GIB = 2**30


def report(name: str, compiled) -> dict:
    m = compiled.memory_analysis()
    row = {
        "program": name,
        "arguments_gib": m.argument_size_in_bytes / GIB,
        "temporaries_gib": m.temp_size_in_bytes / GIB,
        "outputs_gib": m.output_size_in_bytes / GIB,
        "aliased_gib": m.alias_size_in_bytes / GIB,
        "flash_kernel": "tpu_custom_call" in compiled.as_text(),
    }
    print(json.dumps(row), flush=True)
    return row


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

    from bench.harness import find_cell, load_family
    from repro.kernels import ops
    from repro.models import build
    from repro.optim.adamw import AdamWConfig, adamw_init
    from repro.sharding.specs import ShardingOptions, ShardingRules
    from repro.train.steps import make_train_step

    jax.config.update("jax_enable_compilation_cache", False)
    ops._on_tpu = lambda: True  # the described chip is a TPU; the process holds none
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    train = find_cell("train.stablelm-1.6b.steady")
    cfg = load_family(train).program_config(train.config, attn_impl="pallas")
    model = build(cfg)
    opt = AdamWConfig(**train.traffic["optimizer"])
    rows, seq = train.traffic["global_batch"], train.traffic["seq_len"]
    p_shape = jax.eval_shape(lambda r: model.init(r)[0], jax.random.key(0))
    o_shape = jax.eval_shape(lambda p: adamw_init(p, opt), p_shape)

    def placed(tree, sharding):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
                            tree)

    tokens = jax.ShapeDtypeStruct((rows, seq), jnp.int32, sharding=one)
    step = make_train_step(model, opt)
    report("train step, 1 chip, donating",
           jax.jit(step, donate_argnums=(0, 1)).lower(
               placed(p_shape, one), placed(o_shape, one), {"tokens": tokens}).compile())
    report("train step, 1 chip, verify twin (no donation)",
           jax.jit(step).lower(placed(p_shape, one), placed(o_shape, one),
                               {"tokens": tokens}).compile())

    from jax.sharding import AxisType

    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"),
                axis_types=(AxisType.Auto, AxisType.Auto))
    rules = ShardingRules(cfg, mesh, ShardingOptions())
    rules.install()
    try:
        rep = NamedSharding(mesh, P())
        tokens4 = jax.ShapeDtypeStruct((4 * rows, seq), jnp.int32,
                                       sharding=NamedSharding(mesh, P("data")))
        with mesh:
            c = jax.jit(step, donate_argnums=(0, 1)).lower(
                placed(p_shape, rep), placed(o_shape, rep), {"tokens": tokens4}).compile()
        row = report("train step, data=4 over v5e 2x2, donating, per chip", c)
        print(json.dumps({"program": row["program"],
                          "all-reduce": "all-reduce" in c.as_text()}), flush=True)
    finally:
        rules.uninstall()

    serve = find_cell("serve.qwen3-1.7b.agent")
    scfg = load_family(serve).program_config(serve.config, attn_impl="pallas")
    smodel = build(scfg)
    sp = placed(jax.eval_shape(lambda r: smodel.init(r)[0], jax.random.key(0)), one)
    new = serve.traffic["new_tokens"]
    for s in serve.traffic["prompt_lengths"]:
        cache = placed(jax.eval_shape(lambda s=s: smodel.init_cache(1, s + new)), one)
        tok = {"token": jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one)}
        report(f"decode step, cache {s + new}", jax.jit(smodel.decode_step).lower(
            sp, cache, tok).compile())


if __name__ == "__main__":
    main()
