#!/usr/bin/env python3
"""Readings of a cell's control and planted faults, for setting its limits.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--seconds 10]

The benchmark's own runs never run this. For each seed it prints one JSON
line with the numbers that ``correct`` compares:

* training cells: the plain reference put in the program's place with its
  parameters stored in bfloat16 (the control: no float32 master copy), with
  half of each batch left out (a fault) and, for a cell on several chips,
  with the exchange between chips left out (a fault: each chip steps on its
  own rows, and the first chip's are read), each against the float32
  reference. These readings run the reference alone, on one chip;
* serving cells: a run of the cell (the program's own gap) and, on the same
  sample of answers, the gap of the tokens that the reference with its
  matrices in int8 or float8 puts first (the control).

A cell's limit lies between the largest program reading over a dozen seeds
or more and the smallest control reading (``PERF.md`` lists both).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def train_readings(cell, seed: int) -> dict:
    from bench import compare, harness, inputs
    from bench.reference import train as ref_train

    cfg, mix = cell.config, cell.traffic
    family = harness.load_family(cell)
    rows = mix["global_batch"]
    batches = [inputs.token_batch(seed, s, vocab=cfg["vocab_size"], seq_len=mix["seq_len"],
                                  rows=rows) for s in range(3)]
    ref = ref_train.run(family, cfg, seed, batches, mix["optimizer"])
    out = {"seed": seed}
    variants = [("control_bf16_params", {"param_dtype": "bfloat16"}),
                ("fault_half_batch", {"rows": rows // 2})]
    if cell.chips > 1:
        variants.append(("fault_no_exchange", {"rows": rows // cell.chips}))
    for name, kw in variants:
        t = time.perf_counter()
        other = ref_train.run(family, cfg, seed, batches, mix["optimizer"], **kw)
        gaps = compare.train_gaps(other, ref)
        out[name] = {k: gaps[k] for k in ("loss_gap", "grad_gap", "change_gap",
                                          "_grad_leaf", "_change_leaf")}
        out[name]["seconds"] = time.perf_counter() - t
    return out


def serve_readings(cell, seed: int, precisions: list) -> dict:
    from bench import harness

    cell.settings = dict(cell.settings, controls=precisions)
    run = harness.Run(cell, time.perf_counter())
    try:
        outcome = harness.load_driver(cell).run(cell, run)
    finally:
        run.compiles.close()
    return {"seed": seed, "logit_gap": outcome.checks["logit_gap"].value,
            **outcome.counters["controls"], "failed": outcome.failed,
            **outcome.end_to_end}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--precisions", default="int8,float8")
    args = ap.parse_args()

    from bench import harness

    cell = harness.find_cell(args.workload)
    harness.check_chips(dataclasses.replace(cell, chips=1) if cell.driver == "train" else cell)
    harness.configure_jax()
    cell.seconds = args.seconds
    for seed in (int(s) for s in args.seeds.split(",")):
        cell.seed = seed
        if cell.driver == "train":
            row = train_readings(cell, seed)
        else:
            row = serve_readings(cell, seed, args.precisions.split(","))
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
