#!/usr/bin/env python3
"""Smoke run of durable training and serving on a TPU, in one process.

    python chip_smoke.py               # one chip: training + serving phases
    python chip_smoke.py --four-chips  # four chips: data-parallel phase only

It drives the main paths once, through the entry points a user calls, at the
published widths of the models, with random weights made from a seed:

* training: serpytor-demo-100m (fp32, 8 layers, d_model 768, vocab 32000),
  global batch 8 x 1024, through ``repro.Client(...).train(Trainer(...))``.
  A reference run takes 6 steps with a checkpoint every 2. A second run
  crashes in the checkpoint node after step 3 has committed, so its newest
  snapshot is step 2. A third run over the crashed run's directory restores
  that snapshot, re-executes step 3 against its journal digest, and must end
  with losses bit-identical to the reference. The compiled train step must
  hold the flash-attention kernel (``tpu_custom_call``): ``impl="auto"``
  chose Pallas.
* serving: qwen3-1.7b (bf16, 28 layers, vocab 151936) answers 4 ``generate``
  requests (128-token prompts, 16 new tokens) through a ``Gateway`` over two
  ``WorkerServer``s. On one prompt the prefill logits of the Pallas path are
  compared with ``attn_impl="ref"`` at a bf16 tolerance.
* ``--four-chips``: the same Trainer on a ``data=4`` mesh for 4 steps, against
  a plain one-device ``jax.jit(make_train_step(...))`` on the same seed and
  batches. Each device must hold a quarter of the batch, and the losses must
  agree to fp32 tolerance.

Without a TPU it exits nonzero before any phase. Every failed check exits
nonzero. The timings printed are smoke readings, not benchmark numbers. The
last line of stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

RUN_ROOT = os.path.join(ROOT, "runs", "chip_smoke")

TRAIN_ARCH = "serpytor-demo-100m"
TRAIN_BATCH, TRAIN_SEQ = 8, 1024
TRAIN_STEPS, CKPT_EVERY, CRASH_AFTER_STEP = 6, 2, 3
FOUR_CHIP_STEPS = 4

SERVE_ARCH = "qwen3-1.7b"
SERVE_REQUESTS, PROMPT_LEN, NEW_TOKENS = 4, 128, 16
#: bf16 tolerance for Pallas-vs-ref prefill logits, relative to max |logit|
LOGIT_RTOL = 2e-2
#: fp32 tolerance for data-parallel vs one-device losses (relative)
LOSS_RTOL = 1e-4


def reading(phase: str, **values) -> None:
    """One labelled smoke reading (not a benchmark measurement)."""
    print(f"[smoke reading] {phase}: " + json.dumps(values, sort_keys=True), flush=True)


def peak_bytes() -> int:
    import jax

    return int((jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", -1))


class CrashAt(Exception):
    """The planned in-process crash of the second training run."""


class SmokeFailure(Exception):
    """A check of the smoke failed."""


def check(ok, detail) -> None:
    """Raise (even under ``python -O``) unless ``ok``."""
    if not ok:
        raise SmokeFailure(detail)


def train_config(run_dir: str, steps: int, batch: int, seq: int, ckpt_every: int):
    from repro.optim.adamw import AdamWConfig
    from repro.train.trainer import TrainConfig

    return TrainConfig(
        run_dir=run_dir,
        num_steps=steps,
        checkpoint_every=ckpt_every,
        log_every=1,
        seed=0,
        global_batch=batch,
        seq_len=seq,
        journal_sync="batch",
        opt=AdamWConfig(lr=3e-4, warmup_steps=2, total_steps=steps),
    )


def losses(trainer) -> dict:
    return {m["step"]: m["loss"] for m in trainer.metrics_log}


def train_phase(cfg, base: str, *, batch: int = TRAIN_BATCH, seq: int = TRAIN_SEQ) -> dict:
    """Reference run, crash after step 3, resume; returns the readings."""
    import jax

    from repro import Client
    from repro.optim.adamw import adamw_init
    from repro.train.trainer import Trainer
    from repro.wire import payload_digest

    t_phase = time.perf_counter()
    client = Client(os.path.join(base, "client"))

    def tc(name):
        return train_config(os.path.join(base, name), TRAIN_STEPS, batch, seq, CKPT_EVERY)

    ref = Trainer(cfg, tc("reference"))
    # compile the donating step as the loop will call it, and keep its text
    params, _ = ref.model.init(jax.random.key(ref.tc.seed))
    opt_state = adamw_init(params, ref.tc.opt)
    t0 = time.perf_counter()
    compiled = ref._train_step.lower(params, opt_state, ref.device_batch(0)).compile()
    compile_s = time.perf_counter() - t0
    kernel_in_step = "tpu_custom_call" in compiled.as_text()
    del params, opt_state, compiled
    t0 = time.perf_counter()
    client.train(ref)
    ref_wall = time.perf_counter() - t0
    want = losses(ref)

    crash = Trainer(cfg, tc("resumed"))
    save = crash.store.save

    def crashing_save(tag, *a, **kw):
        if tag.startswith(f"step{CRASH_AFTER_STEP + 1:08d}"):
            raise CrashAt(f"simulated power loss checkpointing after step {CRASH_AFTER_STEP}")
        return save(tag, *a, **kw)

    crash.store.save = crashing_save
    crashed = False
    try:
        client.train(crash)
    except CrashAt as e:
        crashed = True
        print(f"!! crashed as planned: {e}", flush=True)
    finally:
        crash.store.wait()
        crash.journal.close()
    check(crashed, "the second run did not crash")
    before = losses(crash)  # rounds that closed before the crash

    resumed = Trainer(cfg, tc("resumed"))
    journal_digests, _ = resumed._scan_journal()
    check(sorted(journal_digests) == list(range(CRASH_AFTER_STEP + 1)), journal_digests)
    latest = resumed.store.latest(companions=("-opt",))
    check(latest == f"step{CKPT_EVERY:08d}", f"expected the step-2 snapshot, got {latest}")
    t0 = time.perf_counter()
    client.train(resumed)
    resume_wall = time.perf_counter() - t0
    got = losses(resumed)
    client.close()

    check(sorted(got) == list(range(CKPT_EVERY, TRAIN_STEPS)), sorted(got))
    step3 = next(m for m in resumed.metrics_log if m["step"] == CRASH_AFTER_STEP)
    verified = payload_digest(step3) == journal_digests[CRASH_AFTER_STEP]
    check(verified, "step 3 re-executed with a digest other than its journal's")
    mismatch = {s: (got[s], want[s]) for s in got if got[s] != want[s]}
    mismatch.update({s: (before[s], want[s]) for s in before if before[s] != want[s]})
    print(f"train: reference losses {[want[s] for s in sorted(want)]}", flush=True)
    print(
        f"train: resumed losses   {[got[s] for s in sorted(got)]} "
        f"(from snapshot {latest}; step {CRASH_AFTER_STEP} journal digest "
        f"re-verified: {verified})",
        flush=True,
    )
    print(f"train: resumed losses bit-identical to reference: {not mismatch}", flush=True)
    check(not mismatch, f"resumed losses differ from the reference: {mismatch}")
    return {
        "compile_s": compile_s,
        "reference_run_s": ref_wall,
        "resume_run_s": resume_wall,
        "phase_s": time.perf_counter() - t_phase,
        "step0_loss": want[0],
        "kernel_in_step": kernel_in_step,
    }


def serve_phase(cfg, *, prompt_len: int = PROMPT_LEN, new_tokens: int = NEW_TOKENS) -> dict:
    """Gateway over two WorkerServers answers the generate requests."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import Context, Gateway, WorkerClient, WorkerServer
    from repro.launch.serve import build_registry
    from repro.models import build

    t_phase = time.perf_counter()
    model = build(cfg)
    params, _ = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, prompt_len).tolist() for _ in range(SERVE_REQUESTS)]

    servers = [WorkerServer(f"w{i}", build_registry(cfg, model, params)).start() for i in range(2)]
    try:
        clients = [
            WorkerClient(s.name, s.address, s.heartbeat_server.address, timeout=900)
            for s in servers
        ]
        with Gateway(clients, allocation=("context_affinity", "least_loaded")) as gw:
            t0 = time.perf_counter()
            futs = [
                gw.submit(
                    "generate",
                    Context.origin({"session": f"s{i}"}),
                    {"prompt": p, "new_tokens": new_tokens},
                    affinity_key=f"s{i % 2}",
                )
                for i, p in enumerate(prompts)
            ]
            outs = [f.result(timeout=900) for f in futs]
            serve_s = time.perf_counter() - t0
    finally:
        for s in servers:
            s.stop()
    for o in outs:
        toks = o["tokens"]
        check(len(toks) == new_tokens, o)
        check(all(0 <= t < cfg.vocab_size for t in toks), o)
    print(
        f"serve: {len(outs)} requests answered, tokens of request 0: {outs[0]['tokens']}",
        flush=True,
    )

    batch = {"tokens": jnp.asarray(np.asarray(prompts[0], np.int32))[None, :]}
    kernel, _ = model.prefill(params, batch)
    ref_model = build(dataclasses.replace(cfg, attn_impl="ref"))
    plain, _ = ref_model.prefill(params, batch)
    kernel = np.asarray(kernel, np.float32)
    plain = np.asarray(plain, np.float32)
    check(kernel.shape == plain.shape == (1, cfg.vocab_size), kernel.shape)
    check(np.isfinite(kernel).all() and np.isfinite(plain).all(), "non-finite prefill logits")
    err = float(np.abs(kernel - plain).max())
    scale = float(np.abs(plain).max())
    print(
        f"serve: prefill logits, {cfg.attn_impl} kernel path vs ref: max |diff| {err} "
        f"over max |logit| {scale} (bound {LOGIT_RTOL} x max |logit|); argmax "
        f"{int(kernel.argmax())} vs {int(plain.argmax())}; first served token "
        f"{outs[0]['tokens'][0]}",
        flush=True,
    )
    check(err <= LOGIT_RTOL * scale, f"kernel prefill logits off by {err} (scale {scale})")
    check(outs[0]["tokens"][0] == int(kernel.argmax()), "served token is not the argmax")
    return {
        "serve_s": serve_s,
        "phase_s": time.perf_counter() - t_phase,
        "requests": len(outs),
        "tokens_generated": sum(len(o["tokens"]) for o in outs),
        "logit_max_abs_diff": err,
        "logit_max_abs": scale,
    }


def four_chip_phase(cfg, base: str, *, batch: int = TRAIN_BATCH, seq: int = TRAIN_SEQ) -> dict:
    """Trainer on a data=4 mesh vs a plain one-device jit of the same step."""
    import jax
    import numpy as np

    from repro import Client
    from repro.optim.adamw import adamw_init
    from repro.train.steps import make_train_step
    from repro.train.trainer import Trainer

    n = len(jax.devices())
    check(n == 4, f"--four-chips needs 4 devices, found {n}")
    t_phase = time.perf_counter()
    run_dir = os.path.join(base, "data4")
    trainer = Trainer(cfg, train_config(run_dir, FOUR_CHIP_STEPS, batch, seq, FOUR_CHIP_STEPS))
    mesh = dict(zip(trainer.mesh.axis_names, trainer.mesh.devices.shape, strict=True))
    check(mesh == {"data": 4, "model": 1}, mesh)
    toks = trainer.device_batch(0)["tokens"]
    shards = sorted((s.device.id, s.data.shape) for s in toks.addressable_shards)
    check([shape for _, shape in shards] == [(batch // 4, seq)] * 4, shards)
    print(f"four-chip: batch {toks.shape} split over devices as {shards}", flush=True)
    client = Client(os.path.join(base, "client"))
    client.train(trainer)
    client.close()
    mesh_losses = losses(trainer)

    dev = jax.devices()[0]
    step = jax.jit(make_train_step(trainer.model, trainer.tc.opt))
    params, _ = trainer.model.init(jax.random.key(trainer.tc.seed))
    state = jax.device_put((params, adamw_init(params, trainer.tc.opt)), dev)
    plain = {}
    for s in range(FOUR_CHIP_STEPS):
        b = jax.device_put(trainer.source.batch_at(s), dev)
        p, o, m = step(*state, b)
        state = (p, o)
        plain[s] = float(m["loss"])
    print(f"four-chip: data=4 losses {[mesh_losses[s] for s in sorted(mesh_losses)]}", flush=True)
    print(f"four-chip: one-device losses {[plain[s] for s in sorted(plain)]}", flush=True)
    rel = max(abs(mesh_losses[s] - plain[s]) / abs(plain[s]) for s in plain)
    print(f"four-chip: max relative loss difference {rel} (bound {LOSS_RTOL})", flush=True)
    check(sorted(mesh_losses) == sorted(plain), (mesh_losses, plain))
    check(np.isfinite(list(plain.values())).all(), f"non-finite one-device losses {plain}")
    check(rel <= LOSS_RTOL, f"data=4 losses differ from one device by {rel}")
    return {"phase_s": time.perf_counter() - t_phase, "max_rel_loss_diff": rel}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--four-chips", action="store_true", help="run only the data-parallel phase on four chips"
    )
    args = ap.parse_args()

    import jax

    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU (JAX backend {jax.default_backend()!r}); "
            "this smoke runs on the chip only"
        )
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    print(
        f"device: platform {device['platform']}, kind {device['kind']}, count {device['count']}",
        flush=True,
    )

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    shutil.rmtree(RUN_ROOT, ignore_errors=True)
    try:
        if args.four_chips:
            r = four_chip_phase(get_config(TRAIN_ARCH), RUN_ROOT)
            reading("four_chip_train", peak_bytes_in_use=peak_bytes(), **r)
        else:
            cfg = get_config(TRAIN_ARCH)
            r = train_phase(cfg, RUN_ROOT)
            ln_vocab = math.log(cfg.vocab_size)
            check(
                abs(r["step0_loss"] - ln_vocab) <= 1.0,
                f"step-0 loss {r['step0_loss']} vs ln(vocab) {ln_vocab}",
            )
            print(f"train: flash kernel in compiled train step: {r['kernel_in_step']}", flush=True)
            check(r.pop("kernel_in_step"), "no tpu_custom_call in the compiled train step")
            reading("train", peak_bytes_in_use=peak_bytes(), **r)
            r = serve_phase(get_config(SERVE_ARCH))
            reading("serve", peak_bytes_in_use=peak_bytes(), **r)
    finally:
        shutil.rmtree(RUN_ROOT, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
