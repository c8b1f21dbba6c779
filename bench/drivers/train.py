"""Durable training through ``repro.Client(...).train(Trainer(...))``.

Set-up and window are one ``Client.train`` call over one ``Trainer``: the
Trainer starts from the benchmark's weights (its ``model.init`` hands them
over, as a user loading weights would), and the Trainer's own seeded token
stream feeds it. The first ``warm_steps`` steps compile and warm the step;
they are set-up, and the correctness checks read them. The window opens when
step ``warm_steps`` starts and closes at the start of the first step after
``--seconds``: that step is suspended (``repro.core.Interrupted``), as a
preemption would stop the job, so the run ends without a checkpoint.

The window's rate counts every committed step in it with everything the
durable layer does around it: data regeneration, the step, the host sync of
the metrics, the digest and the journal commit.
"""
from __future__ import annotations

import contextlib
import gc
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from bench import compare, inputs
from bench.harness import Cell, Check, GcPauses, Outcome, Run, load_family
from bench.reference import train as ref_train

#: steps whose readings the checks compare (losses of all, the gradient of
#: the first, the parameters' change after the last)
CHECKED_STEPS = 3


class Window:
    """Host-clock stamps at the start of every step, and the window's end."""

    def __init__(self, warm: int, seconds: float, last: int, run: Run, trace_seconds: float):
        self.warm = warm
        self.seconds = seconds
        self.last = last
        self.run = run
        self.trace_seconds = trace_seconds
        self.starts: Dict[int, float] = {}
        self.cpu: Dict[int, float] = {}  # process CPU seconds at each step's start
        self.pauses = GcPauses()
        self.stop_step: Optional[int] = None
        self.traced_steps: Optional[int] = None
        self.loads_at_open = 0
        self._trace = contextlib.ExitStack()
        self._tracing = False

    def at_step(self, step: int) -> None:
        """Called as step ``step`` starts; raises ``Interrupted`` to close."""
        from repro.core.durable import Interrupted

        if step == self.warm:
            self.run.settle()
            gc.callbacks.append(self.pauses)
        now = time.perf_counter()
        self.starts[step] = now
        self.cpu[step] = time.process_time()
        if step == self.warm:
            self.loads_at_open = self.run.compiles.loads
            if self.run.cell.trace:
                self._trace.enter_context(self.run.traced())
                self._tracing = True
                self.starts[step] = time.perf_counter()
            return
        if step < self.warm:
            return
        open_t = self.starts[self.warm]
        closing = now - open_t >= self.seconds or step >= self.last
        if self._tracing and (closing or now - open_t >= self.trace_seconds):
            self._trace.close()
            self._tracing = False
            self.traced_steps = step - self.warm
        if closing:
            self.stop_step = step
            raise Interrupted("bench-window-closed")

    def close(self) -> None:
        self._trace.close()
        if self.pauses in gc.callbacks:
            gc.callbacks.remove(self.pauses)

    def step_times(self) -> str:
        """The window's step times: median, the slowest, and the time lost to slow steps.

        Beside each step's wall time, the process's CPU time over it: a slow
        step that used no more CPU than a usual one waited (for the device,
        the disk, or a host that ran something else).
        """
        steps = range(self.warm, self.stop_step)
        dt = np.array([self.starts[s + 1] - self.starts[s] for s in steps])
        cpu = np.array([self.cpu[s + 1] - self.cpu[s] for s in steps])
        med = float(np.median(dt))
        slow = dt > 1.5 * med
        worst = sorted(zip(dt.tolist(), cpu.tolist(), steps), reverse=True)[:3]
        return (f"step times: median {med:.6f} s (CPU {float(np.median(cpu)):.6f} s), "
                f"max {float(dt.max()):.6f} s; {int(slow.sum())} over 1.5 x median, "
                f"{float((dt[slow] - med).sum()):.3f} s lost to them; slowest (wall s, CPU s, "
                f"step): {[(round(t, 4), round(c, 4), s) for t, c, s in worst]}; "
                f"{self.pauses.summary()}")


def run(cell: Cell, run: Run) -> Outcome:
    from repro import Client
    from repro.optim.adamw import AdamWConfig
    from repro.train.trainer import TrainConfig, Trainer

    cfg, mix, st = cell.config, cell.traffic, cell.settings
    seed = cell.seed
    weight_seed = seed
    warm = int(st["warm_steps"])
    if warm <= CHECKED_STEPS:
        raise ValueError(f"warm_steps must exceed {CHECKED_STEPS}")
    last = warm + int(st["max_window_steps"])
    rows, seq = int(mix["global_batch"]), int(mix["seq_len"])
    opt = dict(mix["optimizer"])
    tc = TrainConfig(
        run_dir=str(cell.out_dir / "run"),
        num_steps=last + 1,
        checkpoint_every=max(int(mix["checkpoint_every"]), last + 1),
        log_every=10**9,
        seed=seed,
        global_batch=rows,
        seq_len=seq,
        journal_sync=cfg["guarantees"]["journal_sync"],
        async_checkpoint=True,
        opt=AdamWConfig(**opt),
    )
    family = load_family(cell)
    trainer = Trainer(family.program_config(cfg, attn_impl=st.get("attn_impl", "auto")), tc)
    family.check_layout(cfg, trainer.model)
    weights = [family.make_weights(cfg, weight_seed)]

    def init(_rng):
        # hand the benchmark's weights to the Trainer once; the donating
        # step consumes them
        return weights.pop(), None

    trainer.model.init = init

    readings: Dict[str, Any] = {}
    step_fn = trainer._train_step
    calls = [0]

    def recording_step(params, opt_state, batch):
        i = calls[0]
        calls[0] += 1
        if i == 1:
            readings["first_grad_norms"] = {  # m after one step is (1 - b1) g
                k: v / (1.0 - opt["b1"])
                for k, v in compare.device_slice_norms(opt_state["m"]).items()}
        if i == CHECKED_STEPS:
            readings["params"] = jax.device_get(params)
        return step_fn(params, opt_state, batch)

    trainer._train_step = recording_step
    window = Window(warm, cell.seconds, last, run, float(st.get("trace_seconds", cell.seconds)))
    device_batch = trainer.device_batch

    def timed_batch(step):
        window.at_step(step)
        return device_batch(step)

    trainer.device_batch = timed_batch
    client = Client(str(cell.out_dir / "client"))
    try:
        client.train(trainer)
    finally:
        window.close()
        client.close()
    if window.stop_step is None:
        raise RuntimeError("the training window never closed")
    memory_peak = run.memory_peak_bytes()
    run.mark("window")
    t_open, t_close = window.starts[warm], window.starts[window.stop_step]
    steps = window.stop_step - warm
    tokens_per_s = steps * rows * seq / (t_close - t_open)
    compiles = run.compiles.loads - window.loads_at_open
    run.note(f"window: {steps} steps in {t_close - t_open:.3f} s; "
             f"executables loaded in the window: {compiles}")
    run.note(window.step_times())
    setup_s = t_open - run.t_start
    losses = {m["step"]: m["loss"] for m in trainer.metrics_log}
    committed = sorted(losses)
    run.note(f"losses of the first steps: {[losses.get(s) for s in range(CHECKED_STEPS)]}")

    # the program's state is gone with the Trainer's loop; free what is left
    data = [trainer.source.batch_at(s)["tokens"] for s in range(CHECKED_STEPS)]
    del trainer, step_fn, recording_step
    gc.collect()

    checks, detail = check_steps(family, cfg, mix, seed, weight_seed, data, losses, readings)
    run.mark("reference check")
    run.note(f"checked leaves: grad {detail['_grad_leaf']}, change {detail['_change_leaf']}; "
             f"left out of change_gap: {detail['_left_out']}; loss_gap (not compared) "
             f"{detail['loss_gap']!r}")
    limits = st["limits"]
    traced_rate = None
    if run.traced_window is not None and window.traced_steps:
        t0, t1 = run.traced_window
        traced_rate = window.traced_steps * rows * seq / (t1 - t0)
    counters = {
        "traced_steps": window.traced_steps,
        "traced_tokens_per_s": traced_rate,
        "flops_per_token": family.flops_per_token(cfg, seq),
        "attention_calls": family.attention_calls(cfg, rows, seq),
    }
    return Outcome(
        setup_s=setup_s,
        end_to_end={"train_tokens_per_s": tokens_per_s},
        attempted=steps,
        failed=0 if committed == list(range(window.stop_step)) else 1,
        checks={k: Check(float(v), float(limits[k])) for k, v in checks.items()},
        memory_peak_bytes=memory_peak,
        counters=counters,
    )


def check_steps(family, cfg, mix, seed, weight_seed, data: List[np.ndarray], losses,
                readings) -> tuple:
    """Gaps of the program's first steps from the plain reference."""
    rows, seq = int(mix["global_batch"]), int(mix["seq_len"])
    want = [inputs.token_batch(seed, s, vocab=cfg["vocab_size"], seq_len=seq, rows=rows)
            for s in range(CHECKED_STEPS)]
    mismatch = sum(int(np.sum(a != b)) for a, b in zip(data, want, strict=True))
    ref = ref_train.run(family, cfg, weight_seed, want, mix["optimizer"],
                        other_params=readings.pop("params"))
    prog = {
        "losses": [losses.get(s, float("nan")) for s in range(CHECKED_STEPS)],
        "first_grad_norms": readings["first_grad_norms"],
        "change_norms": ref["other_change_norms"],
    }
    gaps = compare.train_gaps(prog, ref)
    checks = {k: gaps[k] for k in ("grad_gap", "change_gap")}
    checks["data_mismatch"] = float(mismatch)
    return checks, gaps

