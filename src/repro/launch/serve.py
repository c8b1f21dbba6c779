"""Production serving CLI: gateway + workers over real HTTP transport.

``python -m repro.launch.serve --arch qwen3-1.7b --requests 8`` spins up N
WorkerServers (each: app port + heartbeat port, reduced model replica),
routes generation requests through the Gateway with context affinity, and
reports latency/throughput + the system/application health split.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, list_archs, smoke_variant
from repro.core import (Context, Gateway, TaskRegistry, WorkerClient,
                        WorkerServer)
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build
from repro.obs.trace import get_tracer


def build_registry(cfg, model, params) -> TaskRegistry:
    reg = TaskRegistry()
    decode = jax.jit(model.decode_step)
    tracer = get_tracer()

    def first_token(params, tokens, pad_to):
        logits, cache = model.prefill(params, {"tokens": tokens}, pad_to=pad_to)
        return jnp.argmax(logits, axis=-1), cache

    # one program per prompt length (the tokens' shape) and pad_to, lowered
    # the first time that length is served
    prefill = jax.jit(first_token, static_argnames="pad_to")

    @reg.task("generate")
    def generate(ctx, prompt, new_tokens):
        n = int(new_tokens)
        # serve.prefill: the prompt on the host to the first token on the host
        with tracer.span("serve.prefill", attrs={"prompt_tokens": len(prompt)}):
            toks = np.asarray(prompt, np.int32)[None, :]
            tok, cache = prefill(params, toks, pad_to=toks.shape[1] + n)
            out = [int(tok[0])] if n else []
        with tracer.span("serve.decode", attrs={"tokens": n}):
            for i in range(n):
                logits, cache = decode(params, cache, {"token": tok})
                tok = jnp.argmax(logits, axis=-1)
                if i + 1 < n:
                    out.append(int(tok[0]))
        return {"tokens": out}

    @reg.task("health")
    def health(ctx):
        return {"params_mb": sum(x.size * x.dtype.itemsize
                                 for x in jax.tree.leaves(params)) / 2**20}

    return reg


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b", choices=list(list_archs()))
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    args = ap.parse_args()

    cfg = smoke_variant(get_config(args.arch))
    if cfg.family in ("vlm", "audio"):
        raise SystemExit("serve CLI supports text decoder archs; "
                         "use examples/serve_lm.py patterns for multimodal")
    model = build(cfg)
    params, _ = model.init(jax.random.key(0))
    print(f"serving {cfg.name} ({cfg.param_count()/1e6:.1f}M reduced) on "
          f"{args.workers} HTTP workers")

    servers = [WorkerServer(f"w{i}", build_registry(cfg, model, params)).start()
               for i in range(args.workers)]
    clients = [WorkerClient(s.name, s.address, s.heartbeat_server.address,
                            timeout=300) for s in servers]
    try:
        rng = np.random.default_rng(0)
        with Gateway(clients,
                     allocation=("context_affinity", "least_loaded")) as gw:
            t0 = time.monotonic()
            futs = [gw.submit("generate",
                              Context.origin({"session": f"s{i}"}),
                              {"prompt": rng.integers(
                                  0, cfg.vocab_size,
                                  args.prompt_len).tolist(),
                               "new_tokens": args.new_tokens},
                              affinity_key=f"s{i % 2}")
                    for i in range(args.requests)]
            outs = [f.result(timeout=600) for f in futs]
            wall = time.monotonic() - t0
        tok = sum(len(o["tokens"]) for o in outs)
        print(f"{args.requests} requests / {tok} tokens in {wall:.2f}s "
              f"({tok/wall:.1f} tok/s); alloc {gw.mean_alloc_us():.1f}µs")
        hb = clients[0].heartbeat()
        print(f"worker w0 heartbeat: ok={hb['ok']} "
              f"cpu={hb['cpu']['used_frac']:.2f}")
    finally:
        for s in servers:
            s.stop()


if __name__ == "__main__":
    main()
