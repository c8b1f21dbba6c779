"""Serving prefill: one jitted program per prompt length.

``generate`` prefills through a jit built from the model it is given, so a
prompt length is lowered once and served again without lowering; its first
token and the greedy decode that follows are those of the eager prefill.
"""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_variant
from repro.core import Context, Gateway, InProcWorker
from repro.launch.serve import build_registry
from repro.models import build
from repro.obs import RingSink, get_tracer

NEW = 8


def _dense(**changes):
    """Qwen3's smoke variant (GQA 4/2, q/k norm, tied vocabulary): 5 layers,
    so the stack runs as a scan, with the flash kernel in interpret mode."""
    cfg = smoke_variant(get_config("qwen3-1.7b"))
    return replace(cfg, **{"num_layers": 5, "attn_impl": "interpret", **changes})


def _windowed(window):
    return _dense(num_layers=2, block_pattern=("attn", "attn"), window=window)


def _smoke(arch):
    return replace(smoke_variant(get_config(arch)), attn_impl="interpret")


def _gdn():
    """Gated DeltaNet layers beside NoPE attention in Olmo blocks."""
    return _dense(num_layers=3, block_pattern=("gdn", "gdn", "attn"), rope_fraction=0.0,
                  qk_norm=False, qk_norm_whole=True, post_norm=True, gdn_n_heads=4,
                  gdn_key_dim=16, gdn_value_dim=32, gdn_neg_eigval=True)


CONFIGS = {
    "dense": _dense,
    "gdn": _gdn,
    "ring": lambda: _windowed(8),  # a ring window shorter than the prompt
    "mamba2": lambda: _smoke("granite-4.0-h-micro"),
    "moe": lambda: _smoke("granite-moe-3b-a800m"),
    "rwkv": lambda: _smoke("rwkv6-7b"),
}

_BUILT = {}


def _model(name):
    if name not in _BUILT:
        cfg = CONFIGS[name]()
        model = build(cfg)
        _BUILT[name] = (cfg, model, model.init(jax.random.key(0))[0])
    return _BUILT[name]


def _prompt(n, vocab, seed=0):
    return np.random.default_rng(seed).integers(1, vocab, n).tolist()


def _eager(model, params, prompt, n=NEW):
    """Eager prefill at the prompt's length, then greedy decode."""
    toks = jnp.asarray(prompt, jnp.int32)[None, :]
    logits, cache = model.prefill(params, {"tokens": toks}, pad_to=len(prompt) + n)
    decode = jax.jit(model.decode_step)
    tok = jnp.argmax(logits, axis=-1)
    out = [int(tok[0])]
    for _ in range(n - 1):
        logits, cache = decode(params, cache, {"token": tok})
        tok = jnp.argmax(logits, axis=-1)
        out.append(int(tok[0]))
    return out


def _serve(reg, lengths, vocab, n=4):
    """Serve one request per length (the prompt drawn from its length) through
    a gateway; returns the tokens and each ``task:generate`` span's
    ``jax_lowerings``, in order."""
    ring = RingSink()
    outs = []
    with Gateway([InProcWorker("w0", reg)]) as gw, get_tracer().attached(ring):
        for length in lengths:
            prompt = _prompt(length, vocab, seed=length)
            out = gw.submit("generate", inputs={"prompt": prompt, "new_tokens": n})
            outs.append(out.result(timeout=300)["tokens"])
    tasks = sorted((s for s in ring.spans() if s["name"] == "task:generate"),
                   key=lambda s: s["ts"])
    return outs, [t["attrs"]["jax_lowerings"] for t in tasks]


@pytest.mark.parametrize("name,length", [("dense", 1), ("dense", 13), ("dense", 21),
                                         ("ring", 21), ("mamba2", 13), ("moe", 13),
                                         ("rwkv", 13), ("gdn", 13), ("gdn", 21)])
def test_generate_matches_eager_prefill(name, length):
    cfg, model, params = _model(name)
    prompt = _prompt(length, cfg.vocab_size, seed=length)
    reg = build_registry(cfg, model, params)
    ring = RingSink()
    with get_tracer().attached(ring):
        out = reg.get("generate")(Context.origin({"session": "s"}), prompt, NEW)
    assert out["tokens"] == _eager(model, params, prompt)
    (span,) = [s for s in ring.spans() if s["name"] == "serve.prefill"]
    assert span["attrs"]["prompt_tokens"] == length


def test_a_served_length_lowers_nothing_again():
    cfg, model, params = _model("dense")
    reg = build_registry(cfg, model, params)
    outs, lowered = _serve(reg, (17, 17, 22, 22, 17), cfg.vocab_size)
    assert [x > 0 for x in lowered] == [True, False, True, False, False]
    assert outs[0] == outs[1] == outs[4] and outs[2] == outs[3]
    assert outs[0] == _eager(model, params, _prompt(17, cfg.vocab_size, seed=17), 4)


def test_no_new_tokens_returns_nothing():
    cfg, model, params = _model("dense")
    reg = build_registry(cfg, model, params)
    out = reg.get("generate")(Context.origin({"session": "s"}), [3, 4, 5], 0)
    assert out == {"tokens": []}


def test_prefill_is_built_from_the_model_given():
    """Two registries over one config: each prefills with its own model, so a
    wrapped model (as the benchmark's fault checks build) is the one served."""
    cfg, model, params = _model("dense")
    prefill = model.prefill

    def forced(p, batch, pad_to=0):
        logits, cache = prefill(p, batch, pad_to=pad_to)
        return logits.at[:, 7].set(1e9), cache

    wrapped = type(model)(**{**model.__dict__, "prefill": forced})
    prompt = _prompt(13, cfg.vocab_size, seed=5)
    ctx = Context.origin({"session": "s"})
    first = build_registry(cfg, wrapped, params).get("generate")(ctx, prompt, 2)["tokens"]
    plain = build_registry(cfg, model, params).get("generate")(ctx, prompt, 2)["tokens"]
    assert first[0] == 7 and plain == _eager(model, params, prompt, 2)
