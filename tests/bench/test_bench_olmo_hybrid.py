"""The olmo_hybrid family: its cut, its reference against the program, the
gated delta kernel's counts, matcher and readers, and a tiny run of its
serving cell on the CPU."""
from __future__ import annotations

import copy
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
from bench import harness, inputs
from bench import trace as tr
from bench.drivers import serve
from bench.harness import BENCH_DIR, load_module, peaks_for
from test_bench_spec import reduced_errors

CELL = "serve.olmo-hybrid-7b.longdoc"
family = load_module(BENCH_DIR / "families" / "olmo_hybrid.py")
gd = load_module(BENCH_DIR / "kernels" / "gated_delta.py")
PEAKS = peaks_for("TPU v5 lite")

#: every mechanism of the configuration at widths a test can run: two
#: periods of (linear, linear, full), 4 delta-rule heads of 16 / 32 with
#: doubled β, MHA 4 x 16 with whole-projection QK-norm and no rotary,
#: post-norm blocks, an untied vocabulary that pads to 512
TINY = {
    "name": "tiny-olmo-hybrid", "bench_family": "olmo_hybrid", "model_type": "olmo_hybrid",
    "vocab_size": 500, "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 6,
    "num_attention_heads": 4, "num_key_value_heads": 4, "hidden_act": "silu",
    "max_position_embeddings": 4096, "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False,
    "layer_types": ["linear_attention", "linear_attention", "full_attention"] * 2,
    "linear_num_key_heads": 4, "linear_num_value_heads": 4, "linear_key_head_dim": 16,
    "linear_value_head_dim": 32, "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}, "param_dtype": "float32",
    "compute_dtype": "float32", "remat": "none",
}
SERVE_MIX = dict(bench_tiny.SERVE_MIX, prompt_lengths=[21, 70], prompt_weights=[0.5, 0.5])


def _config():
    return harness.load_json(bench_tiny.ROOT / "bench/configs/olmo-hybrid-7b.json")


@pytest.mark.parametrize("layers,ok", [(8, True), (3, False)], ids=["two_periods", "three_layers"])
def test_the_cut_keeps_the_contract(layers, ok):
    cfg = dict(_config(), num_hidden_layers=layers)
    reduced = ["num_hidden_layers", "layer_types"]
    assert (reduced_errors(reduced, cfg) == []) is ok, reduced_errors(reduced, cfg)


def test_the_cut_is_two_whole_periods():
    cfg = _config()
    published = cfg["published"]["layer_types"]
    assert cfg["layer_types"] == published[:8] == published[8:16]
    assert len(published) == cfg["published"]["num_hidden_layers"] == 32
    assert family.segments(cfg) == [(("linear", "linear", "linear", "full"), 2)]
    catalog = {k: v for k, v in cfg.items() if k not in ("num_hidden_layers", "layer_types")}
    assert catalog["linear_key_head_dim"] * 2 == catalog["linear_value_head_dim"] == 192


def test_parameters_by_hand():
    """One linear layer 215.6 M, one full layer 185.8 M, the stage 2.436 B
    (two periods, the embedding and the untied head): the figures of the
    deployment's arithmetic."""
    cfg = _config()
    d, ff, h, dk, dv, V = 3840, 11008, 30, 96, 192, 100352
    mlp = 3 * d * ff
    linear = d * (2 * h * dk + 2 * h * dv + 2 * h) + h * dv * d + 4 * (2 * h * dk + h * dv) \
        + 2 * h + dv + 2 * d + mlp
    full = 4 * d * d + 2 * d + 2 * d + mlp
    assert linear == 215_570_172 and full == 185_809_920
    total = sum(int(np.prod(s)) for s in jax.tree.leaves(
        family.layout(cfg), is_leaf=lambda x: isinstance(x, tuple)))
    assert total == 6 * linear + 2 * full + 2 * d * V + d == 2_435_748_072


def test_kernel_counts_by_hand():
    c = {"batch": 1, "heads": 2, "seq": 8, "key_dim": 3, "value_dim": 5, "chunk": 4,
         "dtype_bytes": 2}
    strict, causal = 6, 10
    per_chunk = 3 * strict + 8 * strict + 3 * 4 * 3 * 5 + 8 * causal
    assert gd.flops(c) == 2 * 2 * 2 * per_chunk
    assert gd.bytes_moved(c) == 2 * 8 * 16 * 2 + 2 * 4 * 2 * 8 + 2 * 4 * 2 * 15
    assert gd.visited(dict(c, seq=9)) == 2 * 3
    # a 32k prompt's call is memory-bound on a v5e, about 130 FLOPs a byte
    call = {"batch": 1, "heads": 30, "seq": 32768, "key_dim": 96, "value_dim": 192,
            "chunk": 64, "dtype_bytes": 2}
    t, bound = gd.roofline_s(call, PEAKS)
    assert bound == "memory" and t == pytest.approx(gd.bytes_moved(call) / 819e9)
    assert 120 < gd.flops(call) / gd.bytes_moved(call) < 140


def _event(result, operands, dur=10.0):
    return tr.Event(f"%custom-call.3 = {result} custom-call({operands}), "
                    f'custom_call_target="tpu_custom_call", kernel_metadata={{}}', 0.0, dur)


T = "{3,2,1,0:T(8,128)(2,1)}"
QK = f"bf16[1,30,4096,96]{T} %q, bf16[1,30,4096,96]{T} %k, "
V = f"bf16[1,30,4096,192]{T} %v, "
ROWS = "f32[1,30,64,1,64]{4,3,2,1,0:T(1,128)} %g, f32[1,30,64,1,64]{4,3,2,1,0:T(1,128)} %b, "
H0 = "f32[1,30,192,96]{3,2,1,0:T(8,128)} %h0"
OUT = f"(bf16[1,30,4096,192]{T}, f32[1,30,192,96]{{3,2,1,0:T(8,128)}})"
CALL = {"batch": 1, "heads": 30, "seq": 4096, "key_dim": 96, "value_dim": 192, "chunk": 64,
        "dtype_bytes": 2}
EVENTS = {
    "forward": _event(OUT, QK + V + ROWS + H0),
    # the Mamba-2 scan: two outputs, six operands, dt and B/C of other ranks
    "ssd": _event("(bf16[2,64,2048,64]{3,2,1,0}, f32[2,64,64,128]{3,2,1,0})",
                  "bf16[2,64,2048,64]{3,2,1,0} %x, f32[2,64,2048]{2,1,0} %dt, "
                  "f32[2,64,2048]{2,1,0} %cum, bf16[2,2048,128]{2,1,0} %b, "
                  "bf16[2,2048,128]{2,1,0} %c, f32[2,64,64,128]{3,2,1,0} %h0"),
    # the flash forward: one output, three operands
    "flash": _event(f"bf16[1,30,4096,128]{T}",
                    f"bf16[1,30,4096,128]{T} %q, bf16[1,30,4096,128]{T} %k, "
                    f"bf16[1,30,4096,128]{T} %v"),
    # a state that is not [b, h, V, K]
    "other_state": _event(f"(bf16[1,30,4096,192]{T}, f32[1,30,96,192]{{3,2,1,0}})",
                          QK + V + ROWS + "f32[1,30,96,192]{3,2,1,0} %h0"),
    # rows that do not tile the sequence
    "other_rows": _event(OUT, QK + V + ROWS.replace("64,1,64", "32,1,64") + H0),
    "xla": tr.Event(f"%fusion.2 = bf16[1,30,4096,192]{T} fusion({V[:-2]}), kind=kLoop",
                    0.0, 10.0),
}


@pytest.mark.parametrize("name", sorted(EVENTS))
def test_matcher_takes_only_the_forward(name):
    assert gd.call_of(EVENTS[name]) == (CALL if name == "forward" else None)


def _observe(events, spans, peaks=PEAKS):
    trace = None if events is None else tr.TraceSummary(tr.RawTrace({0: events}, {}, []), 1.0)
    return harness.Observations(cell=harness.find_cell(CELL), spans=spans, trace=trace,
                                peaks=peaks)


def _read(metric, obs):
    return harness.load_reader(harness.find_cell(CELL), metric)(obs)


def _prefill(tokens):
    return {"name": "serve.prefill", "attrs": {"prompt_tokens": tokens}, "dur": 0.1}


def test_readers_on_a_synthetic_trace():
    """Two prompts of 4096 tokens, six 2 ms calls each (one per linear
    layer), beside the other kernels of the window."""
    events = [_event(OUT, QK + V + ROWS + H0, dur=2e6) for _ in range(12)]
    events += [EVENTS["ssd"], EVENTS["flash"], EVENTS["xla"]]
    spans = [_prefill(4096), _prefill(4096), {"name": "task:generate", "attrs": {}, "dur": 1.0}]
    obs = _observe(events, spans)
    assert _read("serve.gdn_fwd_ms", obs) == pytest.approx(24.0 / 8.192)
    least, _ = gd.roofline_s(CALL, PEAKS)
    assert _read("serve.gdn_fwd_roofline", obs) == pytest.approx(100 * least / 2e-3)


def test_each_call_is_counted_at_its_own_shape():
    longer = _event(OUT.replace("4096", "8192"),
                    (QK + V).replace("4096", "8192") + ROWS.replace("64,1,64", "128,1,64") + H0,
                    dur=3e6)
    obs = _observe([EVENTS["forward"], longer], [_prefill(4096)])
    t4, _ = gd.roofline_s(CALL, PEAKS)
    t8, _ = gd.roofline_s(dict(CALL, seq=8192), PEAKS)
    assert _read("serve.gdn_fwd_roofline", obs) == pytest.approx(100 * (t4 + t8) / (3e-3 + 1e-8))


@pytest.mark.parametrize("events,spans", [
    (None, [_prefill(4096)]),                                  # no trace
    ([EVENTS["ssd"], EVENTS["flash"]], [_prefill(4096)]),       # no such kernel (the parent)
    ([EVENTS["forward"]], []),                                 # no prefill in the window
], ids=["no_trace", "no_kernel", "no_prefill"])
def test_readers_read_nothing_where_nothing_matches(events, spans):
    obs = _observe(events, spans)
    assert _read("serve.gdn_fwd_ms", obs) is None
    if spans:
        assert _read("serve.gdn_fwd_roofline", obs) is None


def test_roofline_needs_the_peaks():
    obs = _observe([EVENTS["forward"]], [_prefill(4096)], peaks={})
    assert _read("serve.gdn_fwd_roofline", obs) is None
    assert _read("serve.gdn_fwd_ms", obs) == pytest.approx(1e-5 / 4.096)


def test_kernel_counter_counts_chunks_per_lowering():
    from repro.kernels import ops
    from repro.obs.metrics import metrics

    b, h, s, k, v, chunk = 2, 3, 40, 8, 16, 16
    r = np.random.default_rng(5)
    f = lambda *shape: jnp.asarray(r.normal(size=shape), jnp.float32)
    counter = metrics().counter("repro_kernel_gdn_chunks_total", kind="visited")
    before = counter.value
    ops.gated_delta(f(b, h, s, k), f(b, h, s, k), f(b, h, s, v), -jnp.abs(f(b, h, s)),
                    jax.nn.sigmoid(f(b, h, s)), chunk=chunk, impl="interpret")
    call = {"batch": b, "heads": h, "seq": s, "chunk": chunk}
    assert counter.value - before == gd.visited(call) == 2 * 3 * 3


def _program(cfg, impl="interpret"):
    from repro.models import build

    model = build(family.program_config(cfg, attn_impl=impl))
    family.check_layout(cfg, model)
    return model


def test_prefill_then_decode_matches_the_reference():
    """The program's logits, prefill at 37 tokens (not a multiple of the
    chunk) and then decode through the cache, against the token-by-token
    float32 reference's full forward, on the family's seeded weights. Both
    compute in float32 and differ by reassociation (the chunked kernel, the
    flash recurrence, the decode's cache): 2e-5 of logits of about 0.5
    holds that with room, while the controls below move them by 1e-2 and
    more."""
    params = family.make_weights(TINY, 3_000_000_101)
    toks = jnp.asarray(np.random.default_rng(3).integers(0, 500, (1, 48)), jnp.int32)
    model = _program(TINY)
    logits, cache = model.prefill(params, {"tokens": toks[:, :37]}, pad_to=48)
    got = [logits]
    for i in range(37, 47):
        logits, cache = model.decode_step(params, cache, {"token": toks[:, i]})
        got.append(logits)
    got = np.concatenate([np.asarray(g) for g in got])
    want = np.asarray(family.position_logits(TINY)(params, toks, np.arange(36, 47)))
    assert np.abs(got - want).max() < 2e-5
    for control in ("bf16_state", "beta1", "float8"):
        moved = np.asarray(family.position_logits(TINY, control)(params, toks, np.arange(36, 47)))
        assert np.abs(moved - want).max() > 1e-2, control


def test_prefill_logits_match_the_reference():
    """The last position's logits of a prefill alone, at the kernel's own
    chunk of 64 over 100 tokens (a partial second chunk), as above."""
    params = family.make_weights(TINY, 3_000_000_103)
    toks = jnp.asarray(np.random.default_rng(4).integers(0, 500, (1, 100)), jnp.int32)
    logits, _ = _program(TINY).prefill(params, {"tokens": toks})
    want = family.position_logits(TINY)(params, toks, np.array([99]))
    assert np.abs(np.asarray(logits) - np.asarray(want)).max() < 2e-5


def test_weights_follow_fla_initialisation():
    w = family.make_weights(TINY, 7)
    g = w["seg0"]["u0"]["gdn"]
    A = np.exp(np.asarray(g["A_log"]))
    dt = np.log1p(np.exp(np.asarray(g["dt_bias"])))
    assert g["A_log"].shape == (2, 4) and 0.0 < A.min() and A.max() <= 16.0
    assert 1e-3 <= dt.min() * (1 + 1e-5) and dt.max() <= 1e-1 * (1 + 1e-5)
    assert not np.allclose(np.asarray(w["seg0"]["u0"]["gdn"]["A_log"]),
                           np.asarray(w["seg0"]["u1"]["gdn"]["A_log"]))
    again = family.make_weights(TINY, 7)
    assert all(np.array_equal(a, b) for a, b in zip(jax.tree.leaves(w), jax.tree.leaves(again),
                                                   strict=True))


def test_a_program_without_the_layer_fails_cleanly(monkeypatch):
    """A program older than the gated delta layer exits the run at once, with
    the harness's code for a cell it cannot run."""
    import types

    from repro.configs import base

    fields = [f for f in dataclasses.fields(base.ModelConfig) if not f.name.startswith("gdn_")]
    monkeypatch.setattr(family, "dataclasses", types.SimpleNamespace(fields=lambda cls: fields))
    with pytest.raises(harness.BenchError) as e:
        family.program_config(TINY)
    assert e.value.code == harness.EXIT_BAD_CELL


def test_controls_fail_the_check():
    """The reference with its state in bfloat16, with β not doubled, or with
    float8 matrices, put in the program's place, reads above the tiny cell's
    limit."""
    reqs = inputs.requests(SERVE_MIX, rate=4.0, seconds=2.0, seed=4, vocab=TINY["vocab_size"])
    sample = [(r.prompt, list(range(1, 9))) for r in reqs[:3]]
    controls = ("bf16_state", "beta1", "float8")
    gaps = serve.check_sample(family, TINY, 4, sample, controls=controls)
    limit = bench_tiny.SERVE_SETTINGS["limits"]["logit_gap"]
    assert all(gaps[f"control_{c}"] > limit for c in controls), gaps


def test_tiny_cell_serves_through_the_gateway(tmp_path):
    real = harness.find_cell(CELL)
    assert {m["name"] for m in real.per_layer} >= {
        "serve.gdn_fwd_ms", "serve.gdn_fwd_roofline", "serve.prefill_ms"}
    cell = harness.Cell(name=CELL, chips=1, config=copy.deepcopy(TINY),
                        traffic=copy.deepcopy(SERVE_MIX),
                        settings=copy.deepcopy(bench_tiny.SERVE_SETTINGS),
                        end_to_end=real.end_to_end, per_layer=real.per_layer,
                        seed=3_000_000_107, seconds=1.5, out_dir=tmp_path / "bench-out")
    line = bench_tiny.run(cell)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] == 6
    assert line["metrics"]["serve_latency_p70_s"]["value"] > 0
    json.dumps(line)
