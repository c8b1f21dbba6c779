"""The gated delta rule: kernel against the token-by-token recurrence, the
Gated DeltaNet layer through prefill and decode, and the Olmo block options
that leave every other configuration as it was."""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_variant
from repro.configs.base import ModelConfig
from repro.kernels import ops, ref
from repro.models import build
from repro.obs.metrics import metrics


def _args(Bt, H, T, K, V, *, beta_max=2.0, dtype=jnp.float32, seed=0):
    """Normalised keys and queries (the layer's), values, log decays with
    α in about [0.6, 1] and β in [0, beta_max], and a state to carry in."""
    r = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = jnp.asarray(unit(r.normal(size=(Bt, H, T, K))) * K ** -0.5, dtype)
    k = jnp.asarray(unit(r.normal(size=(Bt, H, T, K))), dtype)
    v = jnp.asarray(r.normal(size=(Bt, H, T, V)), dtype)
    g = jnp.asarray(-0.5 * r.uniform(size=(Bt, H, T)), jnp.float32)
    beta = jnp.asarray(beta_max * r.uniform(size=(Bt, H, T)), jnp.float32)
    s0 = jnp.asarray(r.normal(size=(Bt, H, V, K)), jnp.float32)
    return q, k, v, g, beta, s0


@pytest.mark.parametrize("T,chunk,beta_max,carry", [
    (40, 16, 2.0, True),     # two whole chunks and a partial one, a state carried in
    (37, 8, 2.0, False),     # a partial last chunk, from zero
    (16, 16, 1.0, True),     # one whole chunk, β without its factor 2
    (5, 32, 2.0, True),      # shorter than a chunk
], ids=["partial_carry", "partial_zero", "one_chunk", "short"])
def test_kernel_matches_the_recurrence(T, chunk, beta_max, carry):
    """The Pallas kernel (interpreted) and the chunked jnp form against the
    token-by-token recurrence, in float32: they differ by reassociation
    only, about 1e-6 of outputs and states of order 1; 2e-5 holds that with
    room, where a wrong decay or a missing factor of β moves them by 1e-1."""
    q, k, v, g, beta, s0 = _args(2, 3, T, 8, 12, beta_max=beta_max)
    s0 = s0 if carry else None
    o_seq, s_seq = ref.gated_delta_ref(q, k, v, g, beta, initial_state=s0)
    before = metrics().counter("repro_kernel_gdn_chunks_total", kind="visited").value
    o_k, s_k = ops.gated_delta(q, k, v, g, beta, chunk=chunk, initial_state=s0,
                               impl="interpret")
    visited = metrics().counter("repro_kernel_gdn_chunks_total", kind="visited").value - before
    o_c, s_c = ops.gated_delta(q, k, v, g, beta, chunk=chunk, initial_state=s0, impl="ref")
    assert visited == 2 * 3 * -(-T // chunk)
    for o, s in ((o_k, s_k), (o_c, s_c)):
        assert o.shape == o_seq.shape and s.shape == s_seq.shape == (2, 3, 12, 8)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_seq), atol=2e-5)
        np.testing.assert_allclose(np.asarray(s), np.asarray(s_seq), atol=2e-5)
    # the planted fault: β without its factor 2 is far outside that
    if beta_max == 2.0:
        o_half, _ = ref.gated_delta_ref(q, k, v, g, beta / 2, initial_state=s0)
        assert np.abs(np.asarray(o_half) - np.asarray(o_seq)).max() > 1e-1


def test_kernel_is_stable_at_the_reflection():
    """β = 2 on one repeated key with no decay: every transition is a
    reflection, and the kernel's block inverse must stay as exact as the
    recurrence (a power series of the triangular matrix would not)."""
    q, k, v, g, beta, s0 = _args(1, 2, 64, 8, 12)
    k = jnp.broadcast_to(k[:, :, :1], k.shape)
    g, beta = jnp.zeros_like(g), jnp.full_like(beta, 2.0)
    o_seq, s_seq = ref.gated_delta_ref(q, k, v, g, beta, initial_state=s0)
    o_k, s_k = ops.gated_delta(q, k, v, g, beta, chunk=64, initial_state=s0, impl="interpret")
    # outputs of order 10 after 64 reflections; float32 round-off only
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_seq), atol=5e-4)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_seq), atol=5e-4)


def test_kernel_takes_bfloat16_operands():
    """bf16 q, k and v (the served dtype): the output is rounded to bf16 and
    the products with the state run in float32, so the gap to the float32
    recurrence is of bf16's rounding (2^-8 of values of order 1)."""
    q, k, v, g, beta, s0 = _args(1, 2, 48, 16, 32)
    o_seq, s_seq = ref.gated_delta_ref(q, k, v, g, beta, initial_state=s0)
    bf = lambda x: x.astype(jnp.bfloat16)
    o_k, s_k = ops.gated_delta(bf(q), bf(k), bf(v), g, beta, chunk=16, initial_state=s0,
                               impl="interpret")
    assert o_k.dtype == jnp.bfloat16 and s_k.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(o_k, np.float32), np.asarray(o_seq), atol=5e-2)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_seq), atol=5e-2)


def test_chunk_must_be_a_power_of_two():
    q, k, v, g, beta, _ = _args(1, 1, 8, 4, 4)
    with pytest.raises(ValueError):
        ops.gated_delta(q, k, v, g, beta, chunk=24, impl="interpret")


def _hybrid(**changes):
    """Two periods of (gdn, gdn, attn) with the Olmo block, at smoke widths."""
    cfg = ModelConfig(
        name="gdn-smoke", family="hybrid", num_layers=6, d_model=64, num_heads=4,
        num_kv_heads=4, head_dim=16, d_ff=96, vocab_size=500,
        block_pattern=("gdn", "gdn", "attn") * 2, rope_fraction=0.0, qk_norm_whole=True,
        post_norm=True, gdn_n_heads=4, gdn_key_dim=16, gdn_value_dim=32, gdn_neg_eigval=True, param_dtype="float32", compute_dtype="float32", remat="none",
        attn_impl="interpret")
    return replace(cfg, **changes)


def test_prefill_then_decode_matches_one_prefill():
    """Prefill 21 tokens (a partial chunk), then decode through the cache
    (conv tail and float32 delta state beside the attention layers' KV):
    each step's logits are the full prefill's at that length, to float32
    reassociation."""
    cfg = _hybrid()
    model = build(cfg)
    params, _ = model.init(jax.random.key(0))
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 500, (1, 30)), jnp.int32)
    prefill = jax.jit(model.prefill, static_argnames="pad_to")
    decode = jax.jit(model.decode_step)
    logits, cache = prefill(params, {"tokens": toks[:, :21]}, pad_to=30)
    seg = cache["seg0"]["u0"]
    assert seg["delta"].shape == (2, 1, 4, 32, 16) and seg["delta"].dtype == jnp.float32
    assert seg["conv"].shape == (2, 1, 3, 2 * 4 * 16 + 4 * 32)
    assert cache["seg0"]["u2"]["k"].shape == (2, 1, 30, 4, 16)
    for i in range(21, 29):
        logits, cache = decode(params, cache, {"token": toks[:, i]})
        if i in (21, 28):
            full, _ = prefill(params, {"tokens": toks[:, :i + 1]})
            np.testing.assert_allclose(np.asarray(logits), np.asarray(full), atol=1e-5)


def test_the_layer_runs_with_a_state_carried_in():
    """A prompt prefilled in two pieces (the second from the first's cache
    state, through the kernel) gives the one-piece state and output."""
    from repro.models.gated_delta import gdn_mixer, init_gdn_state

    cfg = _hybrid()
    params, _ = build(cfg).init(jax.random.key(1))
    p = jax.tree.map(lambda a: a[0], params["seg0"]["u0"]["gdn"])
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 40, 64)), jnp.float32)
    whole, s_whole = gdn_mixer(x, p, cfg, state=init_gdn_state(cfg, 1, jnp.float32))
    a, s_a = gdn_mixer(x[:, :23], p, cfg, state=init_gdn_state(cfg, 1, jnp.float32))
    b, s_b = gdn_mixer(x[:, 23:], p, cfg, state=s_a)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([a, b], 1)), np.asarray(whole),
                               atol=1e-5)
    for key in ("conv", "delta"):
        np.testing.assert_allclose(np.asarray(s_b[key]), np.asarray(s_whole[key]), atol=1e-5)


def test_post_norm_and_whole_qk_norm_are_their_own_mechanisms():
    """Each Olmo option changes the function (not only the parameters): the
    whole-projection QK-norm has H x head_dim scales, and moving the norm to
    the output changes the logits of the same weights."""
    on = _hybrid()
    params, _ = build(on).init(jax.random.key(0))
    attn = params["seg0"]["u2"]["attn"]
    assert attn["q_norm"].shape == (2, 64) and attn["k_norm"].shape == (2, 64)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 500, (1, 12)), jnp.int32)
    a, _ = build(on).prefill(params, {"tokens": toks})
    b, _ = build(replace(on, post_norm=False)).prefill(params, {"tokens": toks})
    assert np.abs(np.asarray(a) - np.asarray(b)).max() > 1e-3


#: parameter trees and logits of smoke configurations before the Gated
#: DeltaNet layer and the Olmo options existed, at seed 0: with the options
#: off the program must build and compute them as it did
BEFORE = {
    "qwen3-1.7b": ("8482dcce537bf39f", 13, [0.03382574021816254, 0.20934481918811798,
                                             -0.07052285969257355],
                   [0.25110360980033875, 0.021583296358585358, 0.13401556015014648]),
    "stablelm-1.6b": ("886be4ba34471f80", 15, [-0.05392840877175331, 0.18748074769973755,
                                                0.13863103091716766],
                      [0.16585136950016022, -0.008614511229097843, -0.01101622637361288]),
    "granite-4.0-h-micro": ("d7eaecb9c4ec926a", 24, [0.01329041738063097, 0.1437569260597229,
                                                      0.010149206966161728],
                            [0.016572967171669006, 0.1602792739868164,
                             -0.01664314605295658]),
}


@pytest.mark.parametrize("arch", sorted(BEFORE))
def test_options_off_leave_other_configs_as_before(arch):
    import hashlib

    cfg = smoke_variant(get_config(arch))
    assert not (cfg.post_norm or cfg.qk_norm_whole or cfg.gdn_n_heads)
    model = build(cfg)
    params, _ = model.init(jax.random.key(0))
    sig = sorted((jax.tree_util.keystr(p), tuple(x.shape), str(x.dtype))
                 for p, x in jax.tree_util.tree_leaves_with_path(params))
    tree, leaves, prefill, decode = BEFORE[arch]
    assert (hashlib.sha256(repr(sig).encode()).hexdigest()[:16], len(sig)) == (tree, leaves)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 24)), jnp.int32)
    logits, cache = model.prefill(params, {"tokens": toks}, pad_to=26)
    step, _ = model.decode_step(params, cache, {"token": jnp.argmax(logits, -1)})
    # float32 on the CPU, one build: equal to the last bits but for XLA's
    # own reassociation across versions
    np.testing.assert_allclose(np.asarray(logits[0, :3]), prefill, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(step[0, :3]), decode, rtol=1e-5, atol=1e-7)
