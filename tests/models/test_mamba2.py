"""The Mamba-2 mixer and the hybrid block on the program's own paths.

The recurrence is held to a token-by-token loop written here in numpy;
the hybrid model (toy widths of ``chipbench/families/falcon_h1.py``, every
multiplier away from 1, seeded norms) to the benchmark's plain reference,
``chipbench/reference/falcon_h1.py``, which shares no code with the
program. All float32 on the CPU.
"""
import dataclasses
import functools
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elephas_tpu.models import mamba2, paged_decode
from elephas_tpu.models.transformer import (Mamba2Mixer, Multipliers,
                                            TransformerConfig, decode_block,
                                            decode_step, forward,
                                            init_kv_cache, init_params,
                                            param_specs, prefill_cache,
                                            prefill_cache_chunked)

from ._transformer_util import FORWARD

REPO = Path(__file__).resolve().parent.parent.parent


def _load(kind, name):
    path = REPO / "chipbench" / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"h1_{kind}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


SCAN = jax.jit(mamba2.ssd_chunk_scan, static_argnums=6)
UPDATE = jax.jit(mamba2.ssd_update)
# each compiled whole once a shape (and configuration): op by op, a call
# is dozens of small compiles
CONV = jax.jit(mamba2.causal_conv)
FAMILY = _load("families", "falcon_h1")
REFERENCE = _load("reference", "falcon_h1")
SIZES = dict(FAMILY.REHEARSE_SIZES, rms_norm_eps=1e-5, rope_theta=1e11,
             tie_word_embeddings=False, mamba_rms_norm=True,
             mamba_norm_before_gate=False, mamba_proj_bias=False,
             mamba_conv_bias=True)
T = 29


@pytest.fixture(scope="module")
def model():
    config = FAMILY.program_config(SIZES, max_seq_len=64,
                                   param_dtype="float32", dtype=jnp.float32)
    params = FAMILY.make_params(config, 3)
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, T), 1,
                                           config.vocab_size))
    want = np.asarray(jax.jit(lambda p, t: REFERENCE.forward(
        FAMILY.to_reference(p, config), t, SIZES))(params, tokens))
    return config, params, tokens, want


@pytest.fixture(scope="module")
def whole(model):
    """The first 28 tokens prefilled in one piece."""
    config, params, tokens, _ = model
    return jax.jit(lambda p, t: prefill_cache(p, t, config, 64))(
        params, tokens[:, :28])


# ------------------------------------------------------------ recurrence
@functools.partial(jax.jit, static_argnums=(0, 1))
def _inputs(t, seed=0, b=2, h=4, p=8, g=2, n=16):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(keys[0], (b, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (b, t, h)) - 1.0)
    A = -jnp.exp(jax.random.normal(keys[2], (h,)))
    B = jax.random.normal(keys[3], (b, t, g, n))
    C = jax.random.normal(keys[4], (b, t, g, n))
    state = jax.random.normal(keys[5], (b, h, p, n))
    return x, dt, A, B, C, state


def _token_by_token(x, dt, A, B, C, state):
    x, dt, A, B, C, s = (np.asarray(a, np.float64)
                         for a in (x, dt, A, B, C, state))
    hg = x.shape[2] // B.shape[2]
    ys = []
    for t in range(x.shape[1]):
        b_t, c_t = np.repeat(B[:, t], hg, 1), np.repeat(C[:, t], hg, 1)
        s = (np.exp(dt[:, t] * A)[..., None, None] * s
             + (dt[:, t, :, None] * x[:, t])[..., None] * b_t[:, :, None])
        ys.append(np.einsum("bhpn,bhn->bhp", s, c_t))
    return np.stack(ys, 1), s


@pytest.mark.parametrize("t,chunk", [(5, 8), (8, 8), (19, 4), (24, 8)])
def test_chunk_scan_is_the_recurrence_and_the_repeated_update(t, chunk):
    x, dt, A, B, C, state = _inputs(t, seed=t)
    want_y, want_s = _token_by_token(x, dt, A, B, C, state)
    y, s = SCAN(x, dt, A, B, C, state, chunk)
    np.testing.assert_allclose(y, want_y, atol=2e-4)
    np.testing.assert_allclose(s, want_s, atol=2e-4)
    ys = []
    for k in range(t):
        y_k, state = UPDATE(state, x[:, k], dt[:, k], A, B[:, k], C[:, k])
        ys.append(y_k)
    np.testing.assert_allclose(jnp.stack(ys, 1), want_y, atol=2e-4)
    np.testing.assert_allclose(state, want_s, atol=2e-4)


def test_the_scan_takes_the_state_it_is_given():
    """Two halves, the second from the first's state, are the whole; a
    second half from zero is not."""
    x, dt, A, B, C, state = _inputs(16)
    whole_y, whole_s = SCAN(x, dt, A, B, C, state, 4)
    cut = (lambda a: a[:, :6]), (lambda a: a[:, 6:])
    y0, s0 = SCAN(*(cut[0](a) for a in (x, dt)), A,
                  *(cut[0](a) for a in (B, C)), state, 4)
    y1, s1 = SCAN(*(cut[1](a) for a in (x, dt)), A,
                  *(cut[1](a) for a in (B, C)), s0, 4)
    np.testing.assert_allclose(jnp.concatenate([y0, y1], 1), whole_y,
                               atol=2e-4)
    np.testing.assert_allclose(s1, whole_s, atol=2e-4)
    y_cold, _ = SCAN(*(cut[1](a) for a in (x, dt)), A,
                     *(cut[1](a) for a in (B, C)), jnp.zeros_like(state), 4)
    assert np.abs(y_cold - y1).max() > 0.1


def test_the_convolution_carries_its_last_inputs():
    p = {"conv_w": jax.random.normal(jax.random.PRNGKey(0), (4, 6)),
         "conv_b": jnp.arange(6.0) / 10}
    xbc = jax.random.normal(jax.random.PRNGKey(1), (2, 9, 6))
    zero = jnp.zeros((2, 3, 6))
    whole, carried = CONV(p, xbc, zero)
    np.testing.assert_array_equal(carried, xbc[:, -3:])
    # by hand: tap k multiplies the input 3 - k tokens back
    padded = np.concatenate([np.zeros((2, 3, 6)), np.asarray(xbc)], 1)
    by_hand = sum(padded[:, k:k + 9] * np.asarray(p["conv_w"])[k]
                  for k in range(4)) + np.asarray(p["conv_b"])
    np.testing.assert_allclose(whole, jax.nn.silu(by_hand), atol=1e-5)
    head, carried = CONV(p, xbc[:, :4], zero)
    parts = [head]
    for k in range(4, 9):                      # then one token at a time
        out, carried = CONV(p, xbc[:, k:k + 1], carried)
        parts.append(out)
    np.testing.assert_allclose(jnp.concatenate(parts, 1), whole, atol=1e-6)
    cold, _ = CONV(p, xbc[:, 4:], zero)
    assert np.abs(cold[:, 0] - whole[:, 4]).max() > 0.05


def test_the_mixer_says_what_it_is():
    with pytest.raises(ValueError, match="d_ssm"):
        Mamba2Mixer(d_ssm=30, heads=4, head_dim=8, groups=2, d_state=16)
    with pytest.raises(ValueError, match="groups"):
        Mamba2Mixer(d_ssm=32, heads=4, head_dim=8, groups=3, d_state=16)
    with pytest.raises(ValueError, match="five"):
        Multipliers(ssm=(1.0, 1.0))
    mixer = Mamba2Mixer(d_ssm=4096, heads=32, head_dim=128, groups=2,
                        d_state=256)
    assert (mixer.conv_dim, mixer.in_dim) == (5120, 9248)
    assert mixer.segments == (4096, 4096, 512, 512, 32)
    plain = TransformerConfig()
    assert plain.state_leaves() == {} and plain.ssm is None
    with pytest.raises(ValueError, match="mha"):
        TransformerConfig(ssm=mixer, kv_cache_quant=True)
    hybrid = TransformerConfig(ssm=mixer, dtype=jnp.bfloat16)
    assert hybrid.state_leaves() == {
        "conv": ((3, 5120), jnp.bfloat16),
        "ssm": ((32, 128, 256), jnp.float32)}


# ------------------------------------------------- the model's own paths
def test_forward_is_the_plain_reference(model):
    config, params, tokens, want = model
    np.testing.assert_allclose(FORWARD(params, tokens, config), want,
                               atol=5e-5)
    specs = param_specs(config)
    assert (jax.tree_util.tree_structure(specs, is_leaf=lambda s: not
                                         isinstance(s, dict))
            == jax.tree_util.tree_structure(params))


@pytest.mark.parametrize("chunk", [28, 14, 10])      # 1, 2 and 3 chunks
def test_a_prompt_in_chunks_gives_one_state_and_one_set_of_logits(
        model, whole, chunk):
    config, params, tokens, want = model
    whole_logits, whole = whole
    logits, cache = jax.jit(lambda p, t: prefill_cache_chunked(
        p, t, config, 64, chunk=chunk))(params, tokens[:, :28])
    np.testing.assert_allclose(logits, want[:, 27], atol=5e-5)
    np.testing.assert_allclose(whole_logits, want[:, 27], atol=5e-5)
    for name in ("conv", "ssm"):
        for i in range(config.num_layers):
            np.testing.assert_allclose(
                cache["state"][f"layer_{i}"][name],
                whole["state"][f"layer_{i}"][name], atol=5e-5)
    if chunk == 10:
        step_logits, _ = jax.jit(lambda p, c, t: decode_step(
            p, c, t, 28, config))(params, cache, tokens[:, 28])
        np.testing.assert_allclose(step_logits, want[:, 28], atol=5e-5)


@pytest.mark.parametrize("leaf", ["conv", "ssm"])
def test_state_dropped_at_a_chunk_boundary_shows(model, leaf):
    """What the chunks test would read if a chunk did not carry ``leaf``
    over: far outside its tolerance."""
    config, params, tokens, want = model
    @jax.jit
    def two_chunks(params, tokens):
        cache = init_kv_cache(config, 2, 64)
        _, cache = decode_block(params, cache, tokens[:, :14], 0, config)
        cache["state"] = {
            layer: dict(state, **{leaf: jnp.zeros_like(state[leaf])})
            for layer, state in cache["state"].items()}
        return decode_block(params, cache, tokens[:, 14:28], 14, config,
                            last_only=True)[0]

    logits = two_chunks(params, tokens)
    assert logits.shape == (2, 1, config.vocab_size)
    assert np.abs(logits[:, 0] - want[:, 27]).max() > 50 * 5e-5


def test_token_by_token_through_the_cache_is_the_forward(model):
    config, params, tokens, want = model
    cache = init_kv_cache(config, 2, 64)
    step = jax.jit(lambda c, tok, pos: decode_step(params, c, tok, pos,
                                                   config))
    for t in range(T):
        logits, cache = step(cache, tokens[:, t], t)
        np.testing.assert_allclose(logits, want[:, t], atol=5e-5)


def _left_out(sizes_key, index=None):
    """The configuration with one multiplier set to 1."""
    sizes = dict(SIZES)
    if index is None:
        sizes[sizes_key] = 1.0
    else:
        sizes[sizes_key] = [1.0 if k == index else v
                            for k, v in enumerate(SIZES[sizes_key])]
    return sizes


@pytest.mark.parametrize("key,index", [
    ("embedding_multiplier", None), ("lm_head_multiplier", None),
    ("attention_in_multiplier", None), ("attention_out_multiplier", None),
    ("key_multiplier", None), ("ssm_in_multiplier", None),
    ("ssm_out_multiplier", None), ("mlp_multipliers", 0),
    ("mlp_multipliers", 1), *[("ssm_multipliers", k) for k in range(5)]])
def test_every_multiplier_is_seen_by_the_comparison(model, key, index):
    """The program with one multiplier left out is NOT the reference:
    the forward test above would fail for each of them."""
    config, params, tokens, want = model
    without = FAMILY.program_config(_left_out(key, index), max_seq_len=64,
                                    param_dtype="float32",
                                    dtype=jnp.float32)
    assert without.multipliers != config.multipliers
    got = jax.jit(lambda p, t: forward(p, t, without))(params, tokens)
    assert np.abs(got - want).max() > 100 * 5e-5


@pytest.mark.parametrize("leaf", ["norm", "D", "conv_b", "dt_bias",
                                  "A_log"])
def test_every_small_leaf_of_the_mixer_is_seen(model, leaf):
    config, params, tokens, want = model
    layer = params["layer_1"]
    flat = jnp.ones_like if leaf in ("norm", "D") else jnp.zeros_like
    broken = dict(params, layer_1=dict(layer, ssm=dict(
        layer["ssm"], **{leaf: flat(layer["ssm"][leaf])})))
    assert np.abs(FORWARD(broken, tokens, config) - want).max() > 100 * 5e-5


def test_the_gated_norm_norms_in_groups(model):
    config = model[0]
    y = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 64))
    z = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 64))
    weight = jnp.linspace(0.5, 1.5, 64)
    got = mamba2.gated_group_norm({"norm": weight}, y, z, config)
    gated = np.asarray(y * jax.nn.silu(z)).reshape(2, 3, 2, 32)
    by_hand = gated / np.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(got, by_hand.reshape(2, 3, 64) * weight,
                               atol=1e-5)
    whole = gated.reshape(2, 3, 64)
    whole = whole / np.sqrt((whole ** 2).mean(-1, keepdims=True) + 1e-5)
    assert np.abs(np.asarray(got) - whole * np.asarray(weight)).max() > 0.01


# --------------------------------------------------- the pool's two kinds
def test_prefill_and_paged_steps_through_the_slot_state(model):
    """Chunked prefill, install into a slot another row held, then paged
    steps: every step's logits are the reference's full forward."""
    config, params, tokens, want = model
    prompt = 20
    _, cache = jax.jit(lambda p, t: prefill_cache_chunked(
        p, t, config, 64, chunk=8))(params, tokens[:, :prompt])
    pool = paged_decode.init_paged_pool(config, 20, 4, slots=2)
    assert pool["state"]["layer_0"]["ssm"].shape == (2, 4, 16, 16)
    tables = np.stack([1 + np.arange(8), 9 + np.arange(8)]).astype(np.int32)
    rows = [jax.tree_util.tree_map(lambda a: a[r:r + 1], cache)
            for r in range(2)]
    with pytest.raises(ValueError, match="slot"):
        paged_decode.install_row_paged(pool, rows[0], tables[0], 5)
    for order in ((1, 0), (0, 1)):           # first each other's slot
        for slot, r in enumerate(order):
            pool = paged_decode.install_row_paged(pool, rows[r],
                                                  tables[slot], 5, slot=slot)
    step = jax.jit(lambda p, pl, tok, pos: paged_decode.decode_step_paged(
        p, pl, jnp.asarray(tables), tok, pos, config))
    for t in range(prompt, T):
        logits, pool = step(params, pool, tokens[:, t], jnp.full((2,), t))
        np.testing.assert_allclose(logits, want[:, t], atol=5e-5)
    with pytest.raises(ValueError, match="slots"):
        paged_decode.decode_step_paged(
            params, pool, jnp.asarray(tables[:1]), tokens[:1, 0],
            jnp.zeros((1,), jnp.int32), config)
    with pytest.raises(ValueError, match="slots >= 1"):
        paged_decode.init_paged_pool(config, 20, 4)


def test_a_stateless_pool_is_what_it_was():
    config = TransformerConfig(vocab_size=50, num_layers=1, num_heads=2,
                               d_model=16, d_ff=32, max_seq_len=32)
    pool = paged_decode.init_paged_pool(config, 4, 8)
    assert set(pool) == {"layer_0"} and set(pool["layer_0"]) == {"k", "v"}
    assert "state" not in init_kv_cache(config, 1, 32)
    paged_decode.require_stateless_cache(config, "anything")
    hybrid = dataclasses.replace(
        config, ssm=Mamba2Mixer(d_ssm=16, heads=2, head_dim=8, groups=1,
                                d_state=4))
    with pytest.raises(ValueError, match="register_prefix needs a snapshot"):
        paged_decode.require_stateless_cache(hybrid, "register_prefix")
    # the parameters' structure, not their values
    params = jax.eval_shape(lambda key: init_params(hybrid, key),
                            jax.random.PRNGKey(0))
    assert set(params["layer_0"]["ssm"]) == {
        "w_in", "conv_w", "conv_b", "A_log", "dt_bias", "D", "norm",
        "w_out"}
