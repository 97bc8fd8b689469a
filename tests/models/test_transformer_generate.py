"""Transformer flagship tests: cached decode against ``forward``,
generation, sampling filters, beam search, and the position and
window variants through decode."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from elephas_tpu.models.transformer import (TransformerConfig, forward,
                                            init_params, make_train_step,
                                            shard_params)

from ._transformer_util import (DECODE_STEP, FORWARD, LM_LOSS, _config,
                                _gqa_config, _moe_config, _rope_config,
                                assert_greedy_continuation)


def test_decode_step_matches_forward_teacher_forced():
    """Feeding a sequence through the KV-cache decode loop must reproduce
    the full forward pass's logits position by position."""
    from elephas_tpu.models.transformer import decode_step, init_kv_cache

    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                           config.vocab_size))
    full = np.asarray(FORWARD(params, jnp.asarray(tokens), config))

    cache = init_kv_cache(config, 2, max_len=12)
    step = jax.jit(lambda cache, tok, pos: decode_step(params, cache, tok,
                                                       pos, config))
    for t in range(12):
        logits, cache = step(cache, jnp.asarray(tokens[:, t]), t)
        np.testing.assert_allclose(np.asarray(logits), full[:, t],
                                   atol=2e-4, rtol=2e-4)


def test_decode_step_matches_forward_moe():
    from elephas_tpu.models.transformer import decode_step, init_kv_cache

    config = _moe_config(num_experts=4, expert_top_k=2)
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                           config.vocab_size))
    full = np.asarray(FORWARD(params, jnp.asarray(tokens), config))
    cache = init_kv_cache(config, 2, max_len=8)
    step = jax.jit(lambda cache, tok, pos: decode_step(params, cache, tok,
                                                       pos, config))
    for t in range(8):
        logits, cache = step(cache, jnp.asarray(tokens[:, t]), t)
        np.testing.assert_allclose(np.asarray(logits), full[:, t],
                                   atol=2e-4, rtol=2e-4)


def test_generate_greedy_is_deterministic_and_shaped():
    from elephas_tpu.models.transformer import generate

    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (3, 5), 0,
                                config.vocab_size)
    out1 = np.asarray(generate(params, prompt, 6, config))
    out2 = np.asarray(generate(params, prompt, 6, config))
    assert out1.shape == (3, 6)
    np.testing.assert_array_equal(out1, out2)
    assert (out1 >= 0).all() and (out1 < config.vocab_size).all()
    # greedy continuation must equal step-by-step argmax over forward
    assert_greedy_continuation(params, prompt, out1, config)


def test_generate_sampling_and_length_validation():
    from elephas_tpu.models.transformer import generate

    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 4), 0,
                                config.vocab_size)
    out = np.asarray(generate(params, prompt, 5, config, temperature=0.8,
                              key=jax.random.PRNGKey(7)))
    assert out.shape == (2, 5)
    import pytest

    with pytest.raises(ValueError, match="exceeds"):
        generate(params, prompt, config.max_seq_len, config)


def test_decode_step_routed_config_uses_dense_gating():
    """Decode always uses dense top-k gating (capacity drops are a
    training-time artifact): for a routed-dispatch config, teacher-forced
    decode logits must equal the dense-dispatch forward pass."""
    import dataclasses

    from elephas_tpu.models.transformer import decode_step, init_kv_cache

    config = _moe_config(num_experts=8, expert_top_k=2,
                         moe_dispatch="routed")
    dense_config = dataclasses.replace(config, moe_dispatch="dense")
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                           config.vocab_size))
    full = np.asarray(FORWARD(params, jnp.asarray(tokens), dense_config))
    cache = init_kv_cache(config, 2, max_len=8)
    step = jax.jit(lambda cache, tok, pos: decode_step(params, cache, tok,
                                                       pos, config))
    for t in range(8):
        logits, cache = step(cache, jnp.asarray(tokens[:, t]), t)
        np.testing.assert_allclose(np.asarray(logits), full[:, t],
                                   atol=2e-4, rtol=2e-4)


def test_rope_decode_matches_forward():
    from elephas_tpu.models.transformer import decode_step, init_kv_cache

    config = _rope_config()
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 10),
                                           0, config.vocab_size))
    full = np.asarray(FORWARD(params, jnp.asarray(tokens), config))
    cache = init_kv_cache(config, 2, max_len=10)
    step = jax.jit(lambda cache, tok, pos: decode_step(params, cache, tok,
                                                       pos, config))
    for t in range(10):
        logits, cache = step(cache, jnp.asarray(tokens[:, t]), t)
        np.testing.assert_allclose(np.asarray(logits), full[:, t],
                                   atol=2e-4, rtol=2e-4)


def test_rope_generate_greedy_matches_forward_loop():
    from elephas_tpu.models.transformer import generate

    config = _rope_config()
    params = init_params(config, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 4), 0,
                                config.vocab_size)
    out = np.asarray(generate(params, prompt, 5, config))
    assert_greedy_continuation(params, prompt, out, config)


def test_gqa_decode_matches_forward_and_cache_is_smaller():
    """Teacher-forced decode through the kv_heads-wide cache reproduces
    the full forward logits; the cache is group-fold smaller than MHA's."""
    from elephas_tpu.models.transformer import decode_step, init_kv_cache

    for kv in (1, 2):  # MQA and 2-group GQA
        config = _gqa_config(kv)
        params = init_params(config, jax.random.PRNGKey(0))
        tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1),
                                               (2, 10), 0, config.vocab_size))
        full = np.asarray(FORWARD(params, jnp.asarray(tokens), config))
        cache = init_kv_cache(config, 2, max_len=10)
        assert cache["layer_0"]["k"].shape == (2, kv, 10, config.head_dim)
        step = jax.jit(lambda cache, tok, pos: decode_step(
            params, cache, tok, pos, config))
        for t in range(10):
            logits, cache = step(cache, jnp.asarray(tokens[:, t]), t)
            np.testing.assert_allclose(np.asarray(logits), full[:, t],
                                       atol=2e-4, rtol=2e-4)


def test_gqa_rope_generate_runs():
    import dataclasses

    from elephas_tpu.models.transformer import generate

    config = dataclasses.replace(_gqa_config(2), positional="rope")
    params = init_params(config, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 4), 0,
                                config.vocab_size)
    out = np.asarray(generate(params, prompt, 5, config))
    assert out.shape == (2, 5)
    # greedy continuation equals argmax over the full forward
    assert_greedy_continuation(params, prompt, out, config)


def test_generate_top_k_and_top_p_sampling():
    from elephas_tpu.models.transformer import _filter_logits, generate

    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 4), 0,
                                config.vocab_size)
    key = jax.random.PRNGKey(3)

    # top_k=1 sampling degenerates to greedy
    greedy = np.asarray(generate(params, prompt, 6, config))
    tk1 = np.asarray(generate(params, prompt, 6, config, temperature=1.0,
                              key=key, top_k=1))
    np.testing.assert_array_equal(greedy, tk1)

    # permissive filters change nothing vs plain sampling (same key)
    plain = np.asarray(generate(params, prompt, 6, config, temperature=1.0,
                                key=key))
    loose = np.asarray(generate(params, prompt, 6, config, temperature=1.0,
                                key=key, top_k=config.vocab_size,
                                top_p=1.0))
    np.testing.assert_array_equal(plain, loose)

    # filter semantics on a known distribution
    logits = jnp.log(jnp.asarray([[0.5, 0.25, 0.15, 0.1]]))
    f = np.asarray(_filter_logits(logits, top_k=2, top_p=None))
    assert np.isfinite(f[0, :2]).all() and (f[0, 2:] < -1e29).all()
    f = np.asarray(_filter_logits(logits, top_k=None, top_p=0.6))
    # nucleus at 0.6: keep 0.5 then 0.25 (cum 0.5 < 0.6 keeps the 2nd)
    assert np.isfinite(f[0, :2]).all() and (f[0, 2:] < -1e29).all()
    f = np.asarray(_filter_logits(logits, top_k=None, top_p=0.4))
    assert np.isfinite(f[0, 0]) and (f[0, 1:] < -1e29).all()

    import pytest
    with pytest.raises(ValueError):
        generate(params, prompt, 4, config, temperature=1.0, key=key,
                 top_k=0)
    with pytest.raises(ValueError):
        generate(params, prompt, 4, config, temperature=1.0, key=key,
                 top_p=0.0)


def test_beam_search_beats_greedy_and_beam1_equals_greedy():
    from elephas_tpu.models.transformer import beam_search, generate

    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (3, 4), 0,
                                config.vocab_size)

    greedy = np.asarray(generate(params, prompt, 6, config))
    seqs, scores = beam_search(params, prompt, 6, config, num_beams=1)
    np.testing.assert_array_equal(np.asarray(seqs)[:, 0], greedy)

    seqs4, scores4 = beam_search(params, prompt, 6, config, num_beams=4)
    assert seqs4.shape == (3, 4, 6) and scores4.shape == (3, 4)
    # scores sorted best-first and the best beam >= greedy's joint logp
    s4 = np.asarray(scores4)
    assert (np.diff(s4, axis=1) <= 1e-5).all()

    def joint_logp(seq_tokens):
        full = np.concatenate([np.asarray(prompt), seq_tokens], axis=1)
        logits = np.asarray(FORWARD(params, jnp.asarray(full), config))
        logp = jax.nn.log_softmax(jnp.asarray(logits), axis=-1)
        total = np.zeros(full.shape[0])
        for t in range(6):
            pos = prompt.shape[1] - 1 + t
            total += np.asarray(logp)[np.arange(full.shape[0]), pos,
                                      full[:, pos + 1]]
        return total

    g = joint_logp(greedy)
    b = joint_logp(np.asarray(seqs4)[:, 0])
    assert (b >= g - 1e-4).all(), (b, g)


def test_beam_search_eos_freezes_finished_beams():
    from elephas_tpu.models.transformer import beam_search

    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 3), 0,
                                config.vocab_size)
    eos = 5
    seqs, scores = beam_search(params, prompt, 8, config, num_beams=3,
                               eos_id=eos, length_penalty=1.0)
    s = np.asarray(seqs)
    # after the first eos in a beam, every subsequent token is eos
    for b in range(2):
        for k in range(3):
            row = s[b, k]
            hits = np.flatnonzero(row == eos)
            if hits.size:
                assert (row[hits[0]:] == eos).all()
    assert np.isfinite(np.asarray(scores)).all()


def test_generate_under_dp_tp_sharded_params_matches_unsharded():
    """Serving story: generation with tensor/data-parallel-sharded params
    runs through GSPMD (the decode scan partitions automatically) and
    reproduces the single-device continuation token for token."""
    from elephas_tpu.models.transformer import generate

    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (4, 5), 0,
                                config.vocab_size)
    ref = np.asarray(generate(params, prompt, 8, config))

    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
    sp = shard_params(params, config, mesh)
    pd = jax.device_put(prompt, NamedSharding(mesh, P("data", None)))
    got = np.asarray(generate(sp, pd, 8, config))
    np.testing.assert_array_equal(ref, got)


def test_llama_style_config_trains_and_decodes():
    """The full modern-LLM configuration — RoPE + GQA + SwiGLU + RMSNorm
    + untied head + chunked loss — trains, and decode matches forward."""
    import dataclasses

    from elephas_tpu.models.transformer import init_kv_cache

    config = TransformerConfig(vocab_size=64, num_layers=2, num_heads=4,
                               num_kv_heads=2, d_model=32, d_ff=64,
                               max_seq_len=32, positional="rope",
                               mlp_variant="swiglu", norm="rmsnorm",
                               tied_embedding=False, loss_vocab_chunk=16,
                               dtype=jnp.float32)
    params = init_params(config, jax.random.PRNGKey(0))
    assert "w3" in params["layer_0"]["mlp"]
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (4, 12),
                                           0, 64))
    full = np.asarray(FORWARD(params, jnp.asarray(tokens), config))
    cache = init_kv_cache(config, 4, max_len=12)
    for t in range(12):
        logits, cache = DECODE_STEP(params, cache,
                                    jnp.asarray(tokens[:, t]), t, config)
        np.testing.assert_allclose(np.asarray(logits), full[:, t],
                                   atol=2e-4, rtol=2e-4)

    # chunked == dense loss for this config too
    dense_cfg = dataclasses.replace(config, loss_vocab_chunk=None)
    np.testing.assert_allclose(
        float(LM_LOSS(params, jnp.asarray(tokens), config)),
        float(LM_LOSS(params, jnp.asarray(tokens), dense_cfg)),
        atol=1e-5, rtol=1e-5)

    tx = optax.adam(1e-2)
    opt = tx.init(params)
    step = make_train_step(config, tx)
    first = None
    for _ in range(8):
        params, opt, loss = step(params, opt, jnp.asarray(tokens))
        first = first if first is not None else float(loss)
    assert float(loss) < first

    # sharded parity (tp shards the swiglu gate too)
    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
    sp = shard_params(params, config, mesh)
    td = jax.device_put(jnp.asarray(tokens),
                        NamedSharding(mesh, P("data", None)))
    sharded = np.asarray(jax.jit(
        lambda p, t: forward(p, t, config, mesh=mesh, batch_axis="data",
                             model_axis="model"))(sp, td))
    expected = np.asarray(FORWARD(params, jnp.asarray(tokens), config))
    np.testing.assert_allclose(expected, sharded, atol=2e-3)


def test_sliding_window_attention_semantics_and_decode_parity():
    import dataclasses

    from elephas_tpu.models.transformer import init_kv_cache

    base = _config()
    params = init_params(base, jax.random.PRNGKey(0))
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 12),
                                           0, 64))

    # a window covering the whole sequence equals full causal attention
    wide = dataclasses.replace(base, attention_window=64)
    np.testing.assert_allclose(
        np.asarray(FORWARD(params, jnp.asarray(tokens), wide)),
        np.asarray(FORWARD(params, jnp.asarray(tokens), base)),
        atol=1e-5, rtol=1e-5)

    # a tight window changes late positions but NOT the first `w`
    tight = dataclasses.replace(base, attention_window=3)
    out_t = np.asarray(FORWARD(params, jnp.asarray(tokens), tight))
    out_f = np.asarray(FORWARD(params, jnp.asarray(tokens), base))
    np.testing.assert_allclose(out_t[:, :3], out_f[:, :3], atol=1e-5,
                               rtol=1e-5)
    assert np.abs(out_t[:, 6:] - out_f[:, 6:]).max() > 1e-5

    # teacher-forced decode must match the windowed forward
    cache = init_kv_cache(tight, 2, max_len=12)
    for t in range(12):
        logits, cache = DECODE_STEP(params, cache,
                                    jnp.asarray(tokens[:, t]), t, tight)
        np.testing.assert_allclose(np.asarray(logits), out_t[:, t],
                                   atol=2e-4, rtol=2e-4)

    import pytest
    with pytest.raises(ValueError):
        dataclasses.replace(base, attention_window=0)


def test_sliding_window_trains_and_generates():
    import dataclasses

    from elephas_tpu.models.transformer import generate

    config = dataclasses.replace(_config(), attention_window=4,
                                 positional="rope")
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64)
    tx = optax.adam(1e-2)
    opt = tx.init(params)
    step = make_train_step(config, tx)
    first = None
    for _ in range(8):
        params, opt, loss = step(params, opt, tokens)
        first = first if first is not None else float(loss)
    assert float(loss) < first
    out = np.asarray(generate(params, tokens[:2, :4], 6, config))
    assert out.shape == (2, 6)
    # greedy continuation equals argmax over the windowed forward
    assert_greedy_continuation(params, tokens[:2, :4], out, config)


def test_repetition_penalty_suppresses_repeats():
    from elephas_tpu.models.transformer import generate

    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (3, 4), 0,
                                config.vocab_size)
    # penalty=1 must be bit-identical to the plain path
    plain = np.asarray(generate(params, prompt, 8, config))
    p1 = np.asarray(generate(params, prompt, 8, config,
                             repetition_penalty=1.0))
    np.testing.assert_array_equal(plain, p1)

    # a huge penalty makes greedy avoid anything seen: all continuations
    # distinct and disjoint from the prompt
    out = np.asarray(generate(params, prompt, 8, config,
                              repetition_penalty=1e6))
    for b in range(3):
        emitted = list(np.asarray(prompt)[b]) + list(out[b])
        assert len(set(out[b])) == 8, out[b]
        assert not (set(out[b]) & set(np.asarray(prompt)[b])), emitted

    import pytest
    with pytest.raises(ValueError):
        generate(params, prompt, 4, config, repetition_penalty=0.5)


def test_sinusoidal_positions_train_and_decode():
    import dataclasses

    from elephas_tpu.models.transformer import init_kv_cache

    config = dataclasses.replace(_config(), positional="sinusoidal")
    params = init_params(config, jax.random.PRNGKey(0))
    assert "pos" not in params["embed"]  # parameter-free
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 10),
                                           0, 64))
    full = np.asarray(FORWARD(params, jnp.asarray(tokens), config))
    # position-sensitive: permuting the sequence changes logits
    perm = np.asarray(tokens)[:, ::-1].copy()
    assert np.abs(np.asarray(FORWARD(params, jnp.asarray(perm), config))
                  [:, -1] - full[:, -1]).max() > 1e-6
    cache = init_kv_cache(config, 2, max_len=10)
    for t in range(10):
        logits, cache = DECODE_STEP(params, cache,
                                    jnp.asarray(tokens[:, t]), t, config)
        np.testing.assert_allclose(np.asarray(logits), full[:, t],
                                   atol=2e-4, rtol=2e-4)
    tx = optax.adam(1e-2)
    opt = tx.init(params)
    step = make_train_step(config, tx)
    first = None
    for _ in range(6):
        params, opt, loss = step(params, opt, jnp.asarray(tokens))
        first = first if first is not None else float(loss)
    assert float(loss) < first


def test_ragged_prompt_generation_matches_per_row():
    """Right-padded ragged prompts: each row's continuation equals an
    individual generate() on its unpadded prompt (greedy oracle)."""
    from elephas_tpu.models.transformer import generate

    config = _config()
    params = init_params(config, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    lens = [3, 6, 4]
    lmax = max(lens)
    prompt = np.zeros((3, lmax), dtype="int32")
    rows = []
    for b, L in enumerate(lens):
        row = rng.integers(4, 64, size=L).astype("int32")
        rows.append(row)
        prompt[b, :L] = row

    out = np.asarray(generate(params, jnp.asarray(prompt), 6, config,
                              prompt_lengths=np.asarray(lens)))
    assert out.shape == (3, 6)
    for b, row in enumerate(rows):
        solo = np.asarray(generate(params, jnp.asarray(row[None, :]), 6,
                                   config))
        np.testing.assert_array_equal(out[b], solo[0])

    # uniform lengths equal the plain path exactly
    uni = np.asarray(generate(params, jnp.asarray(prompt), 6, config,
                              prompt_lengths=np.asarray([lmax] * 3)))
    plain = np.asarray(generate(params, jnp.asarray(prompt), 6, config))
    np.testing.assert_array_equal(uni, plain)

    import pytest
    with pytest.raises(ValueError):
        generate(params, jnp.asarray(prompt), 4, config,
                 prompt_lengths=np.asarray([3, 6]))


def test_alibi_positions_decode_parity_and_extrapolation():
    import dataclasses

    from elephas_tpu.models.transformer import _alibi_slopes, init_kv_cache

    slopes = np.asarray(_alibi_slopes(8))
    np.testing.assert_allclose(slopes[0], 2 ** -1.0, rtol=1e-6)
    np.testing.assert_allclose(slopes[-1], 2 ** -8.0, rtol=1e-6)

    config = dataclasses.replace(_config(), positional="alibi")
    params = init_params(config, jax.random.PRNGKey(0))
    assert "pos" not in params["embed"]
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 10),
                                           0, 64))
    full = np.asarray(FORWARD(params, jnp.asarray(tokens), config))
    # position-sensitive
    base = dataclasses.replace(_config(), positional="sinusoidal")
    cache = init_kv_cache(config, 2, max_len=10)
    for t in range(10):
        logits, cache = DECODE_STEP(params, cache,
                                    jnp.asarray(tokens[:, t]), t, config)
        np.testing.assert_allclose(np.asarray(logits), full[:, t],
                                   atol=2e-4, rtol=2e-4)
    # trains, and runs BEYOND max_seq_len (no positional table bound)
    tx = optax.adam(1e-2)
    opt = tx.init(params)
    step = make_train_step(config, tx)
    first = None
    for _ in range(6):
        params, opt, loss = step(params, opt, jnp.asarray(tokens))
        first = first if first is not None else float(loss)
    assert float(loss) < first
    long_tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 48), 0, 64)
    out = FORWARD(params, long_tokens, config)  # 48 > max_seq_len=32
    assert np.isfinite(np.asarray(out)).all()


def test_generate_logits_processor_constrains_output():
    """A jax-traceable logits hook bounds what generation can pick:
    banning a token set means it never appears (greedy and sampled),
    and a None processor leaves output unchanged."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from elephas_tpu.models.transformer import generate

    config = TransformerConfig(vocab_size=64, num_layers=2, num_heads=4,
                               d_model=32, d_ff=64, max_seq_len=48,
                               dtype=jnp.float32)
    params = init_params(config, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (3, 5), 0, 64)

    banned = jnp.zeros((64,), bool).at[jnp.arange(0, 64, 2)].set(True)

    def ban_even(logits):
        return jnp.where(banned[None, :], -jnp.inf, logits)

    out = np.asarray(generate(params, prompt, 12, config,
                              logits_processor=ban_even))
    assert (out % 2 == 1).all(), out
    sampled = np.asarray(generate(params, prompt, 12, config,
                                  temperature=0.9,
                                  key=jax.random.PRNGKey(2),
                                  logits_processor=ban_even))
    assert (sampled % 2 == 1).all(), sampled
    # ragged path honors the hook too
    ragged = np.asarray(generate(params, prompt, 8, config,
                                 prompt_lengths=np.asarray([5, 3, 4]),
                                 logits_processor=ban_even))
    assert (ragged % 2 == 1).all(), ragged
    # no processor: byte-identical to the default path
    a = np.asarray(generate(params, prompt, 8, config))
    b = np.asarray(generate(params, prompt, 8, config,
                            logits_processor=None))
    np.testing.assert_array_equal(a, b)
