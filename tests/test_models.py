"""Per-architecture smoke tests (reduced configs): one forward/train step on
CPU asserting shapes + finiteness, plus decode↔train consistency."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ALL_ARCHS, reduced_config
from repro.models.registry import build_model
from repro.optim.adamw import AdamWConfig
from repro.train.step import init_state, make_train_step

KEY = jax.random.PRNGKey(0)

# eager model.init dominates this module's wall time (several seconds for
# the deeper archs); build each reduced model + state once and share it —
# tests only read params / run pure steps, never mutate in place
_CACHE = {}


def _model_and_state(arch):
    if arch not in _CACHE:
        cfg = reduced_config(ALL_ARCHS[arch])
        model = build_model(cfg, remat_policy="none")
        _CACHE[arch] = (cfg, model, init_state(model, KEY))
    return _CACHE[arch]


def _batch(cfg, b=2, s=32):
    toks = jax.random.randint(KEY, (b, s), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks}
    if cfg.frontend == "vision":
        batch["input_embeds"] = jnp.zeros((b, s // 8, cfg.d_model),
                                          jnp.float32)
    if cfg.frontend == "audio":
        batch["input_embeds"] = jnp.zeros((b, s, cfg.d_model), jnp.float32)
        batch["tokens"] = batch["labels"] = toks[:, :max(8, s // 4)]
    return batch


@pytest.mark.parametrize("arch", sorted(ALL_ARCHS))
def test_smoke_forward_and_train_step(arch):
    cfg, model, state = _model_and_state(arch)
    batch = _batch(cfg)
    logits = model.forward_train(state["params"], batch["tokens"],
                                 batch.get("input_embeds"))
    assert logits.shape == (*batch["tokens"].shape, cfg.vocab_padded)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))

    step = jax.jit(make_train_step(model, AdamWConfig(warmup_steps=1,
                                                      total_steps=10)))
    state2, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    # params actually changed (some leaf; small leaves may be bf16-invariant)
    changed = any(
        not np.allclose(np.asarray(b, np.float32), np.asarray(a, np.float32))
        for b, a in zip(jax.tree.leaves(state["params"]),
                        jax.tree.leaves(state2["params"])))
    assert changed


@pytest.mark.parametrize("arch", sorted(ALL_ARCHS))
def test_smoke_decode_step(arch):
    cfg, model, state = _model_and_state(arch)
    params = state["params"]
    b, cache_len = 2, 48
    cache = model.init_cache(b, cache_len)
    tok = jnp.zeros((b, 1), jnp.int32)
    logits, cache2 = model.forward_decode(params, cache, tok, jnp.int32(0))
    assert logits.shape == (b, 1, cfg.vocab_padded)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    # cache structure is preserved
    assert jax.tree.structure(cache) == jax.tree.structure(cache2)


@pytest.mark.parametrize("arch", [
    "llama3-8b",
    pytest.param("qwen3-14b", marks=pytest.mark.slow),
    pytest.param("mixtral-8x22b", marks=pytest.mark.slow),
])
def test_decode_matches_train_forward(arch):
    """Sequential decode must reproduce the training forward logits.

    For MoE the expert capacity is raised so no token drops: capacity is
    computed per dispatch group, which differs between full-sequence train
    (G=B·S) and per-token decode (G=B) — with drops, the two modes are
    legitimately different."""
    import dataclasses
    if ALL_ARCHS[arch].family == "moe":
        cfg = dataclasses.replace(reduced_config(ALL_ARCHS[arch]),
                                  capacity_factor=16.0)
        model = build_model(cfg, remat_policy="none")
        params = model.init(KEY)
    else:
        cfg, model, state = _model_and_state(arch)
        params = state["params"]
    b, s = 1, 12
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, cfg.vocab)
    want = model.forward_train(params, toks)        # (b, s, V)

    cache = model.init_cache(b, s, dtype=jnp.float32)
    outs = []
    for i in range(s):
        logits, cache = model.forward_decode(params, cache, toks[:, i:i + 1],
                                             jnp.int32(i))
        outs.append(logits[:, 0])
    got = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("cur_pos", [0, 5, 11], ids=["first", "middle",
                                                      "last"])
@pytest.mark.parametrize("arch,window", [("yi-6b", None), ("yi-6b", 4),
                                         ("qwen3-14b", None)],
                         ids=["plain", "window", "qk_norm"])
def test_dense_decode_matches_write_then_attend(arch, window, cur_pos):
    """The dense decode reads each layer's slab before it writes the new
    row, and attends to the row beside it: against the formulation that
    writes first and attends over the updated slab, the cache it writes is
    bitwise the same and the logits differ only by float32 rounding."""
    import dataclasses
    from reference_decode import forward_decode as write_then_attend
    cfg = dataclasses.replace(reduced_config(ALL_ARCHS[arch]),
                              dtype=jnp.float32, window=window)
    assert cfg.qk_norm == (arch == "qwen3-14b")
    model = build_model(cfg, remat_policy="none")
    params = model.init(KEY)
    b, s = 2, 12
    kk, kv, kt = jax.random.split(jax.random.PRNGKey(4), 3)
    shape = model.init_cache(b, s)["k"].shape
    # every position holds a value, so a position wrongly attended to or
    # wrongly left out moves the logits
    cache = {"k": jax.random.normal(kk, shape).astype(jnp.bfloat16),
             "v": jax.random.normal(kv, shape).astype(jnp.bfloat16)}
    tok = jax.random.randint(kt, (b, 1), 0, cfg.vocab)
    pos = jnp.int32(cur_pos)
    got, got_cache = jax.jit(model.forward_decode)(params, cache, tok, pos)
    want, want_cache = jax.jit(functools.partial(write_then_attend, model))(
        params, cache, tok, pos)
    for name in ("k", "v"):
        assert got_cache[name].dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(got_cache[name]).view(np.uint16),
            np.asarray(want_cache[name]).view(np.uint16))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_mamba_decode_matches_train_forward():
    """SSD chunked scan (train) ≡ stepwise recurrence (decode)."""
    cfg, model, state = _model_and_state("mamba2-370m")
    params = state["params"]
    b, s = 1, 16     # multiple of reduced ssm_chunk=8
    toks = jax.random.randint(jax.random.PRNGKey(2), (b, s), 0, cfg.vocab)
    want = model.forward_train(params, toks)

    cache = model.init_cache(b, s)
    outs = []
    for i in range(s):
        logits, cache = model.forward_decode(params, cache, toks[:, i:i + 1],
                                             jnp.int32(i))
        outs.append(logits[:, 0])
    got = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_sliding_window_limits_context():
    """With SWA, a token far outside the window cannot influence logits.

    capacity_factor is raised so no token is dropped: MoE capacity ranking
    couples tokens globally, which would otherwise leak position-0 changes
    forward through drop decisions."""
    import dataclasses
    cfg = dataclasses.replace(
        reduced_config(ALL_ARCHS["mixtral-8x22b"]),   # window=16
        capacity_factor=8.0)
    model = build_model(cfg, remat_policy="none")
    params = model.init(KEY)
    s = 40
    toks = jax.random.randint(jax.random.PRNGKey(3), (1, s), 0, cfg.vocab)
    toks2 = toks.at[:, 0].set((toks[:, 0] + 1) % cfg.vocab)
    l1 = model.forward_train(params, toks)
    l2 = model.forward_train(params, toks2)
    # position 0 differs → early logits differ...
    assert not np.allclose(np.asarray(l1[:, 0]), np.asarray(l2[:, 0]))
    # ...but the last position is > window away in every layer's receptive
    # field only if depth*window < distance; with 2 layers * 16 = 32 < 39
    np.testing.assert_allclose(np.asarray(l1[:, -1], np.float32),
                               np.asarray(l2[:, -1], np.float32),
                               rtol=1e-4, atol=1e-4)


def test_moe_capacity_drops_tokens_gracefully():
    cfg = reduced_config(ALL_ARCHS["qwen3-moe-235b-a22b"])
    import dataclasses
    tight = dataclasses.replace(cfg, capacity_factor=0.5)
    model = build_model(tight, remat_policy="none")
    params = model.init(KEY)
    toks = jax.random.randint(KEY, (2, 16), 0, tight.vocab)
    logits = model.forward_train(params, toks)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))


def test_param_count_sanity():
    # full-size configs should land near their nameplate sizes
    cfg = ALL_ARCHS["llama3-8b"]
    n = cfg.param_count()
    assert 7e9 < n < 10e9, n
    moe = ALL_ARCHS["mixtral-8x22b"]
    assert 120e9 < moe.param_count() < 180e9
    assert 30e9 < moe.active_param_count() < 50e9
