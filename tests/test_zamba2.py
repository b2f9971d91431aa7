"""zamba2 against the benchmark's plain reference (``chipbench/
reference_zamba2.py``), on the CPU at the configuration file's ``cpu.tiny``
sizes (2 groups, 2 shared blocks at 4 sites, so each block and its adapters
run twice), with seeded random weights.

The program runs in float32 here, as the reference does, so what is left
between them is the order of float32 sums: the chunked SSD against the
per-position scan, and XLA's order of the matrix products.  The tolerance
is on the widest logit difference over the widest reference logit, at
every position.  Each planted fault (blocks A and B swapped, the concat
dropped, a residual inside the shared block, one group for B and C, the
state rounded to bfloat16 each step) fails it.
"""

import dataclasses
import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference_zamba2
from chipbench.runners import hybrid_decode
from repro.configs.registry import ALL_ARCHS
from repro.models import mamba2, zamba2
from repro.models.registry import build_model
from repro.train.step import make_serve_step

ROOT = Path(__file__).resolve().parent.parent
T = 16                       # two SSD chunks of cpu.tiny's chunk_size 8
# float32 against float32: decode and the train forward agree with the
# reference to 3.3e-6 and 3.6e-6 of the logits' scale; 1e-4 leaves thirty
# times that for another summation order, and lies 24 times below the
# smallest planted fault (the state rounded to bfloat16: 2.4e-3; the
# others 0.64 to 1.19)
TOL = 1e-4


@functools.lru_cache(maxsize=None)
def _setup():
    c = json.loads((ROOT / "chipbench/configs/zamba2-7b.json").read_text())
    c.update(c["cpu"]["tiny"])
    cfg = dataclasses.replace(hybrid_decode.arch_config(c),
                              dtype=jnp.float32)
    model = build_model(cfg, remat_policy="none")
    params = hybrid_decode.draw_params(
        model, c, jax.random.PRNGKey(7),
        jax.sharding.SingleDeviceSharding(jax.devices()[0]))
    toks = jax.random.randint(jax.random.PRNGKey(8), (2, T), 0,
                              c["vocab_size"])
    ref = np.asarray(reference_zamba2.logits(c, params, toks))
    return c, model, params, toks, ref


def _err(got, ref):
    return float(np.max(np.abs(np.asarray(got)[..., :ref.shape[-1]] - ref))
                 / np.max(np.abs(ref)))


def _decode(model, params, toks, round_state=False):
    """Feed ``toks`` one per step through the serve step."""
    step = jax.jit(make_serve_step(model))
    cache = model.init_cache(toks.shape[0], T)
    out = []
    for i in range(T):
        logits, cache = step(params, cache, toks[:, i:i + 1], jnp.int32(i))
        if round_state:
            cache["state"] = [s.astype(jnp.bfloat16).astype(jnp.float32)
                              for s in cache["state"]]
        out.append(logits)
    return jnp.stack(out, axis=1)


def test_decode_agrees_with_the_reference_at_every_position():
    c, model, params, toks, ref = _setup()
    assert _err(_decode(model, params, toks), ref) < TOL


def test_train_forward_agrees_with_the_reference():
    c, model, params, toks, ref = _setup()
    assert _err(jax.jit(model.forward_train)(params, toks), ref) < TOL


def _swap_blocks(monkeypatch, params):
    return dict(params, blocks=params["blocks"][::-1])


def _drop_concat(monkeypatch, params):
    block = zamba2.shared_block

    def no_emb(bp, site, x, emb, cfg, **kw):
        return block(bp, site, x, jnp.zeros_like(emb), cfg, **kw)
    monkeypatch.setattr(zamba2, "shared_block", no_emb)
    return params


def _residual_inside(monkeypatch, params):
    block = zamba2.shared_block

    def with_residual(bp, site, x, emb, cfg, **kw):
        t, new = block(bp, site, x, emb, cfg, **kw)
        return t + x @ site["linear"], new        # linear(x + out)
    monkeypatch.setattr(zamba2, "shared_block", with_residual)
    return params


def _one_group(monkeypatch, params):
    split = mamba2.split_xbc

    def first_group(xBC, cfg):
        xs, Bm, Cm = split(xBC, cfg)
        return xs, jnp.broadcast_to(Bm[..., :1, :], Bm.shape), \
            jnp.broadcast_to(Cm[..., :1, :], Cm.shape)
    monkeypatch.setattr(mamba2, "split_xbc", first_group)
    return params


@pytest.mark.parametrize("fault", [_swap_blocks, _drop_concat,
                                   _residual_inside, _one_group])
def test_planted_fault_fails_the_tolerance(monkeypatch, fault):
    c, model, params, toks, ref = _setup()
    broken = fault(monkeypatch, params)
    assert _err(_decode(model, broken, toks), ref) > TOL
    assert _err(jax.jit(model.forward_train)(broken, toks), ref) > TOL


def test_state_rounded_to_bfloat16_fails_the_tolerance():
    c, model, params, toks, ref = _setup()
    assert _err(_decode(model, params, toks, round_state=True), ref) > TOL


def test_published_model_builds_with_its_sites_and_blocks():
    """configs/zamba2_7b.py is the published model: 81 layers, sites 6 to
    77 using two blocks in turn, 13 adapters and linears, 7.35 B
    parameters; shapes only, nothing is allocated."""
    cfg = ALL_ARCHS["zamba2-7b"]
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert len(shapes["layers"]) == 81 and len(shapes["blocks"]) == 2
    assert len(shapes["sites"]) == 13
    assert cfg.hybrid_layer_ids[0] == 6 and cfg.hybrid_layer_ids[-1] == 77
    assert shapes["blocks"][0]["attn"]["wq"].shape == (7168, 7168)
    assert shapes["blocks"][0]["attn"]["wo"].shape == (7168, 3584)
    assert shapes["sites"][0]["adapter"]["lora_b"].shape == (128, 28672)
    assert shapes["layers"][0]["mamba"]["conv_b"].shape == (7424,)
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert n == cfg.param_count()
    assert n == pytest.approx(7.35e9, rel=5e-3)


def test_serve_step_names_its_scopes():
    """The compiled serve step's HLO carries the scopes the benchmark's
    readers look for."""
    c, model, params, toks, ref = _setup()
    cache = model.init_cache(2, T)
    text = jax.jit(make_serve_step(model)).lower(
        params, cache, toks[:, :1], jnp.int32(0)).compile().as_text()
    for name in ("mamba_layer", "ssm_update", "shared_block"):
        assert f"/{name}/" in text, name


@pytest.mark.parametrize("cur_pos", [0, 7, T - 1])
def test_site_attention_stays_write_then_attend(cur_pos):
    """A site's keys and values are buffers of their own, outside the dense
    layer scan: ``attention_decode`` still writes the new row first and
    attends over the updated buffer, bitwise as the formulation the dense
    decode left (``reference_decode``), at the cell's bfloat16."""
    from reference_decode import attention_decode as write_then_attend
    from repro.models import layers as L
    c, model, params, toks, ref = _setup()
    cfg = dataclasses.replace(model.cfg, dtype=jnp.bfloat16)
    ka, kx, kk, kv = jax.random.split(jax.random.PRNGKey(9), 4)
    p = L.init_attention(ka, cfg, d_in=2 * cfg.d_model)
    x = jax.random.normal(kx, (2, 1, 2 * cfg.d_model)).astype(jnp.bfloat16)
    shape = model.init_cache(2, T)["k"][0].shape
    ck = jax.random.normal(kk, shape).astype(jnp.bfloat16)
    cv = jax.random.normal(kv, shape).astype(jnp.bfloat16)
    args = (p, x, ck, cv, jnp.int32(cur_pos))
    got = jax.jit(L.attention_decode, static_argnums=5)(*args, cfg)
    want = jax.jit(write_then_attend, static_argnums=5)(*args, cfg)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(g).view(np.uint16),
                                      np.asarray(w).view(np.uint16))
