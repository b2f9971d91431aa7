"""The main path's Pallas kernels, compiled at real sizes by the TPU
compiler for one chip of a described v5e (nothing runs).  Each compile
must contain the Mosaic kernel (``tpu_custom_call``): a kernel the chip's
compiler refuses fails here, at no chip time.  The dense serve step is
compiled too, at the decode cells' widths, for what only the TPU
compiler's buffer assignment shows: whether the donated KV cache is
updated in place.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""

import os

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.configs.paper_suite import BENCHMARKS
from repro.configs.registry import ALL_ARCHS
from repro.core.jit import jit_compile
from repro.core.options import CompileOptions
from repro.core.overlay import OverlaySpec
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.overlay_exec import ops
from repro.kernels.overlay_exec.kernel import overlay_execute
from repro.kernels.rmsnorm.kernel import rmsnorm
from repro.models.registry import build_model
from repro.train.step import make_serve_step

N_ITEMS = 1 << 24          # chip_smoke.py's work-items per overlay launch


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # the compiler would otherwise write its logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_overlay_executor_longest_suite_program(one_chip):
    spec = OverlaySpec(width=8, height=8, dsp_per_fu=2)
    progs = [jit_compile(src, spec, opts=CompileOptions(max_replicas=1)
                         ).program for src, _, _ in BENCHMARKS.values()]
    longest = max(progs, key=lambda p: p.n_instr)
    # pad to the suite's widest signature, as a swap between any two
    # suite kernels would
    pad_to = max(ops.signature(p)[0] for p in progs)
    pad_regs = max(ops.signature(p)[1] for p in progs)
    instrs, imms, n_regs, n_out = ops.build_image(longest, pad_to=pad_to,
                                                  pad_regs=pad_regs)
    n_in = len(longest.in_slots)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = overlay_execute.lower(
        sds((instrs.size,), jnp.int32), sds(imms.shape, jnp.float32),
        *[sds((1, N_ITEMS), jnp.float32)] * n_in, n_out=n_out,
        n_instr=pad_to, n_regs=n_regs,
        block=ops._pick_block(N_ITEMS, n_regs, n_in, n_out),
        interpret=False).compile()
    _assert_kernel(compiled)


def test_flash_attention_yi_6b_prefill(one_chip):
    # yi-6b: 32 query heads, 4 KV heads, head dim 128, 2048-token prefill
    def sds(h):
        return jax.ShapeDtypeStruct((1, h, 2048, 128), jnp.bfloat16,
                                    sharding=one_chip)
    compiled = flash_attention.lower(sds(32), sds(4), sds(4),
                                     interpret=False).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("rows", [8192, 4], ids=["prefill", "decode"])
def test_rmsnorm_d4096_bf16(one_chip, rows):
    compiled = rmsnorm.lower(
        jax.ShapeDtypeStruct((rows, 4096), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((4096,), jnp.bfloat16, sharding=one_chip),
        interpret=False).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("arch,chips", [("yi-6b", 1), ("nemotron-4-15b", 4)])
def test_dense_serve_step_updates_the_donated_cache_in_place(topo, arch,
                                                             chips):
    """The decode cells' serve step (batch 64, cache 512, the cache
    donated; nemotron-4-15b on a (data=1, model=4) mesh) at the cells'
    widths and 4 layers: the compiled step copies no whole stacked cache,
    and its temporaries stay below one layer's key slab, so it holds no
    second cache.  The CPU backend keeps whole-cache copies whether the
    cache rows are written in place or not, so only a TPU compile can
    check this."""
    cfg = dataclasses.replace(ALL_ARCHS[arch], n_layers=4)
    model = build_model(cfg)
    mesh = Mesh(np.array(topo.devices[:chips]).reshape(1, chips),
                ("data", "model"))

    def abstract(shapes, specs):
        return jax.tree.map(
            lambda s, p: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(mesh, p)),
            shapes, specs)

    params = abstract(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                      model.param_specs())
    cache = abstract(jax.eval_shape(lambda: model.init_cache(64, 512)),
                     model.cache_specs(model_axis=chips))
    rep = NamedSharding(mesh, P())
    compiled = jax.jit(
        make_serve_step(model), donate_argnums=(1,),
        out_shardings=(rep, jax.tree.map(lambda s: s.sharding, cache))).lower(
        params, cache, jax.ShapeDtypeStruct((64, 1), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)).compile()

    k = cache["k"]
    local = k.sharding.shard_shape(k.shape)      # (L, B, Hkv/chips, S, hd)
    stacked = "bf16[" + ",".join(map(str, local)) + "]"
    copies = [line for line in compiled.as_text().splitlines()
              if re.search(r"= " + re.escape(stacked) + r"\{[^}]*\} copy\(",
                           line)]
    assert not copies, copies
    slab = int(np.prod(local[1:])) * k.dtype.itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < slab
