"""The one backend decision (``repro.kernels.interpret_mode``): on the CPU
backend Pallas kernels interpret and the Session computes with the NumPy
reference; a kernel forced to compile on the CPU raises instead of
falling back; a Session built with defaults still matches the paper
suite's oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_suite import BENCHMARKS
from repro.core.jit import CompiledKernel
from repro.core.options import CompileOptions
from repro.core.overlay import OverlaySpec
from repro.core.runtime import Device
from repro.core.session import Session
from repro.kernels import interpret_mode
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.overlay_exec.kernel import overlay_execute
from repro.kernels.rmsnorm.kernel import rmsnorm

SPEC = OverlaySpec(width=8, height=8, dsp_per_fu=2)


def test_cpu_backend_interprets_unless_told():
    assert jax.default_backend() == "cpu"
    assert interpret_mode() is True
    assert interpret_mode(True) is True
    assert interpret_mode(False) is False


def _overlay(interpret):
    # one NOP writing the immediate into the output register
    instrs = jnp.asarray([0, 1, 0, 0, 0, 0], jnp.int32)
    return overlay_execute(instrs, jnp.ones((1,), jnp.float32),
                           jnp.zeros((1, 1024), jnp.float32),
                           n_out=1, n_instr=1, n_regs=2,
                           interpret=interpret)


def _flash(interpret):
    q = jnp.ones((1, 1, 128, 64), jnp.float32)
    return flash_attention(q, q, q, interpret=interpret)


def _rmsnorm(interpret):
    return rmsnorm(jnp.ones((8, 128)), jnp.ones((128,)), interpret=interpret)


@pytest.mark.parametrize("call", [_overlay, _flash, _rmsnorm],
                         ids=["overlay_exec", "flash_attention", "rmsnorm"])
def test_forced_compile_raises_on_cpu(call):
    assert np.all(np.isfinite(np.asarray(call(None))))
    with pytest.raises(ValueError, match="interpret mode"):
        call(False)


@pytest.mark.parametrize("forced", [False, True], ids=["default", "forced"])
@pytest.mark.parametrize("name", sorted(BENCHMARKS))
def test_session_matches_paper_suite_oracles(name, forced, monkeypatch):
    """Default on the CPU: the NumPy reference runs, never the executor.
    Forced (the runtime told it is on an accelerator): the executor runs,
    still interpreted, and agrees as well."""
    if forced:
        import repro.core.runtime
        monkeypatch.setattr(repro.core.runtime, "interpret_mode",
                            lambda interpret=None: False)
    calls = []
    run_overlay = CompiledKernel.run_overlay
    monkeypatch.setattr(CompiledKernel, "run_overlay",
                        lambda self, *a, **kw: calls.append(1) or
                        run_overlay(self, *a, **kw))
    src, _, oracle = BENCHMARKS[name]
    rng = np.random.default_rng(0)
    with Session([Device("ovl0", SPEC), Device("ovl1", SPEC)]) as sess:
        fut = sess.compile(src, CompileOptions(max_replicas=2))
        xs = [rng.uniform(-1, 1, 2048).astype(np.float32)
              for _ in fut.result().compiled.dfg.inputs]
        got = sess.enqueue(fut, *xs).wait()[0].read()
    np.testing.assert_allclose(got, oracle(*xs), rtol=1e-4, atol=1e-4)
    assert len(calls) == (1 if forced else 0)
