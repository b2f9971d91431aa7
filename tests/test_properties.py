"""Property-based tests (hypothesis): compiler invariants that must hold for
ANY pointwise kernel, not just the paper's six."""

import numpy as np
import pytest

# importorskip aborts collection of this module cleanly when hypothesis is
# absent — a skipif mark cannot guard the module-level @given/@settings uses.
hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.dfg import optimize, trace
from repro.core.fuse import fuse_muladd
from repro.core.ir import _lower_consts
from repro.core.jit import jit_compile
from repro.core.options import CompileOptions
from repro.core.overlay import OverlaySpec
from repro.core.program import compile_program
from repro.core.replicate import plan_replication
from repro.kernels.overlay_exec import ref as exec_ref


# ---- random expression generator (operator AST over k inputs) -------------

def expr_strategy(n_inputs: int, max_depth: int = 4):
    leaf = st.one_of(
        st.integers(0, n_inputs - 1).map(lambda i: ("var", i)),
        st.floats(-4, 4, allow_nan=False).map(lambda c: ("const",
                                                         round(c, 3))))

    def extend(children):
        binop = st.tuples(st.sampled_from(["add", "sub", "mul", "min",
                                           "max"]), children, children)
        unop = st.tuples(st.sampled_from(["neg", "abs"]), children)
        return st.one_of(binop, unop)

    return st.recursive(leaf, extend, max_leaves=12)


def eval_ast(ast, env):
    kind = ast[0]
    if kind == "var":
        return env[ast[1]]
    if kind == "const":
        return np.float32(ast[1])
    if kind in ("neg", "abs"):
        v = eval_ast(ast[1], env)
        return -v if kind == "neg" else np.abs(v)
    a, b = eval_ast(ast[1], env), eval_ast(ast[2], env)
    return {"add": lambda: a + b, "sub": lambda: a - b,
            "mul": lambda: a * b, "min": lambda: np.minimum(a, b),
            "max": lambda: np.maximum(a, b)}[kind]()


def build_trace_fn(ast):
    def tv(node, args):
        kind = node[0]
        if kind == "var":
            return args[node[1]]
        if kind == "const":
            return node[1]
        if kind in ("neg", "abs"):
            v = tv(node[1], args)
            if isinstance(v, (int, float)):
                return -v if kind == "neg" else abs(v)
            return -v if kind == "neg" else abs(v)
        a, b = tv(node[1], args), tv(node[2], args)
        if kind in ("min", "max"):
            if isinstance(a, (int, float)) and isinstance(b, (int, float)):
                return min(a, b) if kind == "min" else max(a, b)
            if isinstance(a, (int, float)):
                a, b = b, a          # commutative: put the TraceVal first
            return a.min(b) if kind == "min" else a.max(b)
        return a + b if kind == "add" else a - b if kind == "sub" else a * b

    return lambda *args: tv(ast, args)


def _has_var(ast):
    if ast[0] == "var":
        return True
    if ast[0] == "const":
        return False
    return any(_has_var(c) for c in ast[1:])


@settings(max_examples=40, deadline=None)
@given(ast=expr_strategy(2), data=st.integers(0, 2 ** 31 - 1))
def test_optimizations_preserve_semantics(ast, data):
    """trace → optimize keeps numerical behaviour (vs direct AST eval)."""
    if not _has_var(ast):
        return
    fn = build_trace_fn(ast)
    try:
        g = optimize(_lower_consts(trace(fn, 2)))
    except TypeError:
        return  # kernel degenerated to a constant after folding
    rng = np.random.default_rng(data)
    xs = [rng.uniform(-2, 2, 32).astype(np.float32) for _ in range(2)]
    want = eval_ast(ast, xs) * np.ones(32, np.float32)
    got = np.asarray(g.evaluate(list(xs))[0]) * np.ones(32, np.float32)
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)


@settings(max_examples=40, deadline=None)
@given(ast=expr_strategy(2), data=st.integers(0, 2 ** 31 - 1))
def test_fusion_preserves_semantics(ast, data):
    if not _has_var(ast):
        return
    fn = build_trace_fn(ast)
    try:
        g = optimize(_lower_consts(trace(fn, 2)))
    except TypeError:
        return
    fused = fuse_muladd(g)
    rng = np.random.default_rng(data)
    xs = [rng.uniform(-2, 2, 16).astype(np.float32) for _ in range(2)]
    a = np.asarray(g.evaluate(list(xs))[0]) * np.ones(16, np.float32)
    b = np.asarray(fused.evaluate(list(xs))[0]) * np.ones(16, np.float32)
    np.testing.assert_allclose(b, a, rtol=1e-3, atol=1e-3)


@settings(max_examples=25, deadline=None)
@given(ast=expr_strategy(2), data=st.integers(0, 2 ** 31 - 1))
def test_program_interpreter_matches_dfg(ast, data):
    """Linear program (executor image) ≡ DFG evaluation for random DFGs."""
    if not _has_var(ast):
        return
    fn = build_trace_fn(ast)
    try:
        g = optimize(_lower_consts(trace(fn, 2)))
    except TypeError:
        return
    prog = compile_program(g)
    rng = np.random.default_rng(data)
    xs = [rng.uniform(-2, 2, 8).astype(np.float32) for _ in range(2)]
    want = [np.asarray(o) * np.ones(8, np.float32)
            for o in g.evaluate(list(xs))]
    got = exec_ref.execute(prog, xs)
    for w, o in zip(want, got):
        np.testing.assert_allclose(o, w, rtol=1e-3, atol=1e-3)


@settings(max_examples=20, deadline=None)
@given(w=st.integers(2, 10), h=st.integers(2, 10),
       kfus=st.integers(1, 8), kio=st.integers(1, 6))
def test_replication_plan_invariants(w, h, kfus, kio):
    """Replication never exceeds resources and is maximal."""
    class FakeFug:
        n_fus = kfus
        n_in = max(1, kio - 1)
        n_out = 1
        n_io = n_in + n_out
    spec = OverlaySpec(width=w, height=h)
    plan = plan_replication(FakeFug(), spec)
    assert plan.fus_used <= spec.n_fus
    assert plan.io_used <= spec.n_io
    if plan.limited_by == "fu":
        assert (plan.replicas + 1) * kfus > spec.n_fus
    if plan.limited_by == "io":
        assert (plan.replicas + 1) * FakeFug.n_io > spec.n_io


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 100))
def test_full_jit_pipeline_random_kernels(seed):
    """End-to-end jit_compile on random polynomials: always routes, always
    evaluates correctly."""
    rng = np.random.default_rng(seed)
    coeffs = rng.uniform(-2, 2, 4).round(2)

    def kern(x):
        return ((coeffs[0] * x + coeffs[1]) * x + coeffs[2]) * x + coeffs[3]

    ck = jit_compile(kern, OverlaySpec(),
                     opts=CompileOptions(n_inputs=1, name=f"rand{seed}",
                                         place_effort=0.2))
    x = np.linspace(-1, 1, 64).astype(np.float32)
    want = ((coeffs[0] * x + coeffs[1]) * x + coeffs[2]) * x + coeffs[3]
    np.testing.assert_allclose(ck.run_reference(x), want, rtol=1e-4,
                               atol=1e-4)
