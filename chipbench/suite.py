"""The paper's six OpenCL benchmark kernels (arXiv:1705.02730, §IV,
Table III) as templates over their numeric constants, with NumPy oracles.

A copy, kept with the benchmark, of the sources and oracles in the
program's ``repro.configs.paper_suite``: the yardstick must not move when
the program's copy does.  ``{cN}`` marks the N-th constant; at the default
constants each source is the paper's kernel.  The oracles take the
constants first and compute in the dtype of their inputs.
"""

from __future__ import annotations

import re
from typing import Callable, Dict, NamedTuple, Sequence, Tuple

import numpy as np


class Template(NamedTuple):
    source: str                        # OpenCL-C with {c0}, {c1}, ...
    defaults: Tuple[float, ...]        # the paper's constants
    n_in: int
    n_out: int
    oracle: Callable                   # oracle(consts, *inputs) -> output


def _cheb(c, x):
    return x * (x * (c[0] * x * x - c[1]) * x + c[2])


def _sgf(c, x, y):
    t = c[0] * x * x + c[1] * x * y - c[2] * y * y + c[3] * x - c[4] * y \
        + c[5]
    return t * x + t * y


def _mib(c, a, b):
    s = a * b + a + b
    t = a * a - b * b + c[0] * s
    return s * t + c[1] * s - c[2] * t


def _qsp(c, t, p0, p1, p2):
    a = p0 - c[0] * p1 + p2
    b = c[1] * p1 - c[2] * p0
    return (a * t + b) * t + p0 + p1 - p0


def _poly1(c, x):
    return ((c[0] * x + c[1]) * x - c[2]) * x + c[3]


def _poly2(c, x):
    x2 = x * x
    x4 = x2 * x2
    return c[0] * x4 * x2 - c[1] * x4 + c[2] * x2 - c[3] + c[4] * x4 * x \
        - x2 * x


TEMPLATES: Dict[str, Template] = {
    "chebyshev": Template("""
__kernel void chebyshev(__global float *A, __global float *B) {
  int idx = get_global_id(0);
  float x = A[idx];
  B[idx] = (x*(x*({c0}*x*x-{c1})*x+{c2}));
}
""", (16.0, 20.0, 5.0), 1, 1, _cheb),
    "sgfilter": Template("""
__kernel void sgfilter(__global float *X, __global float *Y,
                       __global float *Out) {
  int idx = get_global_id(0);
  float x = X[idx];
  float y = Y[idx];
  float t = {c0}*x*x + {c1}*x*y - {c2}*y*y + {c3}*x - {c4}*y + {c5};
  Out[idx] = t * x + t * y;
}
""", (2.0, 4.0, 59.0, 3.0, 7.0, 1.0), 2, 1, _sgf),
    "mibench": Template("""
__kernel void mibench(__global float *A, __global float *B,
                      __global float *C) {
  int idx = get_global_id(0);
  float a = A[idx];
  float b = B[idx];
  float s = a*b + a + b;
  float t = a*a - b*b + {c0}*s;
  C[idx] = s*t + {c1}*s - {c2}*t;
}
""", (2.0, 3.0, 5.0), 2, 1, _mib),
    "qspline": Template("""
__kernel void qspline(__global float *T, __global float *P0,
                      __global float *P1, __global float *P2,
                      __global float *Q) {
  int idx = get_global_id(0);
  float t = T[idx];
  float p0 = P0[idx];
  float p1 = P1[idx];
  float p2 = P2[idx];
  float a = p0 - {c0}*p1 + p2;
  float b = {c1}*p1 - {c2}*p0;
  Q[idx] = (a*t + b)*t + p0 + p1 - p0;
}
""", (2.0, 2.0, 2.0), 4, 1, _qsp),
    "poly1": Template("""
__kernel void poly1(__global float *X, __global float *Y) {
  int idx = get_global_id(0);
  float x = X[idx];
  Y[idx] = (({c0}*x + {c1})*x - {c2})*x + {c3};
}
""", (3.0, 5.0, 7.0, 9.0), 1, 1, _poly1),
    "poly2": Template("""
__kernel void poly2(__global float *X, __global float *Y) {
  int idx = get_global_id(0);
  float x = X[idx];
  float x2 = x*x;
  float x4 = x2*x2;
  Y[idx] = {c0}*x4*x2 - {c1}*x4 + {c2}*x2 - {c3} + {c4}*x4*x - x2*x;
}
""", (2.0, 5.0, 4.0, 11.0, 3.0), 1, 1, _poly2),
}


def _literal(c: float) -> str:
    return repr(float(np.float32(c))) + "f"


def source(name: str, consts: Sequence[float]) -> str:
    """The kernel's OpenCL-C text with ``consts`` substituted."""
    return re.sub(r"\{c(\d+)\}", lambda m: _literal(consts[int(m[1])]),
                  TEMPLATES[name].source)


def draw_constants(name: str, rng: np.random.Generator) -> Tuple[float, ...]:
    """Constants for a new variant of ``name``: magnitudes log-uniform in
    [0.25, 64] with random signs, rounded to float32.  They keep clear of
    0 and +-1, which the frontend's algebraic simplification would fold, so
    every variant keeps its template's dataflow graph and executor
    signature."""
    n = len(TEMPLATES[name].defaults)
    mag = np.exp(rng.uniform(np.log(0.25), np.log(64.0), n))
    sign = rng.choice((-1.0, 1.0), n)
    return tuple(float(np.float32(v)) for v in mag * sign)


def oracle(name: str, consts: Sequence[float], inputs, dtype=np.float64):
    """The kernel's output over ``inputs``, computed in ``dtype``."""
    c = [dtype(v) for v in consts]
    return TEMPLATES[name].oracle(c, *[np.asarray(x).astype(dtype)
                                       for x in inputs])
