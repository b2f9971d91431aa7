"""The operations and bytes behind ``exec_roofline`` and ``decode_mfu``."""

import json
from pathlib import Path

import pytest

from chipbench import counts, suite
from chipbench.runners import dense_decode

CONFIGS = Path(__file__).resolve().parent / "configs"


def _cfg(name):
    return dense_decode.shape_config(json.loads(
        (CONFIGS / f"{name}.json").read_text()))


@pytest.mark.parametrize("name,per_item", [
    ("chebyshev", 8), ("sgfilter", 12), ("mibench", 12), ("qspline", 20),
    ("poly1", 8), ("poly2", 8)])
def test_overlay_bytes_follow_the_kernels_dfg(name, per_item):
    from repro.core.jit import jit_compile
    from repro.core.options import CompileOptions
    from repro.core.overlay import OverlaySpec
    t = suite.TEMPLATES[name]
    ck = jit_compile(suite.source(name, t.defaults),
                     OverlaySpec(width=8, height=8, dsp_per_fu=2),
                     opts=CompileOptions(max_replicas=6))
    assert (len(ck.dfg.inputs), len(ck.program.out_slots)) == (t.n_in,
                                                              t.n_out)
    assert counts.overlay_bytes(t.n_in, t.n_out, 1 << 20) == \
        per_item << 20


def test_yi6b_weight_bytes():
    assert counts.dense_weight_bytes(_cfg("yi-6b")) == 12_122_071_040


def test_nemotron_weight_bytes():
    # 15.63e9 parameters in bfloat16
    assert counts.dense_weight_bytes(_cfg("nemotron-4-15b")) == \
        pytest.approx(2 * 15.63e9, rel=1e-3)


def test_decode_step_counts():
    c = _cfg("yi-6b")
    one = counts.dense_decode_step(c, batch=64, kv_len=256)
    # every weight once but the embedding table, of which 64 rows are read
    weights = 12_122_071_040 - 64000 * 4096 * 2 + 64 * 4096 * 2
    kv = 2 * 32 * 64 * 4 * 128 * 257 * 2
    assert one["bytes"] == weights + kv
    proj = 32 * (4096 * 128 * (32 + 8) + 32 * 128 * 4096 + 3 * 4096 * 11008)
    assert one["flops"] == 2 * 64 * (proj + 4096 * 64000) \
        + 4 * 32 * 64 * 32 * 128 * 256
    four = counts.dense_decode_step(c, batch=64, kv_len=256, chips=4)
    assert four["bytes"] == one["bytes"] / 4
    peaks = dict(flops_bf16=197e12, hbm_bw=819e9)
    # decode at batch 64 is bound by the bytes
    assert counts.least_seconds(one["flops"], one["bytes"], peaks) == \
        one["bytes"] / 819e9
