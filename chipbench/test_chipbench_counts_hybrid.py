"""The parameters, operations and bytes behind ``decode_mfu`` and
``mamba_roofline.zamba2`` for the zamba2 cell, and the scope reduction
that ``mamba_ms.zamba2`` and ``shared_block_ms.zamba2`` read."""

import json
from pathlib import Path

import pytest

from chipbench import counts_hybrid, scopes, tracefile
from chipbench.runners import hybrid_decode
from chipbench.tracefile import Event

CFG = json.loads((Path(__file__).resolve().parent / "configs" /
                  "zamba2-7b.json").read_text())


def _published():
    """The whole model the cut comes from (the file's deployment)."""
    dep = CFG["deployment"]
    ids = dep["published_hybrid_layer_ids"]
    n = dep["published_num_hidden_layers"]
    return dict(CFG, num_hidden_layers=n, hybrid_layer_ids=ids,
                layers_block_type=["hybrid" if i in ids else "mamba"
                                   for i in range(n)])


def test_parameters_of_the_published_model_and_of_the_cut():
    assert counts_hybrid.param_count(_published()) == \
        pytest.approx(7.35e9, rel=5e-3)
    # 27 Mamba layers, both blocks, 4 sites, the tied embedding
    assert counts_hybrid.param_count(CFG) == pytest.approx(2.968e9,
                                                           rel=5e-4)


@pytest.mark.parametrize("which", ["cut", "published"])
def test_program_param_count_agrees_with_the_counts(which):
    c = CFG if which == "cut" else _published()
    assert hybrid_decode.arch_config(c).param_count() == \
        counts_hybrid.param_count(c)


def test_scopes_sum_to_the_step():
    s = counts_hybrid.decode_step(CFG, batch=32, kv_len=256)
    assert set(s["scopes"]) == {"mamba_layer", "shared_block", "lm"}
    for k in ("flops", "bytes"):
        assert sum(v[k] for v in s["scopes"].values()) == s[k]
    # about 11.5 GB a step, of which the Mamba layers carry about 65 %
    assert s["bytes"] == pytest.approx(11.5e9, rel=0.02)
    assert s["scopes"]["mamba_layer"]["bytes"] / s["bytes"] == \
        pytest.approx(0.65, abs=0.02)
    # float32 SSM state of 27 layers read and written: 3.17 GB
    state = 2 * 27 * 32 * 112 * 64 * 64 * 4
    assert state == pytest.approx(3.17e9, rel=2e-3)
    # keys and values move with the positions held, nothing else does
    s2 = counts_hybrid.decode_step(CFG, batch=32, kv_len=257)
    assert s2["bytes"] - s["bytes"] == 4 * 2 * 32 * 32 * 224 * 2
    assert s2["scopes"]["mamba_layer"] == s["scopes"]["mamba_layer"]


def test_scope_time_per_step():
    """Leaf operations whose scope holds the name, inside the window, over
    the serve steps; a name that is only part of a scope's word does not
    count."""
    D0, OPS, MODS = "/device:TPU:0", tracefile.OPS_LINE, \
        tracefile.MODULES_LINE
    ev = [Event("/host:CPU", "python", tracefile.WINDOW, 0, 100),
          Event(D0, MODS, "jit_serve_step(1)", 0, 40),
          Event(D0, MODS, "jit_serve_step(2)", 50, 90),
          Event(D0, OPS, "fusion.1", 0, 10, "jit(serve_step)/mamba_layer/dot"),
          Event(D0, OPS, "fusion.2", 10, 14,
                "jit(serve_step)/mamba_layer/ssm_update/mul"),
          Event(D0, OPS, "fusion.3", 14, 30,
                "jit(serve_step)/shared_block/dot"),
          Event(D0, OPS, "fusion.4", 50, 56, "jit(serve_step)/mamba_layers"),
          Event(D0, OPS, "fusion.5", 60, 70, "jit(serve_step)/mamba_layer"),
          Event(D0, OPS, "fusion.6", 95, 120, "jit(serve_step)/mamba_layer")]
    assert scopes.scope_ns(ev, D0, "mamba_layer", {}) == 10 + 4 + 10
    assert scopes.scope_ns(ev, D0, "ssm_update", {}) == 4
    assert scopes.ms_per_step(ev, "shared_block", {}) == \
        pytest.approx(16e-6 / 2)
    assert scopes.ms_per_step(ev, "attention", {}) is None
    assert scopes.ms_per_step([], "mamba_layer", {}) is None


HLO = """HloModule jit_serve_step, entry_computation_layout={...}

%fused_computation (param_0: bf16[8,128]) -> bf16[8,128] {
  %param_0 = bf16[8,128]{1,0:T(8,128)(2,1)} parameter(0)
  ROOT %mul.1 = bf16[8,128]{1,0:T(8,128)(2,1)} multiply(%param_0, %param_0), metadata={op_name="jit(serve_step)/mamba_layer/mul"}
}

ENTRY %main.9 (w: bf16[8,128], c: bf16[4,128]) -> (bf16[8,128], bf16[4,128]) {
  %w = bf16[8,128]{1,0:T(8,128)(2,1)} parameter(0)
  %c = bf16[4,128]{1,0:T(8,128)(2,1)} parameter(1)
  %copy-start.3 = (bf16[8,128]{1,0:T(8,128)(2,1)S(1)}, bf16[8,128]{1,0:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(%w), cross_program_prefetch_index=0
  %copy-done.3 = bf16[8,128]{1,0:T(8,128)(2,1)S(1)} copy-done(%copy-start.3)
  %bitcast.2 = bf16[8,128]{1,0:T(8,128)(2,1)S(1)} bitcast(%copy-done.3)
  %fusion.1 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(%bitcast.2), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(serve_step)/mamba_layer/mul" stack_frame_id=3}, backend_config={"flag_configs":[]}
  %slice-start.7 = ((bf16[4,128]{1,0:T(8,128)(2,1)}), bf16[1,128]{1,0:T(8,128)(2,1)S(1)}, s32[]{:S(2)}) slice-start(%c), slice={[0:1], [0:128]}
  %slice-done.7 = bf16[1,128]{1,0:T(8,128)(2,1)S(1)} slice-done(%slice-start.7)
  %fusion.2 = bf16[4,128]{1,0:T(8,128)(2,1)} fusion(%slice-done.7, %fusion.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(serve_step)/shared_block/add"}
  ROOT %tuple.4 = (bf16[8,128]{1,0:T(8,128)(2,1)}, bf16[4,128]{1,0:T(8,128)(2,1)}) tuple(%fusion.1, %fusion.2)
}
"""


def test_inherited_scopes_follow_users():
    """An instruction without an ``op_name`` takes its nearest user's,
    through users without one (a copy's done, a bitcast); tuple types,
    layouts and attributes after the operands do not confuse the parse;
    the outputs' tuple, whose users are none, is left out."""
    ins = scopes.parse_hlo(HLO)
    assert ins["copy-start.3"] == (["w"], "")
    assert ins["slice-start.7"] == (["c"], "")
    assert ins["fusion.2"] == (["slice-done.7", "fusion.1"],
                               "jit(serve_step)/shared_block/add")
    got = scopes.inherited(HLO)
    mamba, shared = "jit(serve_step)/mamba_layer/mul", \
        "jit(serve_step)/shared_block/add"
    for n in ("w", "copy-start.3", "copy-done.3", "bitcast.2", "param_0"):
        assert got[n] == mamba
    for n in ("c", "slice-start.7", "slice-done.7"):
        assert got[n] == shared
    assert "tuple.4" not in got and "fusion.1" not in got


def test_scope_time_counts_inherited_operations_inside_the_step():
    """An operation with no scope counts in its inherited scope only
    inside a serve step; one of another program of the same name does
    not, and without a map none does."""
    D0, OPS, MODS = "/device:TPU:0", tracefile.OPS_LINE, \
        tracefile.MODULES_LINE
    ev = [Event("/host:CPU", "python", tracefile.WINDOW, 0, 100),
          Event(D0, MODS, "jit_serve_step(1)", 0, 40),
          Event(D0, MODS, "jit_pick(2)", 40, 50),
          Event(D0, OPS, "fusion.1", 0, 10, "jit(serve_step)/mamba_layer/m"),
          Event(D0, OPS, "%copy-done.3 = bf16[8,128] copy-done(%copy-st"
                "art.3)", 10, 16),
          Event(D0, OPS, "slice-done.7", 16, 18),
          Event(D0, OPS, "fusion.2", 18, 30, "jit(serve_step)/shared_block/a"),
          Event(D0, OPS, "copy-done.3", 42, 49)]
    inherit = scopes.inherited(HLO)
    assert scopes.scope_ns(ev, D0, "mamba_layer", {}) == 10
    assert scopes.scope_ns(ev, D0, "mamba_layer", inherit) == 10 + 6
    assert scopes.scope_ns(ev, D0, "shared_block", inherit) == 2 + 12
    assert scopes.ms_per_step(ev, "mamba_layer", inherit) == \
        pytest.approx(16e-6)


def test_scope_readers_need_the_map():
    """The zamba2 scope readers read nothing where the runner gave no map
    of the operations XLA added, and count them where it did."""
    from chipbench import harness
    D0 = "/device:TPU:0"
    ev = [Event("/host:CPU", "python", tracefile.WINDOW, 0, 100),
          Event(D0, tracefile.MODULES_LINE, "jit_serve_step(1)", 0, 40),
          Event(D0, tracefile.OPS_LINE, "fusion.1", 0, 10,
                "jit(serve_step)/mamba_layer/m"),
          Event(D0, tracefile.OPS_LINE, "copy-done.3", 10, 16)]
    metrics = Path(__file__).resolve().parent / "metrics"
    read = {n: harness.load_module(metrics / f"{n}.py").read
            for n in ("mamba_ms.zamba2", "shared_block_ms.zamba2",
                      "mamba_roofline.zamba2")}
    r = dict(events=ev, step_counts=[counts_hybrid.decode_step(CFG, 32, 1)],
             peaks=dict(flops_bf16=197e12, hbm_bw=819e9))
    assert all(f(r) is None for f in read.values())
    r["inherited_scopes"] = scopes.inherited(HLO)
    assert read["mamba_ms.zamba2"](r) == pytest.approx(16e-6)
    assert read["shared_block_ms.zamba2"](r) is None
    assert read["mamba_roofline.zamba2"](r) > 0
