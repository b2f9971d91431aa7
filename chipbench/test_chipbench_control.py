"""What decides ``correct``: the control (the reference one precision step
below the configuration) must come out wrong, and so must a run whose
timed path is broken underneath, once for each fault a cell can have.

These run the harness on the CPU, past its look for a chip, at the sizes
of the configuration and traffic files' ``"cpu"`` blocks.  The limits are
the configuration files' own, set from chip readings (PERF.md)."""

import ml_dtypes
import numpy as np
import pytest

from chipbench import harness
from chipbench.runners import dense_decode, overlay_session
from chipbench.test_chipbench_harness import BENCH, run_tiny, tiny_cell


@pytest.mark.parametrize("cell", ["suite_bulk", "suite_jit_churn"])
def test_overlay_control_fails_where_the_program_passes(cell):
    c = tiny_cell(cell)
    limit = c["config_data"]["check"]["rel_err_max"]
    _, out = harness.run_traffic(c, 11, 0.5, False, t_start=0.0,
                                 require_chip=False)
    assert out["correct"] and out["checks"]["worst_rel_err"][0] < limit
    control = overlay_session.control_err(out["requests"], out["pool"],
                                          ml_dtypes.bfloat16)
    assert control > limit


def test_decode_control_fails_where_the_program_passes():
    from chipbench import control
    cell = tiny_cell("yi6b_decode", "small")
    limit = cell["config_data"]["check"]["served_logit_gap_max"]
    for r in control.readings(cell, [1, 3], cell["window_s"],
                              require_chip=False):
        assert r["program"] <= limit < r["control"], r


# ------------------------------------------------------------ overlay faults
def _altered(launch):
    def broken(*a, **k):
        out = launch(*a, **k).copy()
        out[len(out) // 3] += 1.0          # one answer altered
        return out
    return broken


def _half_left_out(launch):
    def broken(*a, **k):
        out = launch(*a, **k).copy()
        out[len(out) // 2:] = 0.0          # half of the work-items skipped
        return out
    return broken


@pytest.mark.parametrize("cell", ["suite_bulk", "suite_jit_churn"])
@pytest.mark.parametrize("fault", [_altered, _half_left_out])
def test_overlay_fault_is_not_correct(monkeypatch, cell, fault):
    monkeypatch.setattr(overlay_session, "launch",
                        fault(overlay_session.launch))
    assert not run_tiny(cell)["correct"]


# ------------------------------------------------------------- decode faults
def _token_altered(build_pick):
    def build(logits, rep):
        pick, calls = build_pick(logits, rep), []

        def broken(lg):
            calls.append(1)
            tok = pick(lg)
            # every sequence's fourth served token of each wave is altered
            return (tok + 1) % lg.shape[-1] if len(calls) % 8 == 4 else tok
        return broken
    return build


def _state_unchanged(build_step):
    def build(model, params, cache, c_sh, rep):
        import jax
        from repro.train.step import make_serve_step
        step = make_serve_step(model)
        return jax.jit(lambda p, c, t, i: (step(p, c, t, i)[0], c))
    return build


def _exchange_left_out(build_step):
    """What each chip of a model=4 mesh computes without the all-reduce
    after its row-parallel products: only its own quarter of the rows of
    ``wo`` and ``w_down`` contributes."""
    def build(model, params, cache, c_sh, rep):
        import jax
        from repro.train.step import make_serve_step
        step = make_serve_step(model)

        def own_quarter(w):
            rows = w.shape[-2]
            keep = np.arange(rows) < rows // 4
            return w * keep[:, None].astype(w.dtype)

        def broken(p, c, t, i):
            layers = dict(p["layers"])
            layers["attn"] = dict(layers["attn"],
                                  wo=own_quarter(layers["attn"]["wo"]))
            layers["mlp"] = dict(layers["mlp"],
                                 w_down=own_quarter(layers["mlp"]["w_down"]))
            return step(dict(p, layers=layers), c, t, i)
        return jax.jit(broken)
    return build


@pytest.mark.parametrize("cell,fault,target", [
    ("yi6b_decode", _token_altered, "build_pick"),
    ("yi6b_decode", _state_unchanged, "build_step"),
    ("nemotron15b_decode_tp4", _exchange_left_out, "build_step"),
])
def test_decode_fault_is_not_correct(monkeypatch, cell, fault, target):
    monkeypatch.setattr(dense_decode, target,
                        fault(getattr(dense_decode, target)))
    assert not run_tiny(cell)["correct"]


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_control_in_the_programs_place_is_not_correct(cell):
    """``--control 1``: the harness's own comparison, with the control's
    outputs in the program's place, reports not correct where the
    program's run of the same seed is correct.  Sizes and window are the
    files' ``cpu.small`` over ``cpu.tiny``."""
    c = tiny_cell(cell, "small")
    runs = [harness.run_cell(cell, 3, c["window_s"], False,
                             require_chip=False, cell=c, control=control)
            for control in (False, True)]
    assert runs[0]["correct"] and not runs[1]["correct"], [r["checks"] for r in runs]
    assert any(v["value"] > v["limit"] for v in runs[1]["checks"].values())


def test_chunked_oracle_error_is_the_whole_outputs():
    """The check takes the oracle in chunks; its number is the same as
    over the whole output, here across chunk edges and with one answer
    altered."""
    items = 3 * overlay_session.CHUNK + 5
    pool = np.random.default_rng(4).random((4, items), np.float32) * 2 - 1
    for name, t in overlay_session.suite.TEMPLATES.items():
        req = dict(kernel=name, consts=t.defaults, offset=0, items=items)
        want = overlay_session.suite.oracle(
            name, t.defaults, overlay_session._inputs(pool, req))
        out = want.astype(np.float32)
        out[2 * overlay_session.CHUNK + 1] += 0.5
        req["out"] = out
        assert overlay_session.oracle_err(req, pool) == \
            overlay_session.common.rel_err(out, want)
    req["out"] = out[:-1]
    assert overlay_session.oracle_err(req, pool) == np.inf
