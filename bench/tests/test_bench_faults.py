"""The comparison that decides ``correct``: runs through the harness on
the host CPU with the timed path broken underneath come out not correct,
once for each fault a cell can have, and so does the control (the
reference in the next lower precision in the program's place)."""
import jax
import numpy as np
import pytest

from bench import control
from bench.tests import smoke_cells as sc


def test_serve_token_altered_where_produced(smoke_root, run_smoke,
                                            monkeypatch):
    from repro.launch import serve
    real = serve._batched_step

    def broken(cfg):
        step = real(cfg)

        def altered(params, toks, poss, cache):
            t, p, c = step(params, toks, poss, cache)
            return (t + 1) % cfg.vocab, p, c
        return altered
    monkeypatch.setattr(serve, "_batched_step", broken)
    line = run_smoke(smoke_root, "serve-smoke")
    assert line["correct"] is False
    assert line["checks"]["logit_gap"]["value"] > \
        line["checks"]["logit_gap"]["limit"]


def test_fl_round_returns_its_state_unchanged(smoke_root, run_smoke,
                                              monkeypatch):
    from repro.fl import engine

    def unchanged(w_versions, vidx, *args, **kwargs):
        return jax.tree.map(lambda a: a[vidx], w_versions)
    monkeypatch.setattr(engine, "_cohort_round", unchanged)
    line = run_smoke(smoke_root, "fl-smoke")
    assert line["correct"] is False
    assert line["checks"]["round_mismatch"]["value"] > 0.5


def test_fl_half_of_each_batch_left_out(smoke_root, run_smoke, monkeypatch):
    from repro.fl import engine
    real = engine._cohort_round

    def half(w_versions, vidx, xs, ys, didx, bidx, valid, **kwargs):
        return real(w_versions, vidx, xs, ys, didx,
                    bidx[..., :bidx.shape[-1] // 2], valid, **kwargs)
    monkeypatch.setattr(engine, "_cohort_round", half)
    line = run_smoke(smoke_root, "fl-smoke", seconds=3.0)
    assert line["correct"] is False
    c = line["checks"]["round_mismatch"]
    assert c["value"] > c["limit"]


def test_fl_aggregate_altered_where_produced(smoke_root, run_smoke,
                                             monkeypatch):
    from repro.core.server import TeasqServer
    real = TeasqServer._aggregate

    def altered(self):
        return jax.tree.map(lambda a: a * 1.001, real(self))
    monkeypatch.setattr(TeasqServer, "_aggregate", altered)
    line = run_smoke(smoke_root, "fl-smoke", seconds=3.0)
    assert line["correct"] is False
    assert line["checks"]["agg_rel_err"]["value"] > 1e-4


# The FL cell's control on the chip is the reference at ``high`` precision
# (three bfloat16 passes) in the round and the fold computed in bfloat16;
# the CPU computes float32 products whatever the precision, so here the
# round's reference in bfloat16 stands in for the first.
@pytest.mark.parametrize("cell,keys", [
    ("fl-smoke", [("round_mismatch", "bf16_round_mismatch"),
                  ("agg_rel_err", "control_agg_rel_err")]),
    ("serve-smoke", [("logit_gap", "control_logit_gap"),
                     ("mean_gap", "control_mean_gap")]),
])
def test_the_control_is_not_correct(smoke_root, cell, keys):
    limits = sc.CELLS[cell]["limits"]
    for r in control.readings(cell, [11, 12, 13], 2.0, root=smoke_root,
                              devices=jax.devices("cpu")):
        assert r["correct"] is True and r["control_correct"] is False
        for program, ctl in keys:
            assert np.isfinite(r[program]) and r[program] <= limits[program]
            assert r[ctl] > limits[program]
