"""The dry-run measurement tooling: HLO parsers (trip-count-aware flops /
bytes / collectives), roofline analysis, input specs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.dryrun import (collective_bytes, hlo_bytes, hlo_flops,
                                 _parse_computations)


def test_flops_exact_on_matmul():
    c = jax.jit(lambda a, b: a @ b).lower(
        jnp.zeros((128, 256)), jnp.zeros((256, 64))).compile()
    assert hlo_flops(c.as_text()) == 2 * 128 * 256 * 64


def test_flops_trip_count_aware():
    def f(c, w):   # w traced so XLA cannot constant-fold the dot away
        out, _ = jax.lax.scan(lambda c, _: (c @ w, None), c, None, length=7)
        return out

    c = jax.jit(f).lower(jnp.zeros((64, 64)), jnp.zeros((64, 64))).compile()
    assert hlo_flops(c.as_text()) == 7 * 2 * 64 ** 3
    # XLA's own cost_analysis undercounts ~7x (documents why we need ours);
    # on some jax versions it returns a one-element list per device
    ca = c.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    assert ca["flops"] < 1.01 * 2 * 64 ** 3


def test_flops_grad_counts_both_dots():
    def loss(w, x):
        return jnp.sum((x @ w) ** 2)
    c = jax.jit(jax.grad(loss)).lower(
        jnp.zeros((256, 64)), jnp.zeros((32, 256))).compile()
    got = hlo_flops(c.as_text())
    expect = 2 * (2 * 32 * 256 * 64)
    assert abs(got - expect) / expect < 0.01


def test_bytes_scale_with_trips():
    w = jnp.zeros((128, 128))

    def f(c, n):
        out, _ = jax.lax.scan(lambda c, _: (c @ w, None), c, None, length=n)
        return out

    b2 = hlo_bytes(jax.jit(lambda c: f(c, 2)).lower(
        jnp.zeros((128, 128))).compile().as_text())
    b8 = hlo_bytes(jax.jit(lambda c: f(c, 8)).lower(
        jnp.zeros((128, 128))).compile().as_text())
    assert 2.5 < b8 / b2 < 4.5   # ~4x more loop traffic (fixed overhead)


def test_parse_computations_handles_tuple_params():
    txt = """HloModule m

%body (arg: (s32[], f32[4,4])) -> (s32[], f32[4,4]) {
  %p = (s32[], f32[4,4]) parameter(0)
  ROOT %t = (s32[], f32[4,4]) tuple(%p)
}

ENTRY %main (x: f32[4,4]) -> f32[4,4] {
  %x = f32[4,4] parameter(0)
  ROOT %out = f32[4,4] copy(%x)
}
"""
    comps, entry = _parse_computations(txt)
    assert "body" in comps and entry == "main"


def test_collective_bytes_ring_estimates():
    txt = """HloModule m

ENTRY %main (x: f32[16,1024]) -> f32[16,1024] {
  %x = f32[16,1024] parameter(0)
  %ag = f32[16,1024] all-gather(%x), replica_groups=[16,16]<=[256], dimensions={0}
  ROOT %ar = f32[16,1024] all-reduce(%ag), replica_groups=[16,16]<=[256], to_apply=%add
}
"""
    out = collective_bytes(txt, 256)
    nbytes = 16 * 1024 * 4
    frac = 15 / 16
    np.testing.assert_allclose(out["all-gather"], nbytes * frac)
    np.testing.assert_allclose(out["all-reduce"], 2 * nbytes * frac)
    np.testing.assert_allclose(out["total"], 3 * nbytes * frac)


def test_roofline_analyze():
    from benchmarks import roofline as RL
    rec = {
        "arch": "qwen3_1_7b", "shape": "train_4k", "mesh": "16x16",
        "step": "fed", "params": 2e9, "active_params": 2e9,
        "fed": {"local_steps": 1},
        "cost": {"flops_trip_aware": 1e13, "bytes_trip_aware": 1e12,
                 "flops": 1e11, "bytes accessed": 1e10},
        "collectives": {"total": 5e10},
        "memory": {"temp_size_in_bytes": 10 ** 10},
    }
    row = RL.analyze(rec, 256, "TPU v5 lite")
    assert row["dominant"] == "memory"
    np.testing.assert_allclose(row["compute_s"], 1e13 / 197e12)
    np.testing.assert_allclose(row["collective_s"], 1.0)
    # uses the trip-aware flops, not the raw ones
    expected_ratio = (6 * 2e9 * 4096 * 256) / (1e13 * 256)
    np.testing.assert_allclose(row["useful_ratio"], expected_ratio)


def test_roofline_peaks_refuse_unknown_kind():
    from benchmarks import roofline as RL
    assert RL.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        RL.peaks_for("cpu")


def test_input_specs_cover_all_modalities():
    from repro.launch import specs as S
    from repro.configs.base import get_config
    whisper = get_config("whisper_tiny")
    b = S.batch_specs(whisper, "train_4k")
    assert "frames" in b and b["frames"].shape == (256, 1500, 384)
    vlm = get_config("internvl2_2b")
    b = S.batch_specs(vlm, "train_4k")
    assert "patches" in b and b["patches"].shape == (256, 256, 2048)
    # decode specs: SSM has state not kv
    tok, cache, pos, rolling = S.decode_specs(get_config("mamba2_370m"),
                                              "long_500k")
    flat = jax.tree_util.tree_leaves(cache)
    assert not rolling  # ssm decodes natively, no window
    # dense long_500k rolls an 8k window
    tok, cache, pos, rolling = S.decode_specs(get_config("qwen3_1_7b"),
                                              "long_500k")
    assert rolling
    k = cache["k"]
    assert k.shape[2] == S.WINDOW


def test_fed_group_dp_math_identical_no_mesh():
    """group_parallelism only changes sharding; without a mesh the numbers
    are identical."""
    from repro.configs.base import get_smoke_config
    from repro.core.fed_step import FedConfig, make_fed_train_step
    from repro.models import transformer as T
    cfg = get_smoke_config("smollm_135m")
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab, (4, 32)), jnp.int32)}
    stale = jnp.zeros(2, jnp.int32)
    loss_fn = lambda p, b: T.lm_loss(p, b, cfg)[0]
    outs = []
    for gp in ("tp", "dp"):
        fed = FedConfig(n_groups=2, local_steps=1, lr=1e-2,
                        schedule="gather_q", group_parallelism=gp)
        p1, m = jax.jit(make_fed_train_step(loss_fn, fed))(params, batch, stale)
        outs.append((p1, float(m["local_loss"])))
    assert outs[0][1] == outs[1][1]
    for a, b in zip(jax.tree.leaves(outs[0][0]), jax.tree.leaves(outs[1][0])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
