"""Smoke coverage for the codec throughput benchmark (scripts/tier1.sh runs
``pytest -m smoke``, which exercises the benchmark harness end to end on a
reduced grid without writing results)."""
import pytest

from benchmarks.codec_throughput import bench_codec, run

pytestmark = pytest.mark.smoke


def test_codec_throughput_smoke_grid():
    rows = run(reps=1, grid_ps=(0.25,), grid_pq=(8,), out_path=None)
    assert {r["codec"] for r in rows} == {"dense", "identity", "packed",
                                          "threshold", "packed_fused",
                                          "packed_host"}
    for r in rows:
        assert r["encode_mbps"] > 0
        # passthrough decodes (identity/threshold) report null, not a
        # timer-resolution pseudo-throughput
        if r["resolved"] in ("identity", "threshold"):
            assert r["decode_mbps"] is None
        else:
            assert r["decode_mbps"] > 0
        assert r["wire_bytes"] == r["expected_bytes"], r
        if r["resolved"] != "identity":
            assert r["wire_bytes"] < r["dense_bytes"]
    # the fused variant proved stream equality during the bench itself
    fused = next(r for r in rows if r["codec"] == "packed_fused")
    assert fused["bit_identical_to_host"] is True


def test_codec_throughput_merges_instead_of_clobbering(tmp_path):
    """A partial re-run must update its (codec, p_s, p_q) rows in place and
    keep every other recorded row (the engine_scale merge discipline)."""
    out = tmp_path / "codec_throughput.json"
    run(reps=1, grid_ps=(0.25,), grid_pq=(8,), codecs=("identity",),
        out_path=str(out))
    run(reps=1, grid_ps=(0.25,), grid_pq=(8,), codecs=("packed_fused",),
        out_path=str(out))
    import json
    rows = json.loads(out.read_text())
    assert {r["codec"] for r in rows} == {"identity", "packed_fused"}


def test_codec_throughput_prices_identity_dense():
    import jax
    from repro.models.cnn import init_cnn
    tree = init_cnn(jax.random.PRNGKey(0))
    row = bench_codec("identity", tree, 0.25, 8, reps=1)
    assert row["wire_bytes"] == row["dense_bytes"]
    assert row["compression_x"] == 1.0


def test_host_tuning_refuses_after_backend_init(monkeypatch):
    """The re-exec must come before JAX holds a backend: afterwards the
    process would re-exec while holding the device."""
    import jax
    from benchmarks.common import _HOST_TUNED_MARKER, maybe_reexec_host_tuned
    monkeypatch.delenv(_HOST_TUNED_MARKER, raising=False)
    jax.devices()
    with pytest.raises(RuntimeError, match="before any JAX backend"):
        maybe_reexec_host_tuned(True, host_devices=2)
