"""Kernel autotuner: cache round-trip, deterministic candidate enumeration,
ops.gr_matmul consulting the tuned cache, and envelope fallbacks."""
import json

import numpy as np
import pytest

from repro.core import make_ring
from repro.kernels import (
    cached_blocks,
    candidate_blocks,
    gr_matmul,
    gr_matmul_ref,
    kernel_supported,
    tune_key,
)
from repro.kernels import autotune as at
from repro.kernels import ops as kernel_ops

GR3 = make_ring(2, 32, (3,))
Z32 = make_ring(2, 32, ())


@pytest.fixture(autouse=True)
def fresh_memory_cache():
    """Each test sees the committed disk cache afresh and leaks nothing
    in-process (autotune() mutates the in-memory view, never the JSON)."""
    at.invalidate_memory_cache()
    yield
    at.invalidate_memory_cache()


# ------------------------------------------------------------- cache I/O


def test_cache_roundtrip(tmp_path):
    entries = {
        tune_key(GR3, 16, 16, 16, device="testdev"): {
            "blocks": [8, 16, 16], "us": 123.4, "tried": 5,
        },
        tune_key(Z32, 64, 64, 64, device="testdev"): {
            "blocks": [64, 64, 64], "us": 9.9, "tried": 8,
        },
    }
    path = tmp_path / "cache.json"
    at.save_cache(entries, path)
    assert at.load_cache(path) == json.loads(path.read_text())["entries"]
    assert at.load_cache(path) == entries


def test_cache_load_rejects_malformed_entries(tmp_path):
    path = tmp_path / "bad.json"
    path.write_text(json.dumps({
        "version": at.CACHE_VERSION,
        "entries": {"k": {"blocks": [8, 16], "us": 1.0}},  # 2-tuple: invalid
    }))
    with pytest.raises(ValueError, match="malformed"):
        at.load_cache(path)


def test_cache_version_mismatch_is_empty(tmp_path):
    path = tmp_path / "old.json"
    path.write_text(json.dumps({"version": 999, "entries": {"k": {}}}))
    assert at.load_cache(path) == {}
    assert at.load_cache(tmp_path / "missing.json") == {}


def test_committed_cache_deserializes_and_covers_tier1_points():
    """The committed JSON must stay loadable and must cover the tier-1
    ring/shape points for the device it was tuned on (mirrors the CI
    autotune-smoke --check)."""
    entries = at.load_cache()
    assert entries, "committed autotune cache is missing or empty"
    devices = {key.split("|", 1)[0] for key in entries}
    assert any(
        not at.coverage_gaps(entries, device=dev) for dev in devices
    ), f"no device in {sorted(devices)} fully covers DEFAULT_POINTS"


# ------------------------------------------------- candidate enumeration


def test_candidate_enumeration_is_deterministic():
    a = candidate_blocks(GR3, 128, 128, 128)
    b = candidate_blocks(GR3, 128, 128, 128)
    assert a == b and len(a) == len(set(a))


def test_candidates_include_static_default_and_respect_vmem():
    cands = candidate_blocks(GR3, 128, 128, 128)
    assert (128, 128, 128) in cands
    for bt, bs, br in cands:
        words = (bt * br + br * bs + bt * bs) * GR3.D + GR3.K * bt * bs
        assert words * 4 <= at.VMEM_BUDGET_BYTES, (bt, bs, br)
    # divisor-aware ordering: the first candidate wastes no padding
    bt, bs, br = cands[0]
    assert 128 % bt == 0 and 128 % bs == 0 and 128 % br == 0


def test_candidates_for_small_dims_are_single_block():
    assert candidate_blocks(GR3, 8, 8, 8) == [(8, 8, 8)]
    # ragged dims align up to 8 before enumeration
    assert candidate_blocks(GR3, 7, 5, 3) == [(8, 8, 8)]


def test_tune_key_canonicalizes_ragged_shapes():
    assert tune_key(GR3, 7, 13, 5, device="d") == tune_key(
        GR3, 8, 16, 8, device="d"
    )
    assert tune_key(GR3, 8, 8, 8, device="d") != tune_key(
        Z32, 8, 8, 8, device="d"
    )


# --------------------------------------------------- tuning + ops wiring


def test_autotune_records_and_ops_picks_cached_config(monkeypatch):
    # 24^3 is deliberately off DEFAULT_POINTS so the committed cache can
    # never mask what this test tunes in-process
    res = at.autotune(GR3, 24, 24, 24, budget=3, iters=1)
    assert res.tried <= 3 and res.blocks in candidate_blocks(GR3, 24, 24, 24)
    assert cached_blocks(GR3, 24, 24, 24) == res.blocks

    seen = {}
    real_planar = kernel_ops.gr_matmul_planar

    def spy(A, B, ring, *, bt, bs, br, interpret):
        seen["blocks"] = (bt, bs, br)
        return real_planar(A, B, ring, bt=bt, bs=bs, br=br,
                           interpret=interpret)

    monkeypatch.setattr(kernel_ops, "gr_matmul_planar", spy)
    rng = np.random.default_rng(0)
    A, B = GR3.random(rng, (24, 24)), GR3.random(rng, (24, 24))
    out = gr_matmul(A, B, GR3, interpret=True)
    assert seen["blocks"] == res.blocks
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(gr_matmul_ref(A, B, GR3))
    )


def test_explicit_blocks_override_cache():
    at.autotune(GR3, 24, 24, 24, budget=2, iters=1)
    rng = np.random.default_rng(1)
    A, B = GR3.random(rng, (24, 24)), GR3.random(rng, (24, 24))
    out = gr_matmul(A, B, GR3, blocks=(8, 8, 8), interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(gr_matmul_ref(A, B, GR3))
    )


def test_lru_survives_disk_invalidation_boundary():
    res = at.autotune(GR3, 24, 24, 24, budget=2, iters=1)
    assert cached_blocks(GR3, 24, 24, 24) == res.blocks  # LRU hit
    at.invalidate_memory_cache()
    # nothing was persisted: in-process result gone, committed cache rules
    key = tune_key(GR3, 24, 24, 24)
    assert (cached_blocks(GR3, 24, 24, 24) is None) == (
        key not in at.load_cache()
    )


def test_autotune_rejects_out_of_envelope_rings():
    with pytest.raises(ValueError, match="envelope"):
        at.autotune(make_ring(3, 2, (2,)), 8, 8, 8)


# --------------------------------------------------- fallbacks + padding


def test_gr_matmul_falls_back_outside_envelope():
    # no silent swap to the reference: the caller must use Ring.matmul
    ring = make_ring(3, 2, (2,))
    assert not kernel_supported(ring)
    rng = np.random.default_rng(2)
    A, B = ring.random(rng, (6, 6)), ring.random(rng, (6, 6))
    with pytest.raises(ValueError, match="outside the kernel envelope"):
        gr_matmul(A, B, ring)


def test_planar_kernel_clamps_and_pads_odd_blocks():
    """The old hard assert (T % bt == 0 ...) is gone: non-dividing and
    oversized block sizes zero-pad instead of crashing."""
    import jax.numpy as jnp

    from repro.kernels.gr_matmul import gr_matmul_planar

    rng = np.random.default_rng(3)
    A = GR3.random(rng, (20, 14))
    B = GR3.random(rng, (14, 9))
    Ap, Bp = jnp.moveaxis(A, -1, 0), jnp.moveaxis(B, -1, 0)
    ref = jnp.moveaxis(gr_matmul_ref(A, B, GR3), -1, 0)
    for blocks in [(16, 8, 128), (8, 8, 8), (256, 256, 256)]:
        bt, bs, br = blocks
        out = gr_matmul_planar(
            Ap, Bp, GR3, bt=bt, bs=bs, br=br, interpret=True
        )
        assert out.shape == ref.shape
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(ref), err_msg=str(blocks)
        )
