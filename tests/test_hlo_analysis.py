"""Validate the trip-aware collective-bytes parser against known programs."""
import os

import numpy as np
import pytest

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from repro.launch.hlo_analysis import _shape_bytes, collective_bytes  # noqa: E402

NDEV = len(jax.devices())
needs8 = pytest.mark.skipif(NDEV < 8, reason="needs 8 devices")


def test_shape_bytes():
    assert _shape_bytes("f32[128,256]") == 128 * 256 * 4
    assert _shape_bytes("bf16[16,16] blah u32[4]") == 16 * 16 * 2 + 16
    assert _shape_bytes("(f32[8], s8[8])") == 32 + 8
    assert _shape_bytes("pred[]") == 1


@needs8
def test_collectives_simple_psum():
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("x",))

    def f(a):
        return jax.lax.psum(a, "x")

    m = jax.jit(
        jax.shard_map(f, mesh=mesh, in_specs=P("x"), out_specs=P(), check_vma=False)
    )
    text = m.lower(jax.ShapeDtypeStruct((8, 128), jnp.float32)).compile().as_text()
    coll = collective_bytes(text)
    # one all-reduce of the local (1,128) f32 block -> 512 bytes
    assert coll["all-reduce"] >= 512
    assert coll["count"] >= 1


@needs8
def test_collectives_inside_scan_multiplied():
    """A psum inside a 10-trip scan must be charged 10x."""
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("x",))
    TRIPS = 10

    def f(a):
        def body(c, _):
            return c + jax.lax.psum(a, "x"), None

        out, _ = jax.lax.scan(body, jnp.zeros_like(a), None, length=TRIPS)
        return out

    m = jax.jit(
        jax.shard_map(f, mesh=mesh, in_specs=P("x"), out_specs=P(), check_vma=False)
    )
    text = m.lower(jax.ShapeDtypeStruct((8, 128), jnp.float32)).compile().as_text()
    coll = collective_bytes(text)
    # scan body all-reduce: 128 f32 = 512B, x10 trips (XLA may hoist the
    # loop-invariant psum — accept either exactly 1x or the full 10x)
    assert coll["all-reduce"] in (512, 512 * TRIPS), coll

    def g(a):
        def body(c, x):
            return c + jax.lax.psum(x * c, "x"), None

        out, _ = jax.lax.scan(
            body, jnp.ones_like(a), jnp.ones((TRIPS,) + a.shape)
        )
        return out

    m2 = jax.jit(
        jax.shard_map(g, mesh=mesh, in_specs=P("x"), out_specs=P(), check_vma=False)
    )
    text2 = m2.lower(jax.ShapeDtypeStruct((8, 128), jnp.float32)).compile().as_text()
    coll2 = collective_bytes(text2)
    # loop-carried dependence: cannot be hoisted -> must be multiplied by 10
    assert coll2["all-reduce"] == 512 * TRIPS, coll2
