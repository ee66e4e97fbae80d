"""Elastic backend: bit-identicality under randomized membership traces,
straggler edge cases, decode-operator caching, stream rescale.

The load-bearing property: for EVERY registered scheme family and EVERY
valid join/leave/slowdown trace, the event-driven elastic execution decodes
from a *different* R-subset than the synchronous backends (first R arrivals
vs first R indices) and still produces the exact same bits — the any-R
decode is subset-agnostic because the arithmetic is integer-exact.
"""
import zlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import WorkerTrace, make_ring, sample_trace
from repro.core.straggler import select_workers
from repro.cdmm import (
    ElasticBackend,
    ElasticStream,
    LocalSimBackend,
    NotEnoughResponders,
    ProblemSpec,
    coded_matmul,
    expected_time_to_R,
    get_scheme,
    plan,
)
from repro.dist.chips import compile_counter
from repro.runtime.elastic import replan_batch

Z32 = make_ring(2, 32, ())

# one feasible configuration per registered family (mirrors test_api.py)
CASES = [
    ("ep", ProblemSpec(8, 8, 8, n=1, ring=make_ring(2, 32, (3,)), N=8), (2, 2, 1), 1),
    ("plain", ProblemSpec(8, 8, 8, n=1, ring=Z32, N=8), (2, 2, 1), 1),
    ("ep_rmfe1", ProblemSpec(8, 8, 8, n=1, ring=Z32, N=8), (2, 2, 1), 2),
    ("ep_rmfe2", ProblemSpec(8, 8, 8, n=1, ring=Z32, N=8), (2, 2, 1), 2),
    ("batch_ep_rmfe", ProblemSpec(8, 8, 8, n=2, ring=Z32, N=8), (2, 2, 1), 2),
    ("gcsa", ProblemSpec(8, 8, 8, n=2, ring=Z32, N=8), (1, 1, 1), 2),
]


def _build(name, spec, uvw, n):
    u, v, w = uvw
    return get_scheme(name).build(spec, u, v, w, n)


def _inputs(scheme, spec, rng):
    shape_a = (spec.t, spec.r)
    shape_b = (spec.r, spec.s)
    if scheme.batch > 1:
        shape_a, shape_b = (scheme.batch, *shape_a), (scheme.batch, *shape_b)
    return scheme.base.random(rng, shape_a), scheme.base.random(rng, shape_b)


def _trace_with_R_responders(key, N, R, rng):
    """Random trace conditioned on at least R (or exactly R) responders."""
    for salt in range(100):
        tr = sample_trace(
            jax.random.fold_in(key, salt), N,
            join_spread_ms=2.0, leave_prob=0.25, slowdown_prob=0.3,
        )
        if tr.mask().sum() >= R:
            return tr
    raise AssertionError("trace sampler never produced >= R responders")


# ------------------------------------------------------------ property test


@pytest.mark.parametrize("name,spec,uvw,n", CASES, ids=[c[0] for c in CASES])
def test_elastic_bit_identical_to_local_under_random_traces(name, spec, uvw, n):
    scheme = _build(name, spec, uvw, n)
    rng = np.random.default_rng(11)
    A, B = _inputs(scheme, spec, rng)
    local = LocalSimBackend()
    # crc32, not hash(): PYTHONHASHSEED must not affect trace reproducibility
    key = jax.random.PRNGKey(zlib.crc32(name.encode()) % (2**31))
    for trial in range(3):
        tr = _trace_with_R_responders(
            jax.random.fold_in(key, trial), spec.N, scheme.R, rng
        )
        mask = jnp.asarray(tr.mask())
        eb = ElasticBackend(trace=tr)
        C_elastic = eb(scheme, A, B)
        C_local = local(scheme, A, B, mask=mask)
        np.testing.assert_array_equal(
            np.asarray(C_elastic), np.asarray(C_local),
            err_msg=f"{name} trial {trial} live={eb.last_stats.live_idx}",
        )
        # elastic decodes from the R fastest *arrivals*, sync from the first
        # R live indices — the subsets genuinely differ across trials, yet
        # the bits match; also sanity-check the virtual-time accounting
        st = eb.last_stats
        assert len(st.live_idx) == scheme.R
        assert st.time_to_R_ms <= st.time_to_all_ms
        assert st.n_responders == int(tr.mask().sum())


# --------------------------------------------------- straggler edge cases


def _ep_scheme(N=8):
    return _build("ep", CASES[0][1], (2, 2, 1), 1)


def test_exactly_R_live_decodes():
    scheme = _ep_scheme()
    rng = np.random.default_rng(0)
    A, B = _inputs(scheme, CASES[0][1], rng)
    expect = np.asarray(scheme.base.matmul(A, B))
    # exactly R responders, scattered: everyone else leaves before finishing
    live = np.zeros(scheme.N, bool)
    live[np.array([1, 3, 4, 6, 7])[: scheme.R]] = True
    assert live.sum() == scheme.R
    tr = WorkerTrace.all_live(scheme.N).restrict(live)
    C = ElasticBackend(trace=tr)(scheme, A, B)
    np.testing.assert_array_equal(np.asarray(C), expect)


def test_fewer_than_R_live_raises_not_decodes_garbage():
    scheme = _ep_scheme()
    rng = np.random.default_rng(0)
    A, B = _inputs(scheme, CASES[0][1], rng)
    live = np.zeros(scheme.N, bool)
    live[: scheme.R - 1] = True
    with pytest.raises(NotEnoughResponders, match=f"needs R={scheme.R}"):
        ElasticBackend()(scheme, A, B, mask=jnp.asarray(live))
    # contrast: the sync path would silently decode using a DEAD worker's
    # (meaningless) response — the elastic raise is the correct behavior
    idx = np.asarray(select_workers(jnp.asarray(live), scheme.R))
    assert not live[idx].all()


def test_all_live_fast_path():
    scheme = _ep_scheme()
    rng = np.random.default_rng(1)
    A, B = _inputs(scheme, CASES[0][1], rng)
    eb = ElasticBackend()  # no trace, no mask -> vectorized fast path
    C = eb(scheme, A, B)
    assert eb.last_stats.fast_path
    assert eb.last_stats.live_idx == tuple(range(scheme.R))
    np.testing.assert_array_equal(
        np.asarray(C), np.asarray(scheme.base.matmul(A, B))
    )
    # masked call must NOT take the fast path
    eb(scheme, A, B, mask=jnp.ones(scheme.N, bool))
    assert not eb.last_stats.fast_path


def test_decode_subset_cache_across_two_live_sets():
    scheme = _ep_scheme()
    rng = np.random.default_rng(2)
    A, B = _inputs(scheme, CASES[0][1], rng)
    expect = np.asarray(scheme.base.matmul(A, B))
    eb = ElasticBackend()
    m1 = np.ones(scheme.N, bool)
    m1[[0, 2]] = False
    m2 = np.ones(scheme.N, bool)
    m2[[1, 5]] = False
    C1 = eb(scheme, A, B, mask=jnp.asarray(m1))
    set1 = eb.last_stats.live_idx
    C2 = eb(scheme, A, B, mask=jnp.asarray(m2))
    set2 = eb.last_stats.live_idx
    assert set1 != set2, "the two masks must exercise different subsets"
    np.testing.assert_array_equal(np.asarray(C1), expect)
    np.testing.assert_array_equal(np.asarray(C2), expect)
    # the live set is data to one jitted decode: a third live set, and a
    # replay of the first, compile nothing and still decode exactly
    compiles = compile_counter()
    m3 = np.ones(scheme.N, bool)
    m3[[3, 4]] = False
    for mask in (m3, m1):
        C = eb(scheme, A, B, mask=jnp.asarray(mask))
        np.testing.assert_array_equal(np.asarray(C), expect)
    assert eb.last_stats.live_idx == set1
    assert compiles() == 0


def test_decode_op_validates_subset():
    scheme = _ep_scheme()
    with pytest.raises(ValueError, match="exactly R"):
        scheme.decode_op(tuple(range(scheme.R - 1)))
    with pytest.raises(ValueError, match="invalid live set"):
        scheme.decode_op((0,) * scheme.R)


# ------------------------------------------------------- planner objective


def test_time_to_R_objective_prefers_lower_threshold():
    # the R-th order statistic is monotone in R, so at fixed N the expected
    # elastic completion must rank lower-R schemes first
    assert expected_time_to_R(8, 2) < expected_time_to_R(8, 7)
    spec = ProblemSpec(16, 16, 16, n=1, ring=Z32, N=8)
    p = plan(spec, objective="time_to_R")
    scores = [c.score for c in p.candidates]
    assert scores == sorted(scores)
    Rs = [c.costs.R for c in p.candidates]
    assert p.best.costs.R == min(Rs)


def test_time_to_R_end_to_end_elastic():
    spec = ProblemSpec(16, 16, 16, n=1, ring=Z32, N=8, straggler_budget=2)
    scheme = plan(spec, objective="time_to_R").instantiate()
    rng = np.random.default_rng(3)
    A = Z32.random(rng, (16, 16))
    B = Z32.random(rng, (16, 16))
    tr = sample_trace(jax.random.PRNGKey(9), 8, slowdown_prob=0.4)
    C = coded_matmul(A, B, scheme, backend=ElasticBackend(trace=tr))
    np.testing.assert_array_equal(
        np.asarray(C), np.asarray(Z32.matmul(A, B))
    )


# ------------------------------------------------------- rescale mid-stream


def test_replan_batch_fixed():
    assert replan_batch(256, 16) == 16
    assert replan_batch(256, 15) == 18  # ceil: 15*18 >= 256
    assert replan_batch(7, 2) == 4
    with pytest.raises(ValueError, match="at least one survivor"):
        replan_batch(256, 0)
    with pytest.raises(ValueError, match="at least one survivor"):
        replan_batch(256, -3)
    with pytest.raises(ValueError, match="global_batch"):
        replan_batch(0, 4)


def test_stream_rescales_mid_stream():
    st = ElasticStream(8, 8, 8, Z32, group_size=8)
    rng = np.random.default_rng(4)
    As = Z32.random(rng, (6, 8, 8))
    Bs = Z32.random(rng, (6, 8, 8))
    expect = [np.asarray(Z32.matmul(As[i], Bs[i])) for i in range(6)]

    Cs = st.step(As, Bs, live=16)  # two groups of 8 -> per-group batch 3
    assert st.last_replan == (2, 3)
    for i in range(6):
        np.testing.assert_array_equal(np.asarray(Cs[i]), expect[i])

    Cs = st.step(As, Bs, live=9)  # workers left: one group absorbs the lot
    assert st.last_replan == (1, 6)
    for i in range(6):
        np.testing.assert_array_equal(np.asarray(Cs[i]), expect[i])

    with pytest.raises(NotEnoughResponders):
        st.step(As, Bs, live=7)
