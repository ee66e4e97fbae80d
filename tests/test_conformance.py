"""Property-based conformance suite: EVERY registered scheme family x EVERY
execution backend must decode bit-identically to the plain ``A @ B`` oracle
under randomized specs and randomized responding subsets of size R.

There is no per-scheme special-casing: a feasible configuration for each
family is discovered generically through its registered ``predict`` hook, so
any future ``register_scheme`` call is automatically covered (and the suite
fails if a family has no feasible configuration on the template grid).

hypothesis is optional, mirroring tests/test_kernels.py: the deterministic
sweep always runs; the property-based tests add randomized examples when
hypothesis is installed.  The ``ci-fast`` profile (HYPOTHESIS_PROFILE env
var) keeps the fast CI tier under budget.
"""
import os

# must happen before jax initializes its backends (shard_map backend)
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

try:
    from hypothesis import HealthCheck, given, settings, strategies as st

    HAVE_HYPOTHESIS = True
    settings.register_profile(
        "dev",
        max_examples=5,
        deadline=None,
        suppress_health_check=[HealthCheck.too_slow],
    )
    settings.register_profile(
        "ci-fast",
        max_examples=2,
        deadline=None,
        suppress_health_check=[HealthCheck.too_slow],
    )
    settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "dev"))
except ImportError:  # pragma: no cover - exercised on minimal installs
    HAVE_HYPOTHESIS = False

from repro.core import make_ring  # noqa: E402
from repro.cdmm import (  # noqa: E402
    ElasticBackend,
    ShardMapBackend,
    coded_matmul,
    registered_schemes,
)
from repro.cdmm.api import ProblemSpec  # noqa: E402
from repro.kernels import kernel_supported  # noqa: E402

Z32 = make_ring(2, 32, ())
NDEV = len(jax.devices())
KEY = jax.random.PRNGKey(0)

# template grid the generic feasibility search walks: base sizes 8 with
# every partition in {1,2}^3 and packing in {1,2}.  Ordered so the plainest
# spec that serves a family wins; privacy templates come last, which keeps
# non-secure families on insecure specs (their predicts reject nothing, but
# secure families reject privacy_t=0 so they land on the privacy templates).
SPEC_TEMPLATES = [
    ProblemSpec(8, 8, 8, n=1, ring=make_ring(2, 32, (3,)), N=8),
    ProblemSpec(8, 8, 8, n=1, ring=Z32, N=8),
    ProblemSpec(8, 8, 8, n=2, ring=Z32, N=8),
    ProblemSpec(8, 8, 8, n=1, ring=Z32, N=8, privacy_t=1),
    ProblemSpec(8, 8, 8, n=2, ring=Z32, N=8, privacy_t=1),
]
PARTITIONS = [
    (u, v, w, n) for u in (1, 2) for v in (1, 2) for w in (1, 2)
    for n in (1, 2)
]

BACKENDS = ["local", "shard_map", "elastic"]
_ELASTIC = ElasticBackend()  # shared pool across the whole suite


def find_config(fam):
    """First spec template admitting the family, with the largest-R feasible
    partition (largest R = the most interesting any-R subsets)."""
    for spec in SPEC_TEMPLATES:
        # mirror the planner's arity rule: batch families serve n>1 specs,
        # single families serve n=1 specs
        if fam.batched != (spec.n > 1):
            continue
        feasible = []
        for (u, v, w, n) in PARTITIONS:
            costs = fam.predict(spec, u, v, w, n)
            if costs is not None and costs.R <= spec.N:
                feasible.append(((u, v, w, n), costs.R))
        if feasible:
            (u, v, w, n), _ = max(feasible, key=lambda c: c[1])
            return spec, (u, v, w, n)
    return None


_SCHEMES = {}


def build_scheme(name):
    """Build (and memoize) the family's discovered configuration."""
    if name not in _SCHEMES:
        fam = registered_schemes()[name]
        found = find_config(fam)
        assert found is not None, (
            f"family {name!r} has no feasible configuration on the "
            f"conformance template grid — extend SPEC_TEMPLATES"
        )
        spec, (u, v, w, n) = found
        _SCHEMES[name] = (spec, fam.build(spec, u, v, w, n))
    return _SCHEMES[name]


def _random_problem(scheme, spec, rng, mult):
    """Random inputs at a randomized spec (template sizes x mult)."""
    t, r, s = spec.t * mult, spec.r * mult, spec.s * mult
    base = scheme.base
    if scheme.batch > 1:
        A = base.random(rng, (scheme.batch, t, r))
        B = base.random(rng, (scheme.batch, r, s))
        expect = np.stack(
            [np.asarray(base.matmul(A[i], B[i])) for i in range(scheme.batch)]
        )
    else:
        A = base.random(rng, (t, r))
        B = base.random(rng, (r, s))
        expect = np.asarray(base.matmul(A, B))
    return A, B, expect


def _run_backend(scheme, backend, A, B, mask, key, use_kernel=False):
    mask = jnp.asarray(mask)
    if backend == "elastic":
        be = ElasticBackend(use_kernel=True) if use_kernel else _ELASTIC
        return coded_matmul(A, B, scheme, backend=be, mask=mask, key=key)
    if backend == "shard_map":
        return coded_matmul(
            A, B, scheme, backend=ShardMapBackend(use_kernel=use_kernel),
            mask=mask, key=key,
        )
    return coded_matmul(A, B, scheme, backend="local", mask=mask, key=key)


def check_conformance(name, backend, seed, use_kernel=False):
    """One property check: random inputs + a random R-subset of responders
    must decode to exactly the oracle product on the given backend."""
    spec, scheme = build_scheme(name)
    rng = np.random.default_rng(seed)
    mult = int(rng.integers(1, 3))  # randomized spec: sizes x1 or x2
    A, B, expect = _random_problem(scheme, spec, rng, mult)
    # randomized responding subset of size exactly R
    live = rng.choice(scheme.N, size=scheme.R, replace=False)
    mask = np.zeros(scheme.N, dtype=bool)
    mask[live] = True
    key = jax.random.fold_in(KEY, seed)
    C = np.asarray(_run_backend(scheme, backend, A, B, mask, key, use_kernel))
    np.testing.assert_array_equal(
        C, expect,
        err_msg=f"{name} on {backend} (seed={seed}, live={sorted(live)}, "
                f"use_kernel={use_kernel})",
    )


needs8 = pytest.mark.skipif(NDEV < 8, reason=f"needs 8 devices, have {NDEV}")


def _backend_params():
    return [
        pytest.param(b, marks=needs8 if b == "shard_map" else ())
        for b in BACKENDS
    ]


def test_every_registered_family_is_covered():
    """The suite discovers a configuration for every family — including any
    registered after this test was written."""
    for name in registered_schemes():
        build_scheme(name)
    # both secure families must be present (the tentpole registration)
    assert {"ep_secure", "ep_rmfe_secure"} <= set(registered_schemes())


@pytest.mark.parametrize("backend", _backend_params())
@pytest.mark.parametrize("name", sorted(registered_schemes()))
@pytest.mark.parametrize("seed", [0, 1])
def test_conformance_sweep(name, backend, seed):
    """Deterministic sweep: always runs, hypothesis or not."""
    check_conformance(name, backend, seed)


@pytest.mark.parametrize(
    "backend",
    [pytest.param(b, marks=needs8 if b == "shard_map" else ())
     for b in ("shard_map", "elastic")],
)
@pytest.mark.parametrize("name", sorted(registered_schemes()))
def test_conformance_sweep_use_kernel(name, backend):
    """The distributed backends' forced-kernel path (workers compute their
    block product through the Pallas gr_matmul, interpret mode on CPU)
    must stay bit-identical for every family whose codeword ring is inside
    the kernel envelope — the configuration ShardMapBackend/ElasticBackend
    auto-select where the kernel compiles."""
    _, scheme = build_scheme(name)
    if not kernel_supported(scheme.ring):
        pytest.skip(f"{scheme.ring} outside the kernel envelope")
    check_conformance(name, backend, seed=3, use_kernel=True)


if HAVE_HYPOTHESIS:

    @pytest.mark.parametrize("backend", _backend_params())
    @pytest.mark.parametrize("name", sorted(registered_schemes()))
    @given(seed=st.integers(min_value=2, max_value=2**31 - 1))
    def test_conformance_property(name, backend, seed):
        """Property-based randomized specs/subsets (hypothesis installed)."""
        check_conformance(name, backend, seed)

else:  # pragma: no cover - exercised on minimal installs

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_conformance_property():
        pass


@pytest.fixture(scope="module")
def pool_backend():
    """One real multi-process worker pool shared by the pool sweep."""
    from repro.dist import LocalPool, PoolBackend

    with LocalPool(workers=4) as p:
        yield PoolBackend(p, timeout=120)


@pytest.mark.parametrize("name", sorted(registered_schemes()))
def test_conformance_pool_sweep(name, pool_backend):
    """The multi-process pool backend (repro.dist: real worker OS processes
    behind sockets) decodes bit-identically to LocalSimBackend for every
    registered family under a fixed encode key — the distributed runtime's
    headline conformance property.  The random R-subset mask doubles as the
    any-R check over real processes; mid-request SIGKILL coverage lives in
    tests/test_dist.py."""
    spec, scheme = build_scheme(name)
    rng = np.random.default_rng(11)
    A, B, expect = _random_problem(scheme, spec, rng, 1)
    live = rng.choice(scheme.N, size=scheme.R, replace=False)
    mask = jnp.asarray(np.isin(np.arange(scheme.N), live))
    key = jax.random.fold_in(KEY, 11)
    C_pool = coded_matmul(A, B, scheme, backend=pool_backend, mask=mask,
                          key=key)
    C_local = coded_matmul(A, B, scheme, backend="local", mask=mask, key=key)
    np.testing.assert_array_equal(
        np.asarray(C_pool), np.asarray(C_local),
        err_msg=f"{name}: pool != local (live={sorted(int(i) for i in live)})",
    )
    np.testing.assert_array_equal(
        np.asarray(C_pool), expect, err_msg=f"{name}: pool != oracle",
    )


def test_encode_at_matches_master_encode_for_every_family():
    """The at-worker encode (shard_map / elastic dispatch path) agrees with
    the master-side encode share by share, keyed or not."""
    for name in sorted(registered_schemes()):
        spec, scheme = build_scheme(name)
        rng = np.random.default_rng(99)
        A, B, _ = _random_problem(scheme, spec, rng, 1)
        FA = scheme.encode_a(A, key=KEY)
        GB = scheme.encode_b(B, key=KEY)
        assert FA.shape[0] == GB.shape[0] == scheme.N
        for i in (0, scheme.N - 1):
            np.testing.assert_array_equal(
                np.asarray(scheme.encode_a_at(A, i, key=KEY)),
                np.asarray(FA[i]), err_msg=f"{name} A-share {i}",
            )
            np.testing.assert_array_equal(
                np.asarray(scheme.encode_b_at(B, i, key=KEY)),
                np.asarray(GB[i]), err_msg=f"{name} B-share {i}",
            )
