"""The pool master's weight-share cache: a codeword whose B operand equals a
recent one, byte for byte, reuses that operand's encoded B shares.

Against a real ``LocalPool`` on the CPU: hits decode exactly what an
uncached encode gives, for a batched and a single-product scheme; a weight
changed in place misses; a full and a padded batch of one weight keep two
entries; a keyed (secure) codeword never touches the cache, and one sent
without its key stores nothing; a compute-error retry and a redispatch
after SIGKILL decode exactly on a hit; and the ``pool_weight_lookups`` /
``pool_weight_hits`` counters count each lookup.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import sys
import threading

import numpy as np
import pytest

import jax

from repro.cdmm import ProblemSpec, coded_matmul, get_scheme, plan
from repro.core import make_ring
from repro.dist import LocalPool
from repro.dist.master import _WeightCache

pytestmark = pytest.mark.slow

Z32 = make_ring(2, 32, ())
KEY = jax.random.PRNGKey(7)


def _build(name, spec, uvw, n):
    return get_scheme(name).build(spec, *uvw, n)


def _batch_scheme():
    return _build("batch_ep_rmfe", ProblemSpec(8, 8, 8, n=2, ring=Z32, N=8),
                  (2, 2, 1), 2)


def _ep_scheme():
    spec = ProblemSpec(8, 8, 8, n=1, ring=make_ring(2, 32, (3,)), N=8)
    return _build("ep", spec, (2, 2, 1), 1)


def _operands(scheme, rng, pad=0):
    """Host A and B of the scheme's shape; ``pad`` trailing batch slots are
    zero, as the serving engine pads a partial batch."""
    shape = (8, 8) if scheme.batch == 1 else (scheme.batch, 8, 8)
    A = np.array(scheme.base.random(rng, shape))
    B = np.array(scheme.base.random(rng, shape))
    if pad:
        A[-pad:] = 0
        B[-pad:] = 0
    return A, B


def _oracle(scheme, A, B):
    return np.asarray(coded_matmul(A, B, scheme, backend="local"))


def _counts(master):
    s = master.stats()
    return s.get("pool_weight_lookups", 0), s.get("pool_weight_hits", 0)


def _entries(master, scheme):
    return master._weights._by_scheme.get(scheme, [])


@pytest.fixture(scope="module")
def pool():
    with LocalPool(workers=3) as p:
        yield p


# --------------------------------------------------------------------------
# the encoders and the cache itself (no processes)
# --------------------------------------------------------------------------


def _store(cache, scheme, B):
    """Look ``B`` up and, on a miss, encode a share so that it is stored;
    whether it was a hit."""
    w, hit = cache.lookup(scheme, B)
    cache.share(scheme, w, 0, lambda b, i: b)
    return hit


def test_cache_keeps_two_operands_per_scheme_most_recent_first():
    cache, scheme, other = _WeightCache(), _ep_scheme(), _batch_scheme()
    ops = [np.full((4, 4), k, dtype=np.uint32) for k in range(3)]
    assert _store(cache, scheme, ops[0]) is False
    assert _store(cache, scheme, ops[1]) is False
    assert _store(cache, scheme, ops[0].copy()) is True  # 0 most recent
    assert _store(cache, scheme, ops[2]) is False  # evicts 1
    assert _store(cache, scheme, ops[1]) is False
    assert _store(cache, other, ops[0]) is False  # per scheme instance
    # equal elements of another dtype or shape are another operand
    assert _store(cache, scheme, ops[1].astype(np.int32)) is False
    assert _store(cache, scheme, ops[1].reshape(2, 8)) is False


@pytest.mark.parametrize("where", [0, 1, 4095, -1],
                         ids=["first", "second", "mid", "last"])
def test_one_changed_element_misses_wherever_it_is(where):
    """The sampled comparison only rejects early: an operand that differs
    in one element off the sample still misses."""
    cache, scheme = _WeightCache(), _ep_scheme()
    B = np.arange(3 * 4096, dtype=np.uint32).reshape(3, 4096)
    assert _store(cache, scheme, B) is False
    changed = B.copy()
    changed.reshape(-1)[where] += 1
    assert _store(cache, scheme, changed) is False
    assert _store(cache, scheme, B.copy()) is True


def test_cache_copies_the_operand_and_stores_read_only_shares():
    cache, scheme = _WeightCache(), _ep_scheme()
    B = np.arange(16, dtype=np.uint32).reshape(4, 4)
    w, _ = cache.lookup(scheme, B)
    B[0, 0] = 99  # the caller's array, not the stored copy
    assert w.operand[0, 0] == 0 and not w.operand.flags.writeable
    gb, had = cache.share(scheme, w, 3, lambda b, i: b + i)
    assert not had and not gb.flags.writeable and gb[0, 0] == 3
    assert cache.lookup(scheme, B)[1] is False
    gb2, had = cache.share(scheme, w, 3, lambda b, i: pytest.fail("again"))
    assert had and gb2 is gb


def test_a_missed_operand_is_stored_with_its_first_share():
    cache, scheme = _WeightCache(), _ep_scheme()
    B = np.arange(16, dtype=np.uint32).reshape(4, 4)
    w, hit = cache.lookup(scheme, B)
    assert not hit and cache._by_scheme.get(scheme, []) == []

    def fail(b, i):
        raise ValueError("encode failed")

    with pytest.raises(ValueError):
        cache.share(scheme, w, 0, fail)
    assert cache._by_scheme.get(scheme, []) == []
    cache.share(scheme, w, 0, lambda b, i: b)
    assert cache._by_scheme[scheme] == [w]


def test_concurrent_lookups_keep_one_entry():
    """Threads race lookups and share encodes of one operand: the scheme
    keeps one entry, and every thread gets a share equal to the stored
    one."""
    cache, scheme = _WeightCache(), _ep_scheme()
    B = np.arange(64, dtype=np.uint32).reshape(8, 8)
    got, errors = [], []
    barrier = threading.Barrier(32)

    def run():
        try:
            barrier.wait(timeout=60)
            w, _ = cache.lookup(scheme, B.copy())
            got.append(cache.share(scheme, w, 0, lambda b, i: b * 3)[0])
        except Exception as e:  # pragma: no cover - surfaced in assert
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=run)
                   for _ in range(barrier.parties)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads) and not errors
    (entry,) = cache._by_scheme[scheme]
    assert len(got) == barrier.parties
    for g in got:
        np.testing.assert_array_equal(g, entry.shares[0])


# --------------------------------------------------------------------------
# the pool master
# --------------------------------------------------------------------------


@pytest.mark.parametrize("build,pad", [
    (_batch_scheme, 0), (_batch_scheme, 1), (_ep_scheme, 0),
], ids=["batch_fill2", "batch_fill1_padded", "ep"])
def test_hit_is_bit_identical_to_an_uncached_encode(pool, build, pad):
    scheme = build()
    rng = np.random.default_rng(21)
    A, B = _operands(scheme, rng, pad=pad)
    A2, _ = _operands(scheme, rng, pad=pad)
    before = _counts(pool.master)
    C1, _ = pool.execute(scheme, A, B, timeout=120)  # miss: fills
    C2, _ = pool.execute(scheme, A2, B.copy(), timeout=120)  # hit
    np.testing.assert_array_equal(np.asarray(C1), _oracle(scheme, A, B))
    np.testing.assert_array_equal(np.asarray(C2), _oracle(scheme, A2, B))
    after = _counts(pool.master)
    assert (after[0] - before[0], after[1] - before[1]) == (2, 1)
    # the cached shares are the bytes an uncached encode gives
    (entry,) = _entries(pool.master, scheme)
    assert sorted(entry.shares) == list(range(scheme.N))
    for i, gb in entry.shares.items():
        np.testing.assert_array_equal(gb, scheme.encode_b_at(B, i))


def test_weight_changed_in_place_misses(pool):
    scheme = _batch_scheme()
    rng = np.random.default_rng(22)
    A, B = _operands(scheme, rng)
    before = _counts(pool.master)
    pool.execute(scheme, A, B, timeout=120)
    B[0, 0, 0] += 1  # the caller updates its weight in place
    C, _ = pool.execute(scheme, A, B, timeout=120)
    np.testing.assert_array_equal(np.asarray(C), _oracle(scheme, A, B))
    after = _counts(pool.master)
    assert (after[0] - before[0], after[1] - before[1]) == (2, 0)


def test_full_and_padded_batches_of_one_weight_keep_two_entries(pool):
    scheme = _batch_scheme()
    rng = np.random.default_rng(23)
    A, B = _operands(scheme, rng)
    full = np.stack([B[0], B[0]])
    padded = np.stack([B[0], np.zeros_like(B[0])])
    before = _counts(pool.master)
    for Bs in (full, padded, full, padded):
        C, _ = pool.execute(scheme, A, Bs, timeout=120)
        np.testing.assert_array_equal(np.asarray(C), _oracle(scheme, A, Bs))
    assert len(_entries(pool.master, scheme)) == 2
    after = _counts(pool.master)
    assert (after[0] - before[0], after[1] - before[1]) == (4, 2)


def test_keyed_codeword_never_touches_the_cache():
    spec = ProblemSpec(t=8, r=8, s=8, n=1, ring=Z32, N=8, privacy_t=1)
    scheme = plan(spec).instantiate()
    A, B = _operands(scheme, np.random.default_rng(24))
    want = np.asarray(coded_matmul(A, B, scheme, backend="local", key=KEY))
    with LocalPool(workers=2) as fresh:
        for _ in range(2):
            C, _ = fresh.execute(scheme, A, B, key=KEY, timeout=120)
            np.testing.assert_array_equal(np.asarray(C), want)
        assert _counts(fresh.master) == (0, 0)
        assert _entries(fresh.master, scheme) == []
        # without its key the secure encode fails before any B share is
        # stored, and leaves no entry behind
        with pytest.raises(ValueError, match="requires a jax.random key"):
            fresh.execute(scheme, A, B, timeout=120)
        assert _entries(fresh.master, scheme) == []


def test_compute_error_retry_decodes_exactly_on_a_hit(pool):
    scheme = _batch_scheme()
    rng = np.random.default_rng(25)
    A, B = _operands(scheme, rng)
    pool.execute(scheme, A, B, timeout=120)  # fills the cache
    errors = pool.master.stats().get("pool_share_errors", 0)
    hits = _counts(pool.master)[1]
    # R shares only, blind round-robin over the three workers: the failing
    # worker holds one, and the decode needs its retry
    mask = np.arange(scheme.N) < scheme.R
    assert scheme.R >= 3
    pool.master.health.reset_scores()
    pool.master.task_fail_wids.add(pool.master.live_workers()[0])
    try:
        C, _ = pool.execute(scheme, A, B, mask=mask, timeout=120)
    finally:
        pool.master.task_fail_wids.clear()
    np.testing.assert_array_equal(np.asarray(C), _oracle(scheme, A, B))
    assert _counts(pool.master)[1] == hits + 1
    assert pool.master.stats().get("pool_share_errors", 0) > errors


def test_redispatch_after_sigkill_decodes_exactly_on_a_hit(wait_until):
    scheme = _batch_scheme()
    A, B = _operands(scheme, np.random.default_rng(26))
    oracle = _oracle(scheme, A, B)
    with LocalPool(workers=3) as fresh:
        master = fresh.master
        fresh.execute(scheme, A, B, timeout=120)  # warm and fill
        for wid in master.live_workers():
            master.task_delay_ms[wid] = 400.0
        sent = sum(r["tasks_sent"] for r in master.worker_reports().values())
        result = {}

        def request():
            try:
                result["C"], result["stats"] = fresh.execute(
                    scheme, A, B, timeout=120)
            except Exception as e:  # pragma: no cover - surfaced in assert
                result["err"] = e

        t = threading.Thread(target=request)
        t.start()
        wait_until(lambda: sum(r["tasks_sent"] for r in
                               master.worker_reports().values())
                   >= sent + scheme.N, "every share dispatched")
        assert len(fresh.kill(1)) == 1
        t.join(timeout=120)
        assert not t.is_alive(), "request hung after SIGKILL"
        assert "err" not in result, f"request failed: {result.get('err')!r}"
        np.testing.assert_array_equal(np.asarray(result["C"]), oracle)
        assert result["stats"].redispatched >= 1
        assert _counts(master) == (2, 1)
