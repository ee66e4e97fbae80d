"""``weight_hit_share`` reads the master's weight-cache counters over the
window, and reports nothing where the window made no lookup."""
from types import SimpleNamespace

import pytest

from perfbench import cells


def _run(before, after):
    return SimpleNamespace(pool={"before": before, "after": after})


@pytest.mark.parametrize("before,after,want", [
    ({"pool_weight_lookups": 1, "pool_weight_hits": 0},
     {"pool_weight_lookups": 41, "pool_weight_hits": 40}, 100.0),
    ({"pool_weight_lookups": 2, "pool_weight_hits": 1},
     {"pool_weight_lookups": 6, "pool_weight_hits": 4}, 75.0),
    ({"pool_weight_lookups": 3, "pool_weight_hits": 2},
     {"pool_weight_lookups": 3, "pool_weight_hits": 2}, None),
    ({"pool_requests": 1}, {"pool_requests": 9}, None),
], ids=["all_hits", "some_misses", "no_lookup_in_window", "no_counters"])
def test_weight_hit_share(before, after, want):
    got = cells.reader("weight_hit_share")(_run(before, after))
    assert got == (None if want is None else pytest.approx(want))
