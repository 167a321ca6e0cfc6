"""The benchmark's traffic generator: the IRM's scrambled page space, its
Zipf popularity, determinism from the seed, and seeds beyond 32 bits."""
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import traffic_gen as tg  # noqa: E402

STREAM = dict(kind="irm", n_requests=20000, n_pages=500, zipf_s=1.1,
              scramble="fnv1a64", write_fraction=0.0, rate=100.0)


def fnv1a64_by_hand(value: int) -> int:
    h = 0xCBF29CE484222325
    for b in value.to_bytes(8, "little"):
        h = ((h ^ b) * 0x100000001B3) % 2**64
    return h


def test_fnv1a64_matches_the_byte_definition():
    vals = [0, 1, 255, 256, 32767, 2**40 + 7]
    assert tg.fnv1a64(np.array(vals)).tolist() == [fnv1a64_by_hand(v)
                                                   for v in vals]


def test_irm_pages_are_a_scrambled_zipf_over_the_page_space():
    """Every id lies in the page space, the map from rank to page is a
    bijection and the same for every seed, and the pages' request counts
    fall with rank as the Zipf law says."""
    n, n_pages = 200000, 500
    order = tg.page_of_rank(n_pages)
    assert sorted(order.tolist()) == list(range(n_pages))
    assert not np.array_equal(order, np.arange(n_pages))
    a = tg.irm_pages(tg.rng_for(7, 0), n, n_pages, 1.1)
    b = tg.irm_pages(tg.rng_for(8, 0), n, n_pages, 1.1)
    assert a.min() >= 0 and a.max() < n_pages
    for pages in (a, b):
        count = np.bincount(pages, minlength=n_pages)[order]   # by rank
        assert count[0] == count.max()
        pop = np.arange(1, n_pages + 1, dtype=np.float64) ** -1.1
        want = n * pop / pop.sum()
        assert abs(count[0] / want[0] - 1) < 0.02
        assert abs(count[:10].sum() / want[:10].sum() - 1) < 0.02
    assert not np.array_equal(a, b)


def test_trace_is_deterministic_and_takes_large_seeds():
    seed = 2**31 + 12345
    a = tg.make_trace(STREAM, seed)
    b = tg.make_trace(STREAM, seed)
    c = tg.make_trace(STREAM, seed + 1)
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
    assert not np.array_equal(a[0], c[0])
    pages, is_write, times = a
    assert pages.dtype == np.int32 and not is_write.any()
    assert np.all(np.diff(times) > 0)
    assert abs(times[-1] - 20000 / 100.0) < 0.1 * 200
