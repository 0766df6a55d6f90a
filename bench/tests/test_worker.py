"""The warm-up's shape buckets."""
from bench.worker import pow2_at_least, warm_buckets


def _bucket(rows, pages, used):
    return (pow2_at_least(rows), pow2_at_least(pages), pow2_at_least(used))


def test_buckets_cover_every_reachable_step():
    """Every step of up to 8 rows of 1-5 pages each, no page shared,
    within a pool of 24 pages, lands in a warmed bucket."""
    warmed = set(warm_buckets(max_rows=8, min_pages=1, max_pages=5,
                              num_blocks=24))
    for rows in range(1, 9):
        for longest in range(1, 6):
            for used in range(max(rows, longest),
                              min(rows * longest, 24) + 1):
                assert _bucket(rows, longest, used) in warmed


def test_buckets_respect_the_bounds():
    got = warm_buckets(max_rows=16, min_pages=32, max_pages=130,
                       num_blocks=4096)
    assert {r for r, _, _ in got} == {2, 4, 8, 16}
    assert {nb for _, nb, _ in got} == {32, 64, 128, 256}
    assert all(max(r, nb) <= p <= r * nb for r, nb, p in got)
