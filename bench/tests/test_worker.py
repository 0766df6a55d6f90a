"""The surrogate's warm-up shape buckets."""
from bench.models.surrogate import (draw_weights, pow2_at_least, seeded,
                                    warm_buckets, warm_up)


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


def test_a_backend_with_its_own_warm_up_is_asked():
    """A backend that has ``warm_up`` compiles its own shapes: the module
    passes the bounds and reports its count."""
    class Backend:
        def warm_up(self, **bounds):
            self.bounds = bounds
            return 7

    b, bounds = Backend(), {"max_rows": 4, "min_pages": 1, "max_pages": 2,
                            "num_blocks": 8}
    assert warm_up(b, bounds) == {"warm_shapes": 7}
    assert b.bounds == bounds


def test_seeded_backend_draws_the_runs_weights():
    """``seeded`` hands the run's seed (past 32 bits) to the program's
    backend, whose weights then equal the reference's draw; the
    constructor is restored afterwards."""
    import numpy as np
    from repro.backend.surrogate import PagedSurrogateBackend as P
    init = P.__init__
    seed = 2**33 + 17
    b = seeded(lambda name, **kw: P(**kw), seed, "cpu",
               dict(block_size=16, num_blocks=4, n_heads=4, n_kv_heads=2,
                    head_dim=16, vocab=300))
    assert P.__init__ is init
    cfg = {"num_attention_heads": 4, "num_key_value_heads": 2,
           "head_dim": 16, "vocab_size": 300}
    wt = draw_weights(seed, cfg, [3])
    np.testing.assert_array_equal(wt.wo, b._wo)
    np.testing.assert_array_equal(wt.embed[wt.rows[3]], b._embed[3])
