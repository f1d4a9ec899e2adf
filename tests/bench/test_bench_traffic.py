"""The benchmark's traffic generator and warm fill, on the CPU at small
sizes."""
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import gen, harness

TRAFFIC = Path(harness.BENCH) / "traffic"


def _mix(name="read_only", **over):
    return {**json.loads((TRAFFIC / f"{name}.json").read_text()), **over}


def test_same_seed_same_stream_and_seeds_differ():
    mix = _mix()
    a = gen.key_array(2**31 + 11, mix)
    b = gen.key_array(2**31 + 11, mix)
    c = gen.key_array(2**31 + 12, mix)
    assert a.shape == (32768,) and a.dtype == np.uint32
    np.testing.assert_array_equal(a, b)
    assert (a != c).mean() > 0.5
    assert a.max() < mix["items"]


def test_draw_frequencies_match_zipf_pmf():
    """Gray et al.'s O(1) draw is exact for ranks 0 and 1 and approximate
    beyond: its total variation distance to the exact zipf(0.99) pmf over
    1024 records is about 0.018.  Tolerance: ranks 0 and 1 within 5
    standard errors, total variation under 0.03."""
    n, draws = 1024, 1 << 21
    u = np.random.default_rng(3).random(draws)
    r = gen.zipfian_ranks(u, n, 0.99, gen.zeta(n, 0.99))
    freq = np.bincount(np.minimum(r, n - 1), minlength=n) / draws
    p = gen.zipf_pmf(n, 0.99)
    for i in (0, 1):
        assert abs(freq[i] - p[i]) < 5 * np.sqrt(p[i] / draws)
    assert 0.5 * np.abs(freq - p).sum() < 0.03


def _fnv_scalar(v: int) -> int:
    h = 0xCBF29CE484222325
    for _ in range(8):
        h = ((h ^ (v & 0xFF)) * 1099511628211) & (2**64 - 1)
        v >>= 8
    h = h - 2**64 if h >= 2**63 else h
    return abs(h)


def test_fnvhash64_matches_the_byte_loop():
    vals = [0, 1, 2, 255, 256, 10**9 + 7, 10**10, 2**40 + 12345]
    got = gen.fnvhash64(np.array(vals, np.int64))
    assert [int(x) for x in got] == [_fnv_scalar(v) for v in vals]


def test_mix_zetan_is_zeta_of_its_ranks():
    """YCSB's precomputed zetan for 10^10 ranks at 0.99 agrees with the
    sum to eleven digits."""
    mix = _mix()
    assert gen.zeta(mix["zipf_items"], mix["zipfian_constant"]) \
        == pytest.approx(mix["zetan"], rel=1e-11)


def test_zeta_matches_direct_sum_above_the_exact_range():
    n = (1 << 20) + 4097
    direct = float(np.sum(np.arange(1, n + 1, dtype=np.float64) ** -0.99))
    assert gen.zeta(n, 0.99) == pytest.approx(direct, rel=1e-12)


def test_most_popular_key_is_the_hash_of_rank_zero():
    """Rank 0 is drawn with probability 1 / zetan, and becomes the key
    fnvhash64(0) % items."""
    mix = _mix(keys=1 << 18)
    keys = gen.key_array(5, mix)
    head = int(gen.fnvhash64(np.zeros(1, np.int64))[0] % mix["items"])
    counts = np.bincount(keys, minlength=mix["items"])
    assert counts.argmax() == head
    assert counts[head] / keys.size >= 1 / mix["zetan"] - 5 * np.sqrt(1 / mix["zetan"] / keys.size)


def test_cycled_wraps_around():
    k = np.arange(10, dtype=np.uint32)
    np.testing.assert_array_equal(gen.cycled(k, 8, 5), [8, 9, 0, 1, 2])


@pytest.mark.parametrize("sets,keys", [(256, 1 << 13), (64, 1 << 11)])
def test_warm_fill_is_clean_and_matches_the_reference(sets, keys):
    conf = {"backend": "jnp", "num_sets": sets, "ways": 8, "policy": "LRU",
            "seed": 0x51CA}
    system = harness.System(conf)
    ks = gen.key_array(9, _mix(keys=keys, items=keys // 3))
    chunks = harness.fill_chunks(ks, 256)
    state, evictions = system.fill(jax.device_put(chunks))
    assert system.check(state) == 0
    ref = harness.load_module("refs", "flat")
    want, want_evs = harness.reference_fill(ref, conf, chunks)
    assert int(evictions) == want_evs
    assert harness.slot_mismatches(harness.System.lanes(state), want.lanes()) == 0
    assert int(state.occupancy()) == int((want.keys != ref.EMPTY).sum()) > 0
