"""The benchmark's traffic generators and warm fill, on the CPU at small
sizes."""
import hashlib
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
    system = harness.load_module("systems", "kway").System(conf, jax.devices())
    ks = gen.key_array(9, _mix(keys=keys, items=keys // 3))
    chunks = harness.fill_chunks(ks, 256)
    state, evictions = system.fill(chunks)
    assert system.check(state) == 0
    ref = harness.load_module("refs", "flat")
    want, want_evs = harness.reference_fill(ref, conf, chunks)
    assert int(evictions) == want_evs
    assert harness.slot_mismatches(system.lanes(state), want.lanes()) == 0
    assert system.occupancy(state) == int((want.keys != ref.EMPTY).sum()) > 0


def test_sequential_gives_min_value_plus_i_and_wraps():
    """``start`` = 2**31 is Java's ``Integer.MIN_VALUE`` as the cache's
    uint32 key; the stream wraps mod 2**32 and ignores the seed."""
    mix = {"generator": "sequential", "start": 2**31, "keys": 5}
    k = gen.key_array(2**31 + 11, mix)
    assert k.dtype == np.uint32
    assert list(k.view(np.int32)) == [-2**31 + i for i in range(5)]
    np.testing.assert_array_equal(k, gen.key_array(3, mix))
    wrap = gen.key_array(0, {"generator": "sequential", "start": 2**32 - 2, "keys": 4})
    assert list(wrap) == [2**32 - 2, 2**32 - 1, 0, 1]


def test_unknown_generator_is_an_error():
    with pytest.raises(ValueError, match="unknown generator 'uniform_imaginary'"):
        gen.key_array(1, {"generator": "uniform_imaginary", "keys": 4})


# sha256 of getput's key array (and so of its warm fill, the same array in
# order) as the harness drew it before mixes could carry a fill stream
GETPUT_DIGESTS = {
    7: "b265c6f78c154ca0ce7b0a3e7cf6c66ec607e58b42feee8847c9fe73d410acd4",
    2**31 + 5: "eb68007cdebf6014644b537c6c3addaa1a08a8372f0de7d6542b48f883607310",
    3_000_000_017: "477d85cdf2b98bedf1ee9c1cb45463ff680877857f9b3e46ace9869ee9152b17",
}


@pytest.mark.parametrize("seed", sorted(GETPUT_DIGESTS))
@pytest.mark.parametrize("name", ["read_only", "served"])
def test_getput_keys_and_fill_are_pinned(name, seed):
    """A mix without a ``fill`` stream fills with its own key array, every
    key once, in order: getput's cells set up bit for bit as before."""
    mix = _mix(name)
    assert "fill" not in mix
    keys = gen.key_array(seed, mix)
    chunks = harness.fill_chunks(gen.fill_keys(seed, mix, keys), mix["batch"])
    assert chunks.shape == (8, 4096) and chunks.dtype == np.uint32
    assert hashlib.sha256(keys.tobytes()).hexdigest() == GETPUT_DIGESTS[seed]
    assert hashlib.sha256(chunks.tobytes()).hexdigest() == GETPUT_DIGESTS[seed]


def test_put_new_fills_with_its_own_stream():
    """``put_new`` prepopulates with ``Integer.MIN_VALUE + i`` and then
    sends keys from 0: the two streams share no key, and neither reaches
    the empty-way sentinel that ``sanitize`` folds."""
    mix = _mix("put_new")
    keys = gen.key_array(2**31 + 11, mix)
    fill = gen.fill_keys(2**31 + 11, mix, keys)
    assert keys.size == 2**25 and fill.size == 2**24
    assert fill[0] == 2**31 and fill[-1] == 2**31 + 2**24 - 1
    assert keys[0] == 0 and keys[-1] == 2**25 - 1
    assert int(keys.max()) < int(fill.min())
    assert int(fill.max()) < 0xFFFFFFFF
    assert harness.fill_chunks(fill, mix["batch"]).shape == (4096, 4096)


def test_put_new_served_sends_put_new_keys_through_the_served_client():
    """``evict.served`` fills and sends exactly what ``evict.put_new`` does,
    batch by batch through ``access`` in place of ``replay``."""
    served, replayed = _mix("put_new_served"), _mix("put_new")
    assert served["client"] == "closed_loop" and replayed["client"] == "replay"
    keep = {"about", "client"}
    assert ({k: v for k, v in served.items() if k not in keep}
            == {k: v for k, v in replayed.items() if k not in keep})
