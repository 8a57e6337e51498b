"""SizeDist property tests: empirical-mean bounds + seed determinism.

Until now ``SizeDist`` was only exercised indirectly through mixed
scenarios; these pin its contract directly:

(a) lognormal — the mu-correction makes the *empirical* mean track the
    configured ``mean`` (within sampling tolerance) across means and
    sigmas, and every draw respects [min_bytes, max_bytes]
(b) bimodal — draws take exactly the two configured values and the
    empirical large-fraction tracks ``p_large``
(c) fixed — always exactly ``mean``
(d) determinism — equal seeds give identical draw sequences, different
    seeds diverge (the workload engine's reproducibility rests on this)
"""

import math
import random

import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # deterministic dependency-free fallback
    from _hypothesis_shim import given, settings
    from _hypothesis_shim import strategies as st

from repro.sim.workload import KiB, SizeDist

N = 4000


def _samples(dist, seed=0, n=N):
    rnd = random.Random(seed)
    return [dist.sample(rnd) for _ in range(n)]


# -- (a) lognormal -----------------------------------------------------------


@settings(max_examples=18, deadline=None)
@given(
    st.sampled_from([16, 64, 256]),            # mean (KiB)
    st.sampled_from([0.25, 0.6, 1.0]),         # sigma
    st.integers(min_value=0, max_value=2**31),  # sample seed
)
def test_lognormal_empirical_mean_tracks_config(mean_kib, sigma, seed):
    mean = mean_kib * KiB
    dist = SizeDist("lognormal", mean=mean, sigma=sigma,
                    max_bytes=64 << 20)  # keep the tail unclamped
    xs = _samples(dist, seed)
    emp = sum(xs) / len(xs)
    # the mu = log(mean) - sigma^2/2 correction keeps the expectation at
    # ``mean``; at sigma=1.0 the heavy tail needs the widest band
    assert 0.8 * mean <= emp <= 1.25 * mean


def test_lognormal_respects_bounds():
    dist = SizeDist("lognormal", mean=64 * KiB, sigma=2.0,
                    min_bytes=1024, max_bytes=128 * KiB)
    xs = _samples(dist, seed=9)
    assert min(xs) >= 1024
    assert max(xs) <= 128 * KiB
    # a sigma this heavy actually exercises both clamps
    assert 1024 in xs and 128 * KiB in xs


# -- (b) bimodal -------------------------------------------------------------


@settings(max_examples=16, deadline=None)
@given(
    st.sampled_from([0.05, 0.125, 0.5, 0.9]),  # p_large
    st.integers(min_value=0, max_value=2**31),  # sample seed
)
def test_bimodal_mixture_fraction(p_large, seed):
    dist = SizeDist("bimodal", small=4 * KiB, large=256 * KiB,
                    p_large=p_large)
    xs = _samples(dist, seed)
    assert set(xs) <= {4 * KiB, 256 * KiB}
    large = sum(x == 256 * KiB for x in xs)
    frac = large / len(xs)
    # the count of large draws is binomial: 5 sigma is a false alarm once
    # in ~10^6 examples, and tighter than a fixed band at every p_large
    assert abs(frac - p_large) < 5 * math.sqrt(
        p_large * (1 - p_large) / len(xs))
    # with two sizes the empirical mean is fixed by the observed fraction
    assert sum(xs) == large * 256 * KiB + (len(xs) - large) * 4 * KiB


# -- (c) fixed ---------------------------------------------------------------


def test_fixed_is_exact():
    dist = SizeDist("fixed", mean=96 * KiB)
    assert set(_samples(dist, seed=1, n=64)) == {96 * KiB}


def test_unknown_kind_raises():
    with pytest.raises(ValueError):
        SizeDist("zipf").sample(random.Random(0))


# -- (d) seed determinism ----------------------------------------------------


@settings(max_examples=16, deadline=None)
@given(
    st.sampled_from(["lognormal", "bimodal"]),
    st.integers(min_value=0, max_value=2**31),  # shared seed
)
def test_same_seed_same_draws(kind, seed):
    dist = SizeDist(kind, mean=64 * KiB)
    assert _samples(dist, seed, n=256) == _samples(dist, seed, n=256)


def test_different_seeds_diverge():
    dist = SizeDist("lognormal", mean=64 * KiB)
    assert _samples(dist, 0, n=256) != _samples(dist, 1, n=256)
