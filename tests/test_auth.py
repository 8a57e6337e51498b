"""Capability authentication: issue/verify, forgery rejection, np/jnp parity."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # fall back to the deterministic local shim
    from _hypothesis_shim import given, settings
    from _hypothesis_shim import strategies as st

from repro.core.auth import (
    CAP_WORDS,
    TAG_WORDS,
    Capability,
    CapabilityAuthority,
    Rights,
    _mac_ints,
    sponge_mac,
)

AUTH = CapabilityAuthority(b"0123456789abcdef")


def _cap(**kw):
    base = dict(client_id=7, object_id=42, offset=0, length=1 << 20,
                rights=int(Rights.READ | Rights.WRITE), expiry=2_000_000_000)
    base.update(kw)
    return AUTH.issue(**base)


def test_verify_happy_path():
    cap = _cap()
    assert AUTH.verify(cap, now=1_700_000_000, op_rights=Rights.WRITE,
                       offset=100, length=50, client_id=7)


def test_verify_rejects_expiry_rights_extent_identity():
    cap = _cap()
    assert not AUTH.verify(cap, now=2_100_000_000, op_rights=Rights.WRITE)
    assert not AUTH.verify(cap, now=1, op_rights=Rights.DELETE)
    assert not AUTH.verify(cap, now=1, op_rights=Rights.READ,
                           offset=1 << 20, length=1)
    assert not AUTH.verify(cap, now=1, op_rights=Rights.READ, client_id=8)


@given(st.integers(min_value=0, max_value=CAP_WORDS - 1),
       st.integers(min_value=0, max_value=31))
@settings(max_examples=50, deadline=None)
def test_any_field_bitflip_is_forgery(word, bit):
    cap = _cap()
    words = cap.words().copy()
    words[word] ^= np.uint32(1 << bit)
    forged_tag = sponge_mac(words, AUTH.key)
    assert (int(forged_tag[0]), int(forged_tag[1])) != cap.tag


def test_wrong_key_rejected():
    cap = _cap()
    other = CapabilityAuthority(b"fedcba9876543210")
    assert not other.verify(cap, now=1, op_rights=Rights.READ)


def test_pack_unpack_roundtrip():
    cap = _cap(nonce=12345)
    assert Capability.unpack(cap.pack()) == cap
    assert len(cap.pack()) == Capability.PACKED_SIZE == 48


@given(st.lists(st.integers(min_value=0, max_value=2**32 - 1),
                min_size=CAP_WORDS, max_size=CAP_WORDS))
@settings(max_examples=25, deadline=None)
def test_np_jnp_mac_parity(words):
    w = np.array(words, dtype=np.uint32)
    t_np = sponge_mac(w, AUTH.key, xp=np)
    t_j = np.asarray(sponge_mac(jnp.asarray(w), jnp.asarray(AUTH.key), xp=jnp))
    assert np.array_equal(t_np, t_j)


@pytest.mark.parametrize("nwords", [1, 2, CAP_WORDS, 16, 19])
def test_scalar_batched_jnp_mac_parity(nwords):
    """One vector runs on Python ints, a stack of them on numpy's vector
    rounds, a jnp array on the traced rounds: the same tag, bit for bit."""
    rng = np.random.default_rng(1000 + nwords)
    keys = rng.integers(0, 2**32, (4, 4), dtype=np.uint64).astype(np.uint32)
    for key in keys:
        words = rng.integers(0, 2**32, (8, nwords),
                             dtype=np.uint64).astype(np.uint32)
        scalar = np.stack([sponge_mac(w, key) for w in words])
        assert scalar.dtype == np.uint32 and scalar.shape == (8, TAG_WORDS)
        assert np.array_equal(sponge_mac(words, key), scalar)
        traced = sponge_mac(jnp.asarray(words), jnp.asarray(key), xp=jnp)
        assert np.array_equal(np.asarray(traced), scalar)
        assert [_mac_ints(w.tolist(), key.tolist()) for w in words] == [
            tuple(t) for t in scalar.tolist()]


def test_verify_checks_the_mac_every_time():
    cap = _cap()
    checks = AUTH.verifications
    for _ in range(3):
        assert AUTH.verify(cap, now=1, op_rights=Rights.WRITE)
    forged = dataclasses.replace(cap, tag=(cap.tag[0], cap.tag[1] ^ 1))
    assert not AUTH.verify(forged, now=1, op_rights=Rights.WRITE)
    assert AUTH.verify(cap, now=1, op_rights=Rights.WRITE)
    assert AUTH.verifications == checks + 5
    assert np.array_equal(cap.words(), cap.words())
    assert cap.words() is not cap.words()


def test_bulk_verify_kernel():
    from repro.kernels import ops

    caps = [_cap(client_id=i, nonce=i) for i in range(16)]
    w = np.stack([c.words() for c in caps])
    t = np.array([c.tag for c in caps], dtype=np.uint32)
    ok = np.asarray(ops.bulk_verify(jnp.asarray(w), jnp.asarray(t),
                                    jnp.asarray(AUTH.key)))
    assert ok.all()
    t[3, 1] ^= 1
    ok2 = np.asarray(ops.bulk_verify(jnp.asarray(w), jnp.asarray(t),
                                     jnp.asarray(AUTH.key)))
    assert not ok2[3] and ok2.sum() == 15
