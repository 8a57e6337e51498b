"""The correctness control and the planted faults.

The benchmark's own runs never install any of these.  ``control.py``
runs a cell with one of them in place on the chip, and the tests run
every cell with each fault its kind can have (``FAULTS`` of
``kinds/<op>.py``) at a tiny size on the CPU; in every case the check
must judge the run not correct.

* ``control``: the plain reference put in the program's coding layer,
  breaking the configuration's durability guarantee: it stores a code
  of RS(k, m-1) strength (the last parity cell is left zero) and, as
  "any k of k + m" allows, rebuilds from the last k shards present.
* ``unchanged_store``: every shard write is acknowledged and nothing is
  stored (a step that leaves its state unchanged).
* ``unchanged_decode``: the decoder hands back the surviving shards
  without solving for the missing ones.
* ``half_batch``: encode and decode compute the first half of their
  batch (stripes x bytes) and leave the rest zero.
* ``altered_answer``: encode and decode flip one bit of their output,
  where the answer is produced.

One chip has no exchange between chips to leave out.  Each fault is
installed before the cell's set-up, so the whole run serves through the
broken path; :func:`install` returns the function that takes it out.
"""

from __future__ import annotations

import numpy as np

from chipbench.reference import RS

NAMES = ("control", "unchanged_store", "unchanged_decode", "half_batch",
         "altered_answer")


def _reference_codec(poly: int):
    def encode_stripes(self, data, backend=None):
        ref = RS(self.k, self.m, poly)
        data = np.asarray(data, np.uint8)
        out = np.stack([ref.encode(stripe) for stripe in data]) if len(data) \
            else np.zeros((0, self.m, data.shape[2]), np.uint8)
        out[:, -1] = 0
        return out

    def decode_stripes(self, shards, backend=None):
        ref = RS(self.k, self.m, poly)
        present = [i for i, s in enumerate(shards) if s is not None]
        if len(present) < self.k:
            raise ValueError(f"unrecoverable: {len(present)} shards present")
        if all(shards[i] is not None for i in range(self.k)):
            return np.stack([np.asarray(shards[i], np.uint8)
                             for i in range(self.k)], axis=1)
        rows = present[-self.k:]
        count = len(shards[rows[0]])
        return np.stack([ref.decode([np.asarray(shards[r][s], np.uint8)
                                     for r in rows], rows)
                         for s in range(count)])

    return encode_stripes, decode_stripes


def _damaged(fn, damage):
    def wrapper(self, *args, **kwargs):
        out = np.array(fn(self, *args, **kwargs), np.uint8)
        if out.size:
            damage(out.reshape(-1))
        return out

    return wrapper


def _zero_second_half(flat):
    flat[flat.size // 2:] = 0


def _flip_one_bit(flat):
    flat[0] ^= 1


def install(name: str, config: dict):
    """Put fault ``name`` in place; returns the undo function."""
    from repro.core.erasure import RSCode
    from repro.core.handlers import StorageTarget

    if name not in NAMES:
        raise KeyError(f"unknown fault {name!r}: {NAMES}")
    patches = []
    if name == "control":
        enc, dec = _reference_codec(config["field_polynomial"])
        patches = [(RSCode, "encode_stripes", enc),
                   (RSCode, "decode_stripes", dec)]
    elif name == "unchanged_store":
        def write(self, addr, data):
            self.bytes_written += int(np.asarray(data).size)

        patches = [(StorageTarget, "write", write)]
    elif name == "unchanged_decode":
        orig = RSCode.decode_stripes

        def decode_stripes(self, shards, backend=None):
            present = [i for i, s in enumerate(shards) if s is not None]
            if all(shards[i] is not None for i in range(self.k)):
                return orig(self, shards, backend)
            return np.stack([np.asarray(shards[i], np.uint8)
                             for i in present[:self.k]], axis=1)

        patches = [(RSCode, "decode_stripes", decode_stripes)]
    else:
        damage = _zero_second_half if name == "half_batch" else _flip_one_bit
        patches = [(RSCode, attr, _damaged(getattr(RSCode, attr), damage))
                   for attr in ("encode_stripes", "decode_stripes")]
    undo = [(owner, attr, owner.__dict__[attr]) for owner, attr, _ in patches]
    for owner, attr, fn in patches:
        setattr(owner, attr, fn)

    def uninstall():
        for owner, attr, fn in reversed(undo):
            setattr(owner, attr, fn)

    return uninstall
