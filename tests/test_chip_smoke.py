"""``chip_smoke.py``'s control flow on the CPU, and the data plane's
platform default.

The smoke run needs a TPU; here its phases run at a tiny size with the
data plane forced onto the Pallas kernels (interpret mode), so a broken
phase, check or result line shows up on every change.  The platform
resolver is checked by steering ``ops._on_tpu``: on a TPU the storage
entry points and ``CheckpointManager`` must reach the kernel path
without the caller naming a backend, and numpy elsewhere.
"""

import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager, CheckpointPolicy
from repro.checkpoint.storage import StorageCluster
from repro.core import erasure, gf256
from repro.kernels import ops

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import chip_smoke  # noqa: E402


def test_smoke_phases_on_cpu(monkeypatch, capsys):
    import repro.bench

    monkeypatch.setattr(ops, "dataplane_backend",
                        lambda backend=None: backend or "jax")
    monkeypatch.setattr(repro.bench, "setup_compile_cache", lambda: "off")
    result = chip_smoke.main(["--seed", "5"], allow_cpu=True,
                             total_bytes=150_000, cell=4096)
    out = capsys.readouterr().out.strip().splitlines()
    assert json.loads(out[-1]) == result
    assert result == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    text = "\n".join(out)
    for line in ("data-plane backend jax", "cells bit-exact vs RSCode(6,3)",
                 "degraded read", "'lost_bytes': 0", "post-repair read",
                 "phase B: checkpoint of 5 leaves", "restore: 5 leaves",
                 "encode program (3x6 GF matrix", "decode program (6x6"):
        assert line in text, line


def test_smoke_refuses_a_host_without_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code == 2
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "checkout"])
def test_compile_cache_placement(monkeypatch, tmp_path, from_env):
    """``JAX_COMPILATION_CACHE_DIR`` wins and no other directory is set;
    without it the cache is ``<checkout>/.jax_cache``.  Either way source
    paths lose the checkout's root, so kernel programs key the same from
    any checkout."""
    import re

    import jax

    import repro.bench

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_hlo_source_file_canonicalization_regex")
    saved = {n: getattr(jax.config, n) for n in names}
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        where = repro.bench.setup_compile_cache()
        if from_env:
            assert where == str(tmp_path)
            assert (jax.config.jax_compilation_cache_dir
                    == saved["jax_compilation_cache_dir"])
        else:
            assert where == os.path.join(root, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == where
        pattern = jax.config.jax_hlo_source_file_canonicalization_regex
        assert (re.sub(pattern, "", os.path.abspath(ops.__file__))
                == os.path.join("src", "repro", "kernels", "ops.py"))
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)


def test_dataplane_backend_follows_platform(monkeypatch):
    assert ops.dataplane_backend() == "numpy"
    assert ops.dataplane_backend("jax") == "jax"
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    assert ops.dataplane_backend() == "jax"
    assert ops.dataplane_backend("numpy") == "numpy"


def test_served_path_reaches_kernels_on_tpu(monkeypatch):
    """With the platform a TPU, write / degraded read / repair and a
    checkpoint save / restore all dispatch the fused kernel path with no
    backend named (the kernel itself is stubbed by the numpy LUT matmul,
    which cannot compile for a TPU here)."""
    calls = []

    def kernel_stub(coeffs, data):
        coeffs, data = np.asarray(coeffs, np.uint8), np.asarray(data, np.uint8)
        calls.append(coeffs.shape)
        s, k, length = data.shape
        out = gf256.gf_matmul(coeffs, data.transpose(1, 0, 2).reshape(k, -1))
        return jnp.asarray(out.reshape(-1, s, length).transpose(1, 0, 2))

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(ops, "gf_matmul_bytes_batched", kernel_stub)
    assert erasure._backend(None) == "jax"

    rng = np.random.default_rng(0)
    cluster = StorageCluster(8, node_capacity=1 << 20)
    blobs = [rng.bytes(3000) for _ in range(4)]
    layouts = cluster.write_object_bulk(blobs, k=3, m=2)
    assert calls == [(2, 3)]
    cluster.fail_node(layouts[0].data_coords[0].node)
    assert cluster.read_objects(layouts) == blobs
    assert (3, 3) in calls
    del calls[:]
    cluster.repair_node(layouts[0].data_coords[0].node)
    assert calls and cluster.audit()["lost_bytes"] == 0

    del calls[:]
    mgr = CheckpointManager(cluster, CheckpointPolicy(k=3, m=2,
                                                      stripe_bytes=4096))
    tree = {"w": np.arange(3000, dtype=np.float32)}
    mgr.save(1, tree, blocking=True)
    assert (2, 3) in calls
    stripe = mgr._manifests[1]["leaves"][0]["stripes"][0]
    cluster.fail_node(cluster.meta.lookup(stripe["oid"]).data_coords[0].node)
    del calls[:]
    assert np.array_equal(mgr.restore(1)["w"], tree["w"])
    assert (3, 3) in calls
