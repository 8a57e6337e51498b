"""The one traffic generator.  A traffic file's ``op`` names the kind of
operation, ``kinds/<op>.py`` beside this package, found by that name as
a metric's reader is; the traffic file's other keys give the sizes, and
the configuration file the deployment.

A kind's module defines ``Kind``, a :class:`Cell`, and ``FAULTS``, the
planted faults of :mod:`chipbench.faults` that it can have.  A cell
builds its state from the seed (``setup``, warm-up included), serves
operation ``i`` of the window (``op``, which returns the user bytes it
moved and raises when it fails), and after the window compares what the
served path produced with the plain reference (``verify``); a traced
run reads its counters at both ends of each window piece
(``counters``).  Write kinds move to a fresh cluster of the same
configuration when their byte budget fills (:meth:`Cell.full`,
:meth:`Cell.rotate`): the program's metadata service never frees an
extent, so host memory would otherwise grow with the speed of the
system.  The harness stops the window's clock while a
cluster is retired and checked.
"""

from __future__ import annotations

import contextlib
import functools
import importlib.util
import os
import time
import traceback

import numpy as np

from chipbench.reference import RS, split

KINDS_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "kinds")


def jax_key(seed: int):
    """A JAX key from any whole-number seed (also past 32 bits)."""
    import jax

    word = np.random.SeedSequence(seed).generate_state(1, np.uint32)[0]
    return jax.random.key(int(word) & 0x7FFFFFFF)


def make_blobs(seed: int, count: int, nbytes: int) -> np.ndarray:
    """``count`` random objects of ``nbytes`` bytes, made on the device
    in one jitted call and brought to the host as one (count, nbytes)
    array."""
    import jax
    import jax.numpy as jnp

    gen = jax.jit(lambda key: jax.random.bits(key, (count, nbytes), jnp.uint8))
    return np.asarray(gen(jax_key(seed)))


class Cell:
    """The parts every kind shares: the configuration's code, cluster
    and reference, and the counts the check compares with their limits."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.k, self.m = config["k"], config["m"]
        self.rng = np.random.default_rng(seed)
        self.ref = RS(self.k, self.m, config["field_polynomial"])
        #: name -> count of wrong answers found so far; each limit is 0
        self.bad: dict[str, int] = {}
        #: (phase of set-up, seconds), printed by the harness
        self.phases: list[tuple[str, float]] = []

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        yield
        self.phases.append((name, time.perf_counter() - t0))

    def count(self, name: str, n: int) -> None:
        self.bad[name] = self.bad.get(name, 0) + int(n)

    def warm(self, fn, *args) -> None:
        """One warm-up operation of set-up; a failure is counted (and
        judges the run not correct) instead of ending the run."""
        try:
            fn(*args)
        except Exception:  # counted like a failed operation of the window
            traceback.print_exc()
            self.count("warm_up_failures", 1)

    def new_cluster(self, objects: int, object_bytes: int):
        """A cluster of the configuration with room for ``objects``
        objects of ``object_bytes`` bytes: round-robin placement spreads
        them evenly, plus one full placement cycle of slack per node."""
        from repro.checkpoint.storage import StorageCluster

        nodes = self.config["nodes"]
        per_node = -(-objects * (self.k + self.m) // nodes) + self.k + self.m
        return StorageCluster(nodes, node_capacity=per_node * self.chunk(
            object_bytes))

    def chunk(self, object_bytes: int) -> int:
        """Bytes of each of the k + m shards of an object."""
        cell = -(-object_bytes // self.k)
        return -(-cell // 32) * 32

    def setup(self) -> None:
        raise NotImplementedError

    def op(self, i: int) -> int:
        raise NotImplementedError

    def full(self) -> bool:
        return False

    def rotate(self) -> None:
        raise NotImplementedError

    def counters(self):
        """The always-on counters of the cluster the window drives now,
        as a ``repro.trace.CounterRegistry``; read at both ends of each
        piece of a traced window.  A kind whose operations reach a layer
        with counters of its own returns a registry that adds them."""
        from repro.trace import dataplane_registry

        return dataplane_registry(self.cluster, getattr(self, "mgr", None))

    def verify(self) -> None:
        """Compare what the served path produced with the reference,
        counting wrong answers in :attr:`bad`."""
        raise NotImplementedError

    def check(self) -> dict[str, tuple[int, int]]:
        """``{name: (count, limit)}`` once the window has closed."""
        self.verify()
        return {name: (n, 0) for name, n in self.bad.items()}

    # -- shared checks ---------------------------------------------------

    def check_objects(self, cluster, layouts: list, want, readback: int
                      ) -> None:
        """Every shard of every object in ``layouts``, as the storage
        nodes hold it, against ``want(p)``, the k + m shards that object
        ``p`` must hold by the reference; then a seeded sample of
        ``readback`` objects, the last one among them, read shard by
        shard through the packet plane."""
        if not layouts:
            return

        def shards(p):
            layout = layouts[p]
            w = want(p)
            if w.shape[1] != layout.chunk_len:
                raise AssertionError(
                    f"object {layout.object_id}: cell {layout.chunk_len} B, "
                    f"the configuration stores {w.shape[1]} B")
            return zip(list(layout.data_coords) + list(layout.parity_coords), w)

        bad = 0
        for p in range(len(layouts)):
            for coord, w in shards(p):
                got = cluster.nodes[coord.node].read(coord.addr, w.size)
                bad += not np.array_equal(got, w)
        self.count("stored_shards_wrong", bad)
        n = min(readback, len(layouts))
        picks = set(self.rng.choice(len(layouts) - 1, n - 1, replace=False)
                    ) if n > 1 else set()
        picks.add(len(layouts) - 1)
        bad = 0
        for p in sorted(picks):
            for coord, w in shards(p):
                try:
                    got = cluster.client.read(cluster.capability, coord, w.size)
                except OSError:
                    got = None
                bad += got is None or not np.array_equal(got, w)
        self.count("readback_shards_wrong", bad)

    def stripe(self, blob: np.ndarray) -> np.ndarray:
        """The k + m shards the reference stores for ``blob``."""
        cells = split(blob, self.k)
        return np.concatenate([cells, self.ref.encode(cells)])


class DatasetCell(Cell):
    """A data set of the configuration's ``dataset_objects`` objects,
    written in set-up."""

    def write_dataset(self) -> None:
        self.object_bytes = self.traffic["object_bytes"]
        with self.phase("objects made on the device"):
            self.blobs = make_blobs(self.seed, self.config["dataset_objects"],
                                    self.object_bytes)
        self.cluster = self.new_cluster(len(self.blobs), self.object_bytes)
        with self.phase("data set written"):
            self.layouts = self.cluster.write_object_bulk(
                list(self.blobs), k=self.k, m=self.m)

    def slots_on(self, node: int) -> list:
        """Per object, the slot (0..k+m-1) it keeps on ``node``, or None."""
        out = []
        for lay in self.layouts:
            coords = list(lay.data_coords) + list(lay.parity_coords)
            out.append(next((j for j, c in enumerate(coords)
                             if c.node == node), None))
        return out


@functools.lru_cache(maxsize=None)
def kind(op: str):
    """The module ``kinds/<op>.py``."""
    path = os.path.join(KINDS_DIR, op + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no kind of operation {op!r} in kinds/")
    spec = importlib.util.spec_from_file_location("chipbench_kind_" + op, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def kind_names() -> list[str]:
    return sorted(f[:-3] for f in os.listdir(KINDS_DIR) if f.endswith(".py"))


def make(config: dict, traffic: dict, seed: int) -> Cell:
    return kind(traffic["op"]).Kind(config, traffic, seed)
