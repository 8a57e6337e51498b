"""``op: save``: each operation makes the configuration's checkpoint
state on the device afresh from (seed, step), the stand-in for a
training step, then saves it with
``CheckpointManager.save(step, tree, blocking=True)``.

The state is the configuration's ``state``: every leaf of ``leaves``
(its published shape; ``{i}`` in a path with ``repeat`` counts layers)
in each of ``groups`` (parameters and optimizer moments), in ``dtype``,
with dimension 0 divided by ``fsdp_chips``: the rows one chip holds when
that many chips share every leaf.
"""

import numpy as np

from chipbench.cells import Cell, jax_key

FAULTS = ("control", "unchanged_store", "unchanged_decode", "half_batch",
          "altered_answer")


def leaves(state: dict) -> list[tuple[str, tuple, str]]:
    """(path, shape on this chip, dtype) of every leaf of the state."""
    chips = state["fsdp_chips"]
    out = []
    for group in state["groups"]:
        for leaf in state["leaves"]:
            shape = list(leaf["shape"])
            if shape[0] % chips:
                raise ValueError(f"{leaf['path']}: {shape[0]} rows do not "
                                 f"divide over {chips} chips")
            shape[0] //= chips
            for i in range(leaf.get("repeat", 1)):
                out.append((f"{group}/{leaf['path'].format(i=i)}",
                            tuple(shape), state["dtype"]))
    return out


class Kind(Cell):
    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        self.leaves = leaves(self.config["state"])

        def make(key):
            keys = jax.random.split(key, len(self.leaves))
            return {path: jax.random.normal(kk, shape, jnp.dtype(dtype))
                    for kk, (path, shape, dtype) in zip(keys, self.leaves)}

        self._make = jax.jit(make)
        self._key = jax_key(self.seed)
        self.stripe_bytes = self.config["stripe_bytes"]
        sizes = [int(np.prod(shape)) * jnp.dtype(dtype).itemsize
                 for _, shape, dtype in self.leaves]
        self.state_bytes = sum(sizes)
        # each node holds at most one shard of every stripe object
        per_node = sum(self.chunk(min(self.stripe_bytes, n - off))
                       for n in sizes for off in range(0, n, self.stripe_bytes))
        stored = (self.k + self.m) * per_node
        self.max_saves = max(1, self.traffic["cluster_bytes"] // stored)
        self.node_bytes = self.max_saves * per_node
        self.steps: list[int] = []
        self._new_manager()
        with self.phase("warm-up"):
            self.warm(self.op, -1)      # every shape of the window

    def _new_manager(self) -> None:
        from repro.checkpoint.manager import CheckpointManager, CheckpointPolicy
        from repro.checkpoint.storage import StorageCluster

        self.cluster = StorageCluster(self.config["nodes"],
                                      node_capacity=self.node_bytes)
        self.mgr = CheckpointManager(self.cluster, CheckpointPolicy(
            k=self.k, m=self.m, stripe_bytes=self.stripe_bytes))

    def tree(self, step: int):
        import jax

        return self._make(jax.random.fold_in(self._key, step))

    def op(self, i: int) -> int:
        import jax

        step = i + 1
        tree = jax.block_until_ready(self.tree(step))
        self.mgr.save(step, tree, blocking=True)
        if self.mgr.latest_step() != step:
            raise OSError(f"save of step {step} did not complete")
        self.steps.append(step)
        return self.state_bytes

    def full(self) -> bool:
        return len(self.steps) + 1 > self.max_saves

    def rotate(self) -> None:
        self.verify()
        self._new_manager()
        self.steps = []

    def verify(self) -> None:
        """The last save's stripe objects, a seeded sample of
        ``checked_objects`` with the largest and the last among them,
        shard by shard against the reference; then the last save and a
        seeded earlier one restored with m nodes down, so that stripes
        need every parity cell, each leaf against the device array it
        was saved from."""
        if not self.steps:
            return
        arrays = {step: {p: np.asarray(x) for p, x in self.tree(step).items()}
                  for step in self.picks()}
        self.check_stored(self.steps[-1], arrays[self.steps[-1]])
        nodes = self.config["nodes"]
        first = int(self.rng.integers(nodes))
        for node in range(first, first + self.m):
            self.cluster.fail_node(node % nodes)
        bad = 0
        for step, want in sorted(arrays.items()):
            try:
                got = self.mgr.restore(step)
            except (OSError, ValueError):
                bad += len(want)
                continue
            for path, w in want.items():
                g = got.get(path)
                bad += (g is None or g.dtype != w.dtype or g.shape != w.shape
                        or g.tobytes() != w.tobytes())
        self.count("restored_leaves_wrong", bad)

    def picks(self) -> set[int]:
        """The last save and ``checked_saves`` - 1 seeded earlier ones."""
        n = min(self.traffic["checked_saves"], len(self.steps))
        out = {self.steps[-1]}
        if n > 1:
            out |= {int(s) for s in self.rng.choice(
                self.steps[:-1], n - 1, replace=False)}
        return out

    def check_stored(self, step: int, want: dict) -> None:
        # the save's manifest is the manager's own record of which stripe
        # object holds which bytes of which leaf
        manifest = self.mgr._manifests[step]
        objects = [(leaf["path"], j * self.stripe_bytes, s["size"], s["oid"])
                   for leaf in manifest["leaves"]
                   for j, s in enumerate(leaf["stripes"])]
        n = min(self.traffic["checked_objects"], len(objects))
        largest = max(range(len(objects)), key=lambda p: objects[p][2])
        sample = {largest, len(objects) - 1}
        rest = [p for p in range(len(objects)) if p not in sample]
        sample |= {int(p) for p in self.rng.choice(
            rest, max(0, min(n - len(sample), len(rest))), replace=False)}
        sample = sorted(sample)

        def shards(p):
            path, off, size, _ = objects[sample[p]]
            raw = np.frombuffer(want[path].tobytes(), np.uint8)
            return self.stripe(raw[off:off + size])

        layouts = [self.cluster.meta.lookup(objects[p][3]) for p in sample]
        self.check_objects(self.cluster, layouts, shards,
                           self.traffic["readback_objects"])
