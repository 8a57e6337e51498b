"""``BENCHMARK.json`` against its files and limits, every cell's traffic
drawn from the seed alone, and the plain reference against the code's
definition."""

import hashlib
import json
import os

import numpy as np
import pytest

from chipbench import cells, harness, reference

BENCH = harness.load_bench()
WORKLOADS = [w["name"] for w in BENCH["workloads"]]


def test_benchmark_json_names_files_that_exist():
    root = os.path.dirname(os.path.dirname(harness.HERE))
    for path in BENCH["paths"]:
        assert os.path.isdir(os.path.join(root, path)), path
    assert os.path.exists(os.path.join(root, *BENCH["command"][1].split("/")))
    for conf in BENCH["configs"]:
        with open(os.path.join(root, conf["file"])) as f:
            config = json.load(f)
        assert config["name"] == conf["name"]
        assert config["reduced"] == conf["reduced"]
    for w in WORKLOADS:
        entry, config, traffic = harness.cell_files(BENCH, w)
        assert w == f"{entry['config']}.{entry['traffic']}"
        assert traffic["op"] in cells.kind_names()
        assert entry["chips"] == 1
        e2e = harness.cell_metrics(BENCH, w, False)
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
        assert harness.cell_metrics(BENCH, w, True)


def test_every_metric_has_a_reader_and_moves_a_reported_metric():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(harness.reader(m["name"]))
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in moved.get("workloads", WORKLOADS), (m["name"], w)


def test_a_full_check_fits_its_time():
    cells_max = 24
    runs = 2 + 14 * cells_max
    total = runs * (BENCH["run_seconds"] + 60) + cells_max * 2 * 90 + 1200
    assert total <= 43200
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("workload", WORKLOADS)
def test_every_cell_brings_its_tiny_sizes(workload, tiny):
    sizes = tiny(workload)
    _, config, traffic = harness.cell_files(BENCH, workload)
    assert set(sizes) - {"about"} == {"config", "traffic"}
    # each size updates a key the cell's own files have
    assert set(sizes["config"]) <= set(config)
    assert set(sizes["traffic"]) <= set(traffic)


def test_a_cell_without_tiny_sizes_is_named_with_the_file_to_add(tiny):
    with pytest.raises(FileNotFoundError,
                       match="add tests/chip_bench/tiny/no-such-cell.json"):
        tiny("no-such-cell")


def test_unknown_workload_is_refused():
    with pytest.raises(SystemExit):
        harness.cell_files(BENCH, "no-such-cell")


def _plan(cell) -> str:
    """Everything the traffic feeds the system, hashed."""
    h = hashlib.sha256()
    for name in ("pool", "blobs", "order", "keep", "failed", "first"):
        if hasattr(cell, name):
            h.update(np.asarray(getattr(cell, name)).tobytes())
    if hasattr(cell, "tree"):
        for step in (0, 1, 2):
            for leaf in cell.tree(step).values():
                h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("workload", WORKLOADS)
def test_traffic_is_drawn_from_the_seed_alone(workload, tiny):
    _, config, traffic = harness.cell_files(BENCH, workload)
    config.update(tiny(workload).get("config", {}))
    traffic.update(tiny(workload).get("traffic", {}))
    plans = []
    for seed in (2**31 + 5, 2**31 + 5, 2**31 + 6):
        cell = cells.make(dict(config), dict(traffic), seed)
        cell.setup()
        plans.append(_plan(cell))
    assert plans[0] == plans[1]
    assert plans[0] != plans[2]


def test_reference_code_matches_its_definition():
    rs = reference.RS(6, 3, 0x11D)
    # P[i][j] = 1 / ((k + i) XOR j): each entry times its divisor is 1
    for i in range(3):
        for j in range(6):
            assert reference.gf_mul(int(rs.parity[i, j]), (6 + i) ^ j,
                                    0x11D) == 1
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, (6, 4096), dtype=np.uint8)
    parity = rs.encode(data)
    # encoding is linear: the parity of a sum is the sum of the parities
    other = rng.integers(0, 256, (6, 4096), dtype=np.uint8)
    assert np.array_equal(rs.encode(data ^ other), parity ^ rs.encode(other))
    # one data byte: parity row i is that byte times P[i][j]
    unit = np.zeros((6, 2), np.uint8)
    unit[4, 0] = 7
    want = [reference.gf_mul(7, int(rs.parity[i, 4]), 0x11D) for i in range(3)]
    assert list(rs.encode(unit)[:, 0]) == want
    shards = list(data) + list(parity)
    for rows in ([0, 1, 2, 3, 4, 5], [3, 4, 5, 6, 7, 8], [0, 2, 4, 6, 7, 8]):
        assert np.array_equal(rs.decode([shards[r] for r in rows], rows), data)


def test_reference_agrees_with_the_programs_host_codec():
    from repro.core.erasure import RSCode

    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, (2, 6, 1024), dtype=np.uint8)
    want = RSCode(6, 3).encode_stripes(data, backend="numpy")
    rs = reference.RS(6, 3, 0x11D)
    assert np.array_equal(np.stack([rs.encode(d) for d in data]), want)
    cells_ = reference.split(np.arange(100, dtype=np.uint8), 6)
    assert cells_.shape == (6, 32) and cells_.ravel()[100:].sum() == 0


def test_checkpoint_leaves_are_the_models_own():
    """The checkpoint's leaves follow from the model's published sizes:
    every parameter once per group, 1/fsdp_chips of its rows here."""
    _, config, _ = harness.cell_files(BENCH, "ckpt-rs6-3.save")
    model, state = config["model"], config["state"]
    h, layers = model["hidden_size"], model["num_hidden_layers"]
    kv_rows = model["num_key_value_heads"] * h // model["num_attention_heads"]
    published = {leaf["path"]: (leaf["shape"], leaf.get("repeat", 1))
                 for leaf in state["leaves"]}
    attn, mlp = "model/layers/{i}/self_attn/", "model/layers/{i}/mlp/"
    assert published == {
        "model/embed_tokens": ([model["vocab_size"], h], 1),
        attn + "q_proj": ([h, h], layers),
        attn + "k_proj": ([kv_rows, h], layers),
        attn + "v_proj": ([kv_rows, h], layers),
        attn + "o_proj": ([h, h], layers),
        mlp + "gate_proj": ([model["intermediate_size"], h], layers),
        mlp + "up_proj": ([model["intermediate_size"], h], layers),
        mlp + "down_proj": ([h, model["intermediate_size"]], layers),
        "model/layers/{i}/input_layernorm": ([h], layers),
        "model/layers/{i}/post_attention_layernorm": ([h], layers),
        "model/norm": ([h], 1),
        "lm_head": ([model["vocab_size"], h], 1),
    }
    assert not model["tie_word_embeddings"]
    total = sum(int(np.prod(shape)) * rep for shape, rep in published.values())
    assert total == state["parameters"]
    here = cells.kind("save").leaves(state)
    assert len(here) == len(state["groups"]) * (3 + 9 * layers)
    nbytes = sum(int(np.prod(shape)) * np.dtype(dtype).itemsize
                 for _, shape, dtype in here)
    assert nbytes == state["bytes_on_this_chip"]
    assert nbytes * state["fsdp_chips"] == total * 4 * len(state["groups"])
