"""Sharding-rule unit tests: divisibility fallbacks, cache/batch specs.

Uses a tiny (2, 2) mesh built in a subprocess-free way: these tests only
inspect PartitionSpecs (no arrays are placed), so a 1-device mesh would
hide divisibility behavior — we construct a fake Mesh over the single CPU
device reshaped logically via jax.sharding.AbstractMesh.
"""

from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro.parallel import sharding as sh

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


class _Leaf:
    def __init__(self, shape):
        self.shape = shape


def test_mesh_axes_detection():
    assert sh.MeshAxes.for_mesh(MESH).data == ("data",)
    assert sh.MeshAxes.for_mesh(MESH3).data == ("pod", "data")


def test_param_rules_shard_when_divisible():
    params = {
        "embed": {"table": _Leaf((64000, 4096))},
        "layers": {
            "attn": {"wq": {"w": _Leaf((48, 4096, 4096))}},
            "mlp": {"down": {"w": _Leaf((48, 11008, 4096))}},
        },
        "unembed": {"w": _Leaf((4096, 64000))},
        "ln": {"scale": _Leaf((4096,))},
    }
    specs = sh.param_specs(params, MESH)
    assert specs["embed"]["table"] == P("model", "data")
    assert specs["layers"]["attn"]["wq"]["w"] == P(None, "data", "model")
    assert specs["layers"]["mlp"]["down"]["w"] == P(None, "model", "data")
    assert specs["unembed"]["w"] == P("data", "model")
    assert specs["ln"]["scale"] == P(None)


def test_param_rules_fall_back_when_indivisible():
    # 20 heads x 128 = 2560 divides 16, but a 20-sized axis would not;
    # rules operate on flattened projection dims so this shards cleanly,
    # while a truly indivisible dim falls back.
    specs = sh.param_specs({"w_odd": {"w": _Leaf((17, 33))}}, MESH)
    assert specs["w_odd"]["w"] == P(None)


def test_moe_expert_specs():
    params = {
        "w_gate": _Leaf((16, 6144, 10752)),
        "w_down": _Leaf((16, 10752, 6144)),
    }
    specs = sh.param_specs(params, MESH)
    assert specs["w_gate"] == P("model", "data", None)
    assert specs["w_down"] == P("model", None, "data")


def test_batch_and_residual_specs():
    specs = sh.data_batch_specs({"tokens": (256, 4096)}, MESH)
    assert specs["tokens"] == P(("data",), None)
    # batch=1 (long_500k): not divisible -> unsharded
    specs1 = sh.data_batch_specs({"tokens": (1, 524288)}, MESH)
    assert specs1["tokens"] == P(None, None)
    assert sh.residual_spec(256, 4096, MESH) == P(("data",), "model", None)
    assert sh.residual_spec(1, 524288, MESH) == P(None, "model", None)


def test_cache_specs_never_shard_seq_and_find_batch():
    cache = {"k": _Leaf((32, 128, 32768, 8, 128))}   # (L, B, S, kv, hd)
    specs = sh.cache_specs(cache, MESH, max_len=32768, batch=128)
    spec = specs["k"]
    assert spec[2] is None                       # seq never sharded
    assert spec[1] in ("data", ("data",))        # batch found by value, not L
    assert spec[0] is None                       # layer axis NOT data-sharded
    assert spec[4] == "model"                    # hd divisible

    # MLA latent cache (L, B, S, lora)
    mla = {"c": _Leaf((26, 128, 32768, 512))}
    spec = sh.cache_specs(mla, MESH, max_len=32768, batch=128)["c"]
    assert spec[3] == "model" and spec[1] in ("data", ("data",))
    assert spec[2] is None


def test_moe_buffer_spec():
    assert sh.moe_buffer_spec(16, MESH, 256) == P(("data",), "model", None, None)
    assert sh.moe_buffer_spec(10, MESH, 256) is None   # E % 16 != 0
