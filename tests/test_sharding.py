"""Sharding rules: evenness fallback, per-arch adjustments, spec trees."""
import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.distributed.sharding import make_rules, spec_for, tree_shardings
from repro.launch.mesh import make_test_mesh


class FakeMesh:
    """Shape-only stand-in (avoids needing 256 devices in unit tests)."""
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


MESH = FakeMesh({"data": 16, "model": 16})
POD_MESH = FakeMesh({"pod": 2, "data": 16, "model": 16})


def test_rules_dense_gqa_uneven_kv():
    cfg = get_config("qwen3-14b")       # kv=8 -> not divisible by 16
    rules = make_rules(cfg, MESH)
    assert rules["kv_heads"] is None
    assert rules["head_dim"] == "model"


def test_rules_mha_even_kv():
    cfg = get_config("qwen1.5-0.5b")    # kv=16
    rules = make_rules(cfg, MESH)
    assert rules["kv_heads"] == "model"
    assert rules["head_dim"] is None


def test_rules_moe_modes():
    granite = make_rules(get_config("granite-moe-1b-a400m"), MESH)
    assert granite["experts"] == "model"      # 32 % 16 == 0
    qwen = make_rules(get_config("qwen2-moe-a2.7b"), MESH)
    assert qwen["experts"] is None and qwen["expert_ff"] == "model"


def test_multi_pod_batch_axes():
    cfg = get_config("qwen1.5-0.5b")
    rules = make_rules(cfg, POD_MESH)
    assert rules["batch"] == ("pod", "data")


def test_spec_evenness_fallback():
    cfg = get_config("qwen3-14b")
    rules = make_rules(cfg, MESH)
    # 40 heads over 16-way model axis: dropped for ARGUMENT shardings
    spec = spec_for(("layers", "embed", "heads", None), rules,
                    shape=(40, 5120, 40, 128), mesh=MESH)
    assert spec == P(None, "data", None, None)
    # but kept when no shape given (activation constraints may stay uneven)
    spec2 = spec_for(("layers", "embed", "heads", None), rules)
    assert spec2 == P(None, "data", "model", None)


def test_tree_shardings_structure_match():
    cfg = get_config("qwen1.5-0.5b")
    from repro.models.transformer import abstract_params, logical_axes
    mesh = make_test_mesh(1, 1)
    rules = make_rules(cfg, mesh)
    ap = abstract_params(cfg)
    sh = tree_shardings(logical_axes(cfg), mesh, rules, ap)
    assert set(sh.keys()) == set(ap.keys())


def test_vocab_padding_is_lane_aligned():
    from repro.configs.base import padded_vocab
    assert padded_vocab(151655) % 128 == 0
    assert padded_vocab(151936) == 151936        # already aligned
    assert padded_vocab(49155) % 16 == 0


def test_peaks_keyed_by_device_kind():
    """Chip peaks come from one table keyed by device_kind; an unknown
    device is an error, not a default."""
    from repro.launch.mesh import peaks
    v5e = peaks("TPU v5 lite")
    assert v5e["peak_flops_bf16"] == 197e12 and v5e["hbm_bw"] == 819e9
    with pytest.raises(KeyError, match="cpu"):
        peaks("cpu")
