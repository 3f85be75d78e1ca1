"""Config registry: exact assigned specs, analytic param counts vs published
sizes, smoke-variant constraints, spec coverage for all 4 input shapes."""
import jax
import pytest

from repro.configs import (
    ARCH_IDS, SHAPES, all_configs, for_shape, get_config, input_specs,
    smoke_config,
)
from repro.configs.base import cache_seq_axis, input_logical, kv_cache_specs

EXPECTED_PARAMS_B = {
    "qwen3-32b": (30, 35),
    "hymba-1.5b": (1.3, 2.0),
    "phi3-mini-3.8b": (3.5, 4.1),
    "phi-3-vision-4.2b": (3.5, 4.5),
    "granite-moe-1b-a400m": (1.1, 1.6),
    "llama3-8b": (7.5, 8.5),
    "granite-3-2b": (2.2, 3.0),
    "musicgen-medium": (1.4, 2.2),
    "deepseek-v3-671b": (650, 690),
    "mamba2-2.7b": (2.5, 3.0),
}


def test_all_archs_registered():
    assert len(ARCH_IDS) == 10
    assert len(set(ARCH_IDS)) == 10


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_counts_match_published(arch):
    lo, hi = EXPECTED_PARAMS_B[arch]
    n = get_config(arch).param_count() / 1e9
    assert lo <= n <= hi, f"{arch}: {n:.2f}B outside [{lo},{hi}]"


def test_active_params_moe():
    ds = get_config("deepseek-v3-671b")
    active = ds.param_count(active_only=True) / 1e9
    assert 30 <= active <= 45  # DeepSeek-V3: 37B activated
    gm = get_config("granite-moe-1b-a400m")
    assert gm.param_count(active_only=True) < gm.param_count()


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_config_reduced(arch):
    sc = smoke_config(get_config(arch))
    assert sc.n_layers == 2
    assert sc.d_model <= 512
    assert sc.n_experts <= 4


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("shape", tuple(SHAPES))
def test_input_specs_cover_all_shapes(arch, shape):
    cfg = for_shape(get_config(arch), SHAPES[shape])
    specs = input_specs(cfg, SHAPES[shape])
    logical = input_logical(cfg, SHAPES[shape])
    assert set(specs) == set(logical)
    for k, v in specs.items():
        if isinstance(v, dict):
            assert set(v) == set(logical[k])
        else:
            assert len(logical[k]) == len(v.shape)


def test_long_context_uses_ring_buffer():
    cfg = for_shape(get_config("llama3-8b"), SHAPES["long_500k"])
    assert cfg.attention_variant == "sliding_window"
    cache = kv_cache_specs(cfg, 1, SHAPES["long_500k"].seq_len)
    # ring buffer, not 524288
    assert cache["k"].shape[cache_seq_axis("k")] == cfg.sliding_window


def test_mla_keeps_full_compressed_cache():
    cfg = for_shape(get_config("deepseek-v3-671b"), SHAPES["long_500k"])
    cache = kv_cache_specs(cfg, 1, SHAPES["long_500k"].seq_len)
    assert (cache["c_kv"].shape[cache_seq_axis("c_kv")]
            == SHAPES["long_500k"].seq_len)


def test_ssm_cache_is_constant_size():
    cfg = get_config("mamba2-2.7b")
    c32 = kv_cache_specs(cfg, 1, 32768)
    c500 = kv_cache_specs(cfg, 1, 524288)
    assert c32["ssd"].shape == c500["ssd"].shape
