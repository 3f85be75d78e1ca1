"""The main-path Pallas kernels compile for a TPU v5e at granite-3-2b widths.

Interpret mode runs kernel bodies as plain jnp, so it cannot see what the
TPU compiler refuses (unaligned block shapes, too much fast memory, a
kernel GSPMD cannot partition). These tests compile against a described
v5e chip (no chip attached) and assert the compiled program holds the
Mosaic kernel (`tpu_custom_call`) rather than a jnp fallback. Widths are
granite-3-2b's published ones (32 heads / 8 KV heads, d_head 64, d_model
2048, vocab 49155); the engine step is cut to 4 layers.

The topology is described inside a fixture: only one process at a time
may load the TPU compiler library, so the call must not happen while any
module is imported.
"""
import dataclasses
import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest

B, H, KV, D, CL = 32, 32, 8, 64, 1024     # engine slots, heads, cache
L = 4                                      # engine layers
N, DM, V = 4096, 2048, 49155               # loss rows, d_model, vocab
BF = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile_text(one_chip, fn, *shapes, **jit_kw) -> str:
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        shapes)
    return jax.jit(fn, **jit_kw).lower(*args).compile().as_text()


def _sds(shape, dtype=BF):
    return jax.ShapeDtypeStruct(shape, dtype)


def _kernel_case(name):
    from repro.kernels import ops as kops
    i32 = jnp.int32
    if name == "flash_decode":
        return (functools.partial(kops.flash_decode, scale=0.125,
                                  interpret=False),
                _sds((B, H, D)), _sds((L, B, KV, CL, D)),
                _sds((L, B, KV, CL, D)), _sds((B,), i32), _sds((), i32))
    if name == "flash_decode_paged":
        ps = 16
        np_ = B * CL // ps + 1
        return (functools.partial(kops.flash_decode_paged, scale=0.125,
                                  interpret=False),
                _sds((B, H, D)), _sds((np_, ps, KV, D)),
                _sds((np_, ps, KV, D)), _sds((B, CL // ps), i32),
                _sds((B,), i32))
    if name == "prefill_attention":
        c = 128
        return (functools.partial(kops.prefill_attention, scale=0.125,
                                  interpret=False),
                _sds((B, c, H, D)), _sds((B, c, KV, D)), _sds((B, c, KV, D)),
                _sds((B, KV, CL, D)), _sds((B, KV, CL, D)), _sds((), i32))
    if name == "flash_attention":
        s = 1024
        return (functools.partial(kops.flash_attention, scale=0.125,
                                  interpret=False),
                _sds((8, H, s, D)), _sds((8, KV, s, D)), _sds((8, KV, s, D)))

    def loss(h, w, t):
        return kops.fused_logprob(h, w, t, interpret=False)

    shapes = (_sds((N, DM)), _sds((DM, V)), _sds((N,), i32))
    if name == "fused_logprob_forward":
        return (loss,) + shapes
    assert name == "fused_logprob_grad"
    grad = jax.grad(lambda h, w, t: sum(x.sum() for x in loss(h, w, t)),
                    argnums=(0, 1))
    return (grad,) + shapes


@pytest.mark.parametrize("name", [
    "flash_decode", "flash_decode_paged", "prefill_attention",
    "flash_attention", "fused_logprob_forward", "fused_logprob_grad"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, *shapes = _kernel_case(name)
    text = _compile_text(one_chip, fn, *shapes)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("name, kernels", [
    ("flash_decode", ("flash_decode",)),
    ("fused_logprob_forward", ("fused_logprob_fwd",)),
    ("fused_logprob_grad", ("fused_logprob_fwd", "fused_logprob_bwd_dh",
                            "fused_logprob_bwd_dw"))])
def test_kernels_keep_their_names_for_v5e(one_chip, name, kernels):
    """The Mosaic calls carry the kernels' names into the compiled program
    (and so into profiler traces), under JAX's transformations too."""
    fn, *shapes = _kernel_case(name)
    text = _compile_text(one_chip, fn, *shapes)
    calls = [line.strip().split(" = ")[0] for line in text.splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    assert len(calls) == len(kernels)
    for k in kernels:
        assert any(k in c for c in calls), (k, calls)


def test_engine_decode_step_compiles_for_v5e(one_chip):
    """One generation-engine decode step (4 layers, 32 slots, cache 1024)
    compiles with the flash-decode kernel inside."""
    from repro.configs.base import kv_cache_specs
    from repro.configs.granite_3_2b import config as granite
    from repro.core.rollout import EngineConfig, _engine_step
    from repro.models import model as M
    from repro.sharding import tree_values

    cfg = dataclasses.replace(granite(), n_layers=L, use_pallas=True,
                              pallas_interpret=False)
    ec = EngineConfig(n_slots=B, max_len=CL, interpret=False)
    params = jax.eval_shape(
        lambda: tree_values(M.init_params(cfg, jax.random.PRNGKey(0))))
    state = {"tokens": _sds((B, CL), jnp.int32),
             "lp": _sds((B, CL), jnp.float32),
             "n_cached": _sds((B,), jnp.int32),
             "prompt_len": _sds((B,), jnp.int32),
             "active": _sds((B,), bool),
             "cache": kv_cache_specs(cfg, B, CL),
             "key": _sds((2,), jnp.uint32)}
    step = functools.partial(_engine_step, cfg=cfg, ec=ec, block_tables=None,
                             kv_len_hint=CL)
    text = _compile_text(one_chip, step, params, state)
    assert "tpu_custom_call" in text


# one layer's K or V in elements, and the whole cache in bytes (bf16)
LAYER_KV = B * KV * CL * D
CACHE_BYTES = 2 * L * LAYER_KV * 2
# what may output a cache-sized array inside the step: the arguments, the
# layer loop's carry, and the in-place row writes
IN_PLACE = {"parameter", "get-tuple-element", "tuple", "while", "bitcast",
            "dynamic-update-slice"}


def test_engine_decode_step_updates_cache_in_place_for_v5e(one_chip):
    """The engine's own jitted decode step, with its state donated as the
    engine runs it: each layer writes one K and one V row per slot into
    the cache in place, and no copy, transpose or fusion makes an array
    the size of a layer's K or V (the kernel reads the cache's own
    layout). The output cache aliases the donated input, and the
    temporaries stay under one layer's K+V."""
    from repro.configs.base import kv_cache_specs
    from repro.configs.granite_3_2b import config as granite
    from repro.core.rollout import EngineConfig, decode_program
    from repro.models import model as M
    from repro.sharding import tree_values

    cfg = dataclasses.replace(granite(), n_layers=L, use_pallas=True,
                              pallas_interpret=False)
    ec = EngineConfig(n_slots=B, max_len=CL, interpret=False)

    def placed(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    params = jax.tree.map(placed, jax.eval_shape(
        lambda: tree_values(M.init_params(cfg, jax.random.PRNGKey(0)))))
    state = {"tokens": _sds((B, CL), jnp.int32),
             "lp": _sds((B, CL), jnp.float32),
             "n_cached": _sds((B,), jnp.int32),
             "prompt_len": _sds((B,), jnp.int32),
             "active": _sds((B,), bool),
             "cache": kv_cache_specs(cfg, B, CL),
             "key": _sds((2,), jnp.uint32)}
    state = jax.tree.map(placed, state)
    compiled = decode_program(cfg, ec).lower(
        params, state, None, kv_len_hint=CL).compile()

    # (opcode, line) of each instruction whose output holds a layer's K or V
    big = []
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]+)\]\{[^}]*\} ([\w-]+)\(",
                     line)
        dims = [int(d) for d in m.group(1).split(",")] if m else []
        if (math.prod(dims) >= LAYER_KV and CL in dims and KV in dims
                and D in dims):
            big.append((m.group(2), line.strip()[:160]))
    assert big, "no cache-sized array found: the parse is stale"
    assert not [b for b in big if b[0] not in IN_PLACE], big
    # one K and one V row per slot, in the body of the layer loop
    assert sum(op == "dynamic-update-slice" for op, _ in big) == 2 * B

    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= CACHE_BYTES
    assert mem.temp_size_in_bytes < 2 * LAYER_KV * 2
