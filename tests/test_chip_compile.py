"""Compile the Pallas kernels of the main path for a TPU v5e.

Nothing runs here: each kernel is lowered and compiled at real model
widths for one chip of a ``v5e:2x2`` topology that is described, not
attached.  The TPU compiler refuses what interpret mode accepts (blocks
not aligned to the (8, 128) tiling, vector ops Mosaic cannot lower, more
VMEM than a kernel may use), so a kernel that would fail on the chip
fails here on the CPU.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and each test worker
imports every test file.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention.kernel import decode_attention_pallas
from repro.kernels.flash_attention import ops as fa
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.rwkv6.kernel import rwkv6_pallas
from repro.kernels.ssm.kernel import ssd_pallas
from repro.models.rwkv6 import HEAD_K
from repro.models.ssm import HEAD_P

BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compile cache
    off: a compile for a described chip is written to the cache but cannot
    be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _qwen2():
    cfg = get_config("qwen2-0.5b")
    return cfg.num_heads, cfg.num_kv_heads, cfg.head_dim


def _case(name):
    """(fn, [(shape, dtype), ...]) at the widths the models run."""
    h, kvh, d = _qwen2()
    if name.startswith("flash"):
        s = 100 if name == "flash_s100" else 1024
        kw = dict(causal=True,
                  window=256 if name == "flash_window" else None,
                  chunk=256 if name == "flash_chunk" else None)
        args = [((2, s, h, d), BF16), ((2, s, kvh, d), BF16),
                ((2, s, kvh, d), BF16)]
        if name == "flash_grad":
            # a train step: kernel forward, reference VJP backward
            def loss(q, k, v):
                o = fa.flash_attention(q, k, v, impl="pallas",
                                       interpret=False, causal=True)
                return (o.astype(F32) ** 2).sum()
            return jax.grad(loss, argnums=(0, 1, 2)), args
        return functools.partial(flash_attention_pallas, **kw), args
    if name == "decode":
        b, s = 8, 2048
        fn = decode_attention_pallas
        return fn, [((b, h, d), BF16), ((b, s, kvh, d), BF16),
                    ((b, s, kvh, d), BF16), ((b,), jnp.int32)]
    if name == "rwkv6":
        cfg = get_config("rwkv6-1.6b")
        hh, t = cfg.d_model // HEAD_K, 1024
        act = (1, t, hh, HEAD_K)
        return rwkv6_pallas, [(act, BF16), (act, BF16), (act, BF16),
                              (act, F32), ((hh, HEAD_K), F32)]
    assert name == "ssd"
    cfg = get_config("zamba2-1.2b")
    hh, n, t = cfg.ssm_expand * cfg.d_model // HEAD_P, cfg.ssm_state, 1024
    return ssd_pallas, [((1, t, hh, HEAD_P), BF16), ((1, t, hh), F32),
                        ((hh,), F32), ((1, t, n), BF16), ((1, t, n), BF16),
                        ((hh,), F32)]


@pytest.mark.parametrize("name", [
    "flash_causal", "flash_window", "flash_chunk", "flash_s100",
    "flash_grad", "decode", "rwkv6", "ssd"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _case(name)
    shapes = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
              for s, dt in args]
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text(), name
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16 * 2**30, (name, used)
