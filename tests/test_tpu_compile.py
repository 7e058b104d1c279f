"""The Pallas kernels of the main path compile for a TPU v5e.

Each case lowers and compiles one kernel at real widths for a described
(not attached) v5e chip with the TPU compiler installed beside JAX, and
checks that the program holds the Mosaic kernel (``tpu_custom_call``)
under the kernel's own ``name=``, which the profiler's trace shows.
This catches what interpret mode cannot: block shapes off the (8, 128)
tiling, VMEM overflows, ops Mosaic does not lower.  Nothing runs.

The topology is described inside a fixture, never while the module is
imported: only one process at a time may load the TPU library, and
every test worker imports this file.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels.coded_combine import (
    coded_combine,
    coded_combine_f8,
    coded_combine_q,
    coded_combine_q4,
)
from repro.kernels.decode_attention import decode_attention_fwd
from repro.kernels.flash_attention import flash_attention_fwd

K_PODS = 2
BLOCK = 64  # the --grad-block default
LEAF = 50_280 * 1_024  # mamba2-370m's embedding table, one gradient leaf


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A described chip's executables cannot be read back: keep them
    out of the persistent cache."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_names(text):
    """Instruction names of the Mosaic kernels in compiled HLO text."""
    return [m.group(1) for m in re.finditer(
        r"^\s*(?:ROOT )?(%[\w.\-]+) = [^\n]*custom_call_target="
        r'"tpu_custom_call"', text, re.M)]


def test_decode_attention_compiles_at_starcoder2_widths(
        one_chip, no_persistent_cache):
    cfg = get_config("starcoder2-3b")
    B, C = 4, 2048
    H, Kv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    fn = functools.partial(decode_attention_fwd, interpret=False)
    text = _compiled_text(
        fn, one_chip,
        ((B, 1, H, Dh), jnp.bfloat16),
        ((B, C, Kv, Dh), jnp.float32),
        ((B, C, Kv, Dh), jnp.float32),
        ((), jnp.int32),
    )
    assert "tpu_custom_call" in text
    names = _kernel_names(text)
    # the benchmark's decode_attn_roofline finds the kernel by this name
    assert len(names) == 1 and re.match(r"^%?decode_attention_fwd",
                                        names[0]), names


def test_coded_combine_compiles(one_chip, no_persistent_cache):
    fn = functools.partial(coded_combine, interpret=False)
    text = _compiled_text(fn, one_chip, ((1, K_PODS), jnp.float32),
                          ((K_PODS, LEAF), jnp.float32))
    assert "tpu_custom_call" in text
    assert [n.split(".")[0] for n in _kernel_names(text)] == [
        "%coded_combine"]


@pytest.mark.parametrize("kernel,payload,name", [
    (coded_combine_q, ((K_PODS, LEAF), jnp.int8), "dequant_combine_int8"),
    (coded_combine_q4, ((K_PODS, LEAF // 2), jnp.int8),
     "dequant_combine_int4"),
    (coded_combine_f8, ((K_PODS, LEAF), jnp.float8_e4m3fn),
     "dequant_combine_float8_e4m3fn"),
], ids=["int8", "int4", "fp8"])
def test_dequant_combine_compiles(one_chip, no_persistent_cache, kernel,
                                  payload, name):
    fn = functools.partial(kernel, block=BLOCK, interpret=False)
    text = _compiled_text(fn, one_chip, ((1, K_PODS), jnp.float32),
                          payload,
                          ((K_PODS, LEAF // BLOCK), jnp.float32))
    assert "tpu_custom_call" in text
    assert [n.split(".")[0] for n in _kernel_names(text)] == ["%" + name]


def test_flash_attention_compiles_with_its_name(one_chip,
                                                no_persistent_cache):
    bh, s, dh = 8, 256, 128
    fn = functools.partial(flash_attention_fwd, interpret=False)
    text = _compiled_text(fn, one_chip, *[((bh, s, dh), jnp.bfloat16)] * 3)
    assert [n.split(".")[0] for n in _kernel_names(text)] == [
        "%flash_attention_fwd"]
