"""Compile guards: the main-path Pallas kernels compile for a TPU v5e.

Interpret mode never checks block tiling, memory spaces or VMEM budgets;
the TPU compiler, which is installed even where no chip is, does.  Each
case lowers one kernel entry at granite-3-2b widths (32 query heads, 8 KV
heads, head_dim 64, vocab 49155, 16-token pages, the serving backend's
``block_v``) for one chip of a described ``v5e:2x2`` topology, compiles it,
and asserts the compiled program holds the kernel (``tpu_custom_call``).
The paged pool's jitted writes are compiled at the 40-layer pool as well,
to check that they update the donated buffers in place.

The topology is described inside a fixture, never at import time: only one
process may load the TPU library, and every test worker imports this file.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.decode_attention import paged_decode_attention
from repro.kernels.spec_verify import spec_verify, spec_verify_fused, spec_verify_tree
from repro.kernels.spec_verify.kernel import DEFAULT_BV

B, K, N = 8, 4, 8  # batch, chain drafts, tree nodes
H, HKV, HD, BS, V = 32, 8, 64, 16, 49_155  # granite-3-2b widths, 16-token pages
P, G = 513, 8  # 512 pool pages + the sentinel; 8 pages per session
VP = -(-V // DEFAULT_BV) * DEFAULT_BV


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent cache off."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _entries(sd):
    """kernel name -> (function, argument shapes) at the widths above."""
    i32 = functools.partial(sd, dtype=jnp.int32)
    f32 = functools.partial(sd, dtype=jnp.float32)
    planes = tuple(f32((P, BS, HKV)) for _ in range(4))
    fused = functools.partial(spec_verify_fused, impl="pallas", block_v=DEFAULT_BV)
    fused_args = (
        f32((B, K + 1, H, HD)), f32((P, BS, HKV, HD)), f32((P, BS, HKV, HD)), f32((H * HD, V)),
        i32((B, G)), i32((B, K + 1)), i32((B, K)), i32((B,)),
    )
    fused8_args = (fused_args[0], sd((P, BS, HKV, HD), jnp.int8), sd((P, BS, HKV, HD), jnp.int8)) + fused_args[3:]
    paged = functools.partial(paged_decode_attention, impl="pallas")
    paged_args = (f32((B, H, HD)), f32((P, BS, HKV, HD)), f32((P, BS, HKV, HD)), i32((B, G)), i32((B,)))
    paged8_args = (paged_args[0], sd((P, BS, HKV, HD), jnp.int8), sd((P, BS, HKV, HD), jnp.int8)) + paged_args[3:]
    return {
        "spec_verify_fused": (fused, fused_args, {}),
        "spec_verify_fused_int8": (fused, fused8_args, {"quant": planes}),
        "spec_verify": (
            functools.partial(spec_verify, impl="pallas", block_v=DEFAULT_BV),
            (f32((B, K + 1, VP)), i32((B, K)), i32((B,))), {},
        ),
        "spec_verify_tree": (
            functools.partial(spec_verify_tree, impl="pallas", block_v=DEFAULT_BV),
            (f32((B, N + 1, VP)), i32((B, N)), i32((B, N)), i32((B,))), {},
        ),
        "paged_decode_attention": (paged, paged_args, {}),
        "paged_decode_attention_int8": (paged, paged8_args, {"quant": planes}),
    }


@pytest.mark.parametrize(
    "name",
    [
        "spec_verify_fused",
        "spec_verify_fused_int8",
        "spec_verify",
        "spec_verify_tree",
        "paged_decode_attention",
        "paged_decode_attention_int8",
    ],
)
def test_kernel_compiles_for_v5e(one_chip, name):
    sd = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    fn, args, kwargs = _entries(sd)[name]
    compiled = jax.jit(fn).lower(*args, **kwargs).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("T, take", [(3, 3), (9, 2)])
def test_pool_write_compiles_in_place_for_v5e(one_chip, T, take):
    """A pool fill (and a CoW page copy) at granite-3-2b's 40-layer pool
    compiles to an update of the donated buffers: the outputs alias the
    inputs and no copy of a whole buffer is left in the program."""
    from repro.models.paged_kv import _copy_pages, _write_pages

    L = 40

    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    bufs = (sd((L, P, BS, HKV, HD)), sd((L, P, BS, HKV, HD)))
    news = (sd((L, T, HKV, HD)), sd((L, T, HKV, HD)))
    n_pages = 1 + -(-(T - take) // BS)
    programs = [
        _write_pages.lower(bufs, news, sd((n_pages,), jnp.int32), sd((), jnp.int32), take=take),
        _copy_pages.lower(bufs, sd((), jnp.int32), sd((), jnp.int32)),
    ]
    whole = f"f32[{L},{P},{BS},{HKV},{HD}]"
    for lowered in programs:
        text = lowered.compile().as_text()
        assert "input_output_alias" in text
        assert not re.search(rf"= {re.escape(whole)}\S* copy\(", text)
