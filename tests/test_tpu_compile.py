"""Compile guards: the main-path Pallas kernels compile for a TPU v5e.

Interpret mode never checks block tiling, memory spaces or VMEM budgets;
the TPU compiler, which is installed even where no chip is, does.  Each
case lowers one kernel entry at granite-3-2b widths (32 query heads, 8 KV
heads, head_dim 64, vocab 49155, 16-token pages, the serving backend's
``block_v``) for one chip of a described ``v5e:2x2`` topology, compiles it,
and asserts the compiled program holds the kernel (``tpu_custom_call``).
The fused entry compiles at minicpm-2b widths too (36 query heads, all of
them KV heads, vocab 122753), at the edge window's K+1 = 9 rows, where its
LM-head tile needs more than the default scoped VMEM.  The paged pool's
jitted writes are compiled at both 40-layer pools as well, to check that
they update the donated buffers in place, and the serving backend's query
draw with the launch that reads it, at both widths.

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
from repro.kernels.spec_verify.kernel import DEFAULT_BV, fused_vmem_limit

B, K, N = 8, 4, 8  # batch, chain drafts, tree nodes
H, HKV, HD, BS, V = 32, 8, 64, 16, 49_155  # granite-3-2b widths, 16-token pages
P, G = 513, 8  # 512 pool pages + the sentinel; 8 pages per session
VP = -(-V // DEFAULT_BV) * DEFAULT_BV
WIDTHS = {  # (query heads, KV heads, vocab) of each served configuration
    "granite": (H, HKV, V),
    "minicpm": (36, 36, 122_753),
}
WINDOW = 8  # the edge window of the benchmark's cells: K+1 = 9 rows


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


def _fused_args(sd, widths, k, quant):
    """The fused entry's argument shapes (and int8 planes) at ``widths``."""
    h, hkv, v = widths
    i32 = functools.partial(sd, dtype=jnp.int32)
    f32 = functools.partial(sd, dtype=jnp.float32)
    page = sd((P, BS, hkv, HD), jnp.int8 if quant else jnp.float32)
    args = (
        f32((B, k + 1, h, HD)), page, page, f32((h * HD, v)),
        i32((B, G)), i32((B, k + 1)), i32((B, k)), i32((B,)),
    )
    return args, ({"quant": tuple(f32((P, BS, hkv)) for _ in range(4))} if quant else {})


def _entries(sd):
    """kernel name -> (function, argument shapes) at the widths above."""
    i32 = functools.partial(sd, dtype=jnp.int32)
    f32 = functools.partial(sd, dtype=jnp.float32)
    planes = tuple(f32((P, BS, HKV)) for _ in range(4))
    fused = functools.partial(spec_verify_fused, impl="pallas", block_v=DEFAULT_BV)
    paged = functools.partial(paged_decode_attention, impl="pallas")
    paged_args = (f32((B, H, HD)), f32((P, BS, HKV, HD)), f32((P, BS, HKV, HD)), i32((B, G)), i32((B,)))
    paged8_args = (paged_args[0], sd((P, BS, HKV, HD), jnp.int8), sd((P, BS, HKV, HD), jnp.int8)) + paged_args[3:]
    return {
        "spec_verify_fused": (fused, *_fused_args(sd, WIDTHS["granite"], WINDOW, False)),
        "spec_verify_fused_int8": (fused, *_fused_args(sd, WIDTHS["granite"], WINDOW, True)),
        "spec_verify_fused_minicpm": (fused, *_fused_args(sd, WIDTHS["minicpm"], WINDOW, False)),
        "spec_verify_fused_int8_minicpm": (fused, *_fused_args(sd, WIDTHS["minicpm"], WINDOW, True)),
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
        "spec_verify_fused_minicpm",
        "spec_verify_fused_int8_minicpm",
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


@pytest.mark.parametrize(
    "T, take, hkv", [(3, 3, HKV), (9, 2, HKV), (9, 2, 36)], ids=["3-3", "9-2", "9-2-mha36"]
)
def test_pool_write_compiles_in_place_for_v5e(one_chip, T, take, hkv):
    """A pool fill (and a CoW page copy) at the 40-layer pool of granite-3-2b
    (8 KV heads) or minicpm-2b (36) compiles to an update of the donated
    buffers: the outputs alias the inputs and no copy of a whole buffer is
    left in the program."""
    from repro.models.paged_kv import _copy_pages, _write_pages

    L = 40

    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    bufs = (sd((L, P, BS, hkv, HD)), sd((L, P, BS, hkv, HD)))
    news = (sd((L, T, hkv, HD)), sd((L, T, hkv, HD)))
    n_pages = 1 + -(-(T - take) // BS)
    programs = [
        _write_pages.lower(bufs, news, sd((n_pages,), jnp.int32), sd((), jnp.int32), take=take),
        _copy_pages.lower(bufs, sd((), jnp.int32), sd((), jnp.int32)),
    ]
    whole = f"f32[{L},{P},{BS},{hkv},{HD}]"
    for lowered in programs:
        text = lowered.compile().as_text()
        assert "input_output_alias" in text
        assert not re.search(rf"= {re.escape(whole)}\S* copy\(", text)


@pytest.mark.parametrize("quant", [False, True], ids=["f32", "int8"])
def test_granite_launch_keeps_block_v_512_and_the_default_vmem(one_chip, quant):
    """granite-3-2b's fused launch is the kernel it was: ``block_v`` 512 and
    no scoped VMEM limit of its own at every draft bucket, where minicpm-2b's
    K+1 = 9 launch asks for one above the 16 MiB default."""
    assert DEFAULT_BV == 512
    page = jnp.int8 if quant else jnp.float32
    for k1 in (2, 3, 5, 9):
        assert fused_vmem_limit(k1, H, HD, BS, DEFAULT_BV, page, quant) is None
    assert fused_vmem_limit(WINDOW + 1, 36, HD, BS, DEFAULT_BV, page, quant) > 16 << 20

    sd = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    fused = functools.partial(spec_verify_fused, impl="pallas", block_v=DEFAULT_BV)
    texts = {}
    for name, widths in WIDTHS.items():
        args, kwargs = _fused_args(sd, widths, WINDOW, quant)
        texts[name] = jax.jit(fused).lower(*args, **kwargs).as_text()
    # The head enters the kernel as [H, hd, Vp], Vp a multiple of 512.
    assert f"tensor<{H}x{HD}x{VP}xf32>" in texts["granite"]
    assert "scoped_memory_configs" not in texts["granite"]
    assert "scoped_memory_configs" in texts["minicpm"]


@pytest.mark.parametrize("widths", sorted(WIDTHS))
def test_query_draw_and_its_launch_compile_for_v5e(one_chip, widths):
    """The serving backend's query draw (the launcher's seeded target,
    ``DRAW_ROWS`` rows of 8 drafts) compiles for a v5e with the target's
    key an argument, and the fused launch compiles reading the first 8 of
    those rows."""
    import numpy as np

    from repro.models.paged_kv import PagedKVPool
    from repro.runtime import SpecVerifyBackend
    from repro.runtime.server import DRAW_ROWS

    h, hkv, v = WIDTHS[widths]
    key = jax.random.PRNGKey(3)
    queries = jax.random.fold_in(key, 88)

    def query_fn(session, tokens, k=None):
        n = len(tokens) if k is None else k
        draw = jax.random.fold_in(queries, session * 131 + n)
        return jax.random.normal(draw, (len(tokens) + 1, h, HD))

    pool = PagedKVPool(num_blocks=4, block_size=BS, n_layers=1, n_kv_heads=hkv, head_dim=HD)
    backend = SpecVerifyBackend(kv_pool=pool, query_fn=query_fn,
                                lm_head=np.zeros((h * HD, 8), np.float32), impl="pallas")
    sd = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    draw, consts = backend._draw(WINDOW)
    lowered = draw.lower(
        tuple(sd(c.shape, c.dtype) for c in consts), sd((DRAW_ROWS, WINDOW + 2), jnp.int32)
    )
    a, b = (int(x) for x in np.asarray(jax.random.key_data(queries)).ravel())
    assert f"dense<[{a}, {b}]>" not in lowered.as_text()  # the key is an argument
    lowered.compile()
    args, _ = _fused_args(sd, (h, hkv, v), WINDOW, False)
    fused = functools.partial(spec_verify_fused, impl="pallas", block_v=DEFAULT_BV)
    launch = jax.jit(fused).lower(sd((DRAW_ROWS, WINDOW + 1, h, HD), jnp.float32), *args[1:])
    assert "tpu_custom_call" in launch.compile().as_text()
