"""Partitioner rules on a REAL multi-device host mesh.

The conftest forces a 4-way CPU host platform, so these tests exercise
actual ``Mesh``es over live devices — specs must be constructible as
``NamedSharding``s and params must physically land sharded (shard shapes
halved along sharded dims, one addressable shard per device).  The 16×16
pod-scale divisibility audit keeps running on an abstract mesh (no host
has 256 devices), pinning the paper's full-pod claims.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.models import zoo
from repro.sharding.partition import Partitioner, data_axes
from repro.sharding.shardctx import abstract_mesh

requires_mesh = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=4 (set in conftest.py)",
)


def _host_mesh(multi_pod=False):
    devs = np.array(jax.devices()[:4])
    if multi_pod:
        return Mesh(devs.reshape(1, 2, 2), ("pod", "data", "model"))
    return Mesh(devs.reshape(2, 2), ("data", "model"))


def _abstract_mesh(multi_pod=False):
    if multi_pod:
        return abstract_mesh((2, 16, 16), ("pod", "data", "model"))
    return abstract_mesh((16, 16), ("data", "model"))


def _param_specs(arch, multi_pod=False, mesh=None):
    cfg = get_config(arch)
    part = Partitioner(mesh if mesh is not None else _host_mesh(multi_pod))
    spec = jax.eval_shape(lambda: zoo.init(jax.random.PRNGKey(0), cfg))
    return part.param_specs(spec), part, spec


@requires_mesh
def test_granite_attention_tp_sharding():
    specs, part, shapes = _param_specs("granite-3-2b")
    blk = specs["blocks"]
    assert blk["attn"]["wq"] == P(None, "data", "model")  # [L, d, H·hd]
    assert blk["attn"]["wo"] == P(None, "model", "data")  # row-parallel
    assert blk["mlp"]["w_down"] == P(None, "model", "data")
    assert specs["embed"] == P("model", "data")


@requires_mesh
def test_moe_expert_sharding():
    specs, part, shapes = _param_specs("qwen3-moe-30b-a3b")
    moe = specs["blocks"]["moe"]
    assert moe["w_gate"] == P(None, "model", None, "data")  # [L, E, d, f]
    assert moe["w_down"] == P(None, "model", "data", None)  # [L, E, f, d]


@requires_mesh
def test_divisibility_fallbacks_recorded():
    """whisper (20 heads) / minicpm (36 heads): H not divisible is fine
    because sharding uses the flat H·hd dim — no fallback for attention; the
    partitioner must not crash and must log any replicated dims."""
    for arch in ("whisper-large-v3", "minicpm-2b"):
        specs, part, _ = _param_specs(arch)
        assert isinstance(part.explain(), str)


@requires_mesh
def test_params_physically_shard_on_host_mesh():
    """Reduced-config params device_put under the specs: every leaf lands
    with one addressable shard per device, and a tensor-parallel leaf's
    shard shape is halved along its 'model' dim."""
    mesh = _host_mesh()
    cfg = get_config("granite-3-2b", reduced=True)
    part = Partitioner(mesh)
    params = zoo.init(jax.random.PRNGKey(0), cfg)
    shardings = part.param_shardings(params)
    placed = jax.device_put(params, shardings)
    for leaf, sharding in zip(
        jax.tree_util.tree_leaves(placed), jax.tree_util.tree_leaves(shardings)
    ):
        assert len(leaf.addressable_shards) == 4
        assert leaf.sharding.is_equivalent_to(sharding, leaf.ndim)
    wq = placed["blocks"]["attn"]["wq"]  # [L, d, H·hd] under P(None,'data','model')
    full = wq.shape
    shard = wq.addressable_shards[0].data.shape
    assert shard == (full[0], full[1] // 2, full[2] // 2)
    # Round-trip: gathering the shards reproduces the unsharded values.
    host = np.asarray(wq)
    unsharded = np.asarray(zoo.init(jax.random.PRNGKey(0), cfg)["blocks"]["attn"]["wq"])
    np.testing.assert_array_equal(host, unsharded)


@requires_mesh
def test_every_leaf_gets_a_spec_all_archs():
    from repro.configs import ARCH_IDS

    mesh = _host_mesh()
    for arch in ARCH_IDS:
        specs, part, shapes = _param_specs(arch, mesh=mesh)
        n_leaves = len(jax.tree_util.tree_leaves(shapes))
        n_specs = len(jax.tree_util.tree_leaves(specs, is_leaf=lambda x: isinstance(x, P)))
        assert n_leaves == n_specs, arch
        # Every spec must be realizable on the live mesh and divide evenly.
        flat_specs = jax.tree_util.tree_leaves(specs, is_leaf=lambda x: isinstance(x, P))
        flat_shapes = jax.tree_util.tree_leaves(shapes)
        for sp, sh in zip(flat_specs, flat_shapes):
            NamedSharding(mesh, sp)
            for dim, ax in zip(sh.shape, tuple(sp)):
                if ax is None:
                    continue
                axes = ax if isinstance(ax, tuple) else (ax,)
                size = int(np.prod([dict(mesh.shape)[a] for a in axes]))
                assert dim % size == 0, f"{arch}: {sh.shape} vs {sp}"


def test_pod_scale_divisibility_audit():
    """The 16×16 (and 2×16×16) abstract meshes pin the full-pod divisibility
    claims for every arch without needing 256 host devices."""
    from repro.configs import ARCH_IDS

    for multi_pod in (False, True):
        mesh = _abstract_mesh(multi_pod)
        for arch in ARCH_IDS:
            specs, part, shapes = _param_specs(arch, mesh=mesh)
            flat_specs = jax.tree_util.tree_leaves(specs, is_leaf=lambda x: isinstance(x, P))
            flat_shapes = jax.tree_util.tree_leaves(shapes)
            for sp, sh in zip(flat_specs, flat_shapes):
                for dim, ax in zip(sh.shape, tuple(sp)):
                    if ax is None:
                        continue
                    axes = ax if isinstance(ax, tuple) else (ax,)
                    size = int(np.prod([dict(mesh.shape)[a] for a in axes]))
                    assert dim % size == 0, f"{arch}: {sh.shape} vs {sp}"


@requires_mesh
def test_constrain_respects_ambient_mesh_and_divisibility():
    """shardctx.constrain: identity when un-meshed; under `jax.set_mesh` it
    constrains only the dims whose axes exist AND divide, silently dropping
    the rest — the degradation contract model code relies on."""
    from repro.sharding.shardctx import ambient_mesh, axis_size, constrain

    mesh = _host_mesh()
    assert ambient_mesh() is None  # no mesh context → constrain is a no-op
    x = jnp.arange(16.0).reshape(8, 2)
    assert constrain(x, ("data", "model")) is x

    assert axis_size(mesh, None) == 1
    assert axis_size(mesh, "model") == 2
    assert axis_size(mesh, ("data", "model")) == 4

    with jax.set_mesh(mesh):
        assert ambient_mesh() is not None
        # Both dims divide → constrained, values untouched.
        y = jax.jit(lambda a: constrain(a, ("data", "model")))(x)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
        # 'pod' absent here → ('pod','data') degrades to ('data',); dim 7
        # does not divide model=2 → that dim falls back to unconstrained.
        z = jax.jit(lambda a: constrain(a, (("pod", "data"), "model")))(jnp.ones((8, 7)))
        assert z.shape == (8, 7)
        # Nothing constrainable → returns the input unchanged.
        w = jnp.ones((3,))
        assert constrain(w, (None,)) is w


@requires_mesh
def test_cache_specs_flash_decode_layout():
    cfg = get_config("granite-3-2b")
    part = Partitioner(_host_mesh())
    params = jax.eval_shape(lambda: zoo.init(jax.random.PRNGKey(0), cfg))
    batch = {"tokens": jax.ShapeDtypeStruct((128, 8), jnp.int32)}
    cache = zoo.cache_spec(params, batch, cfg, 32_832)
    specs = part.cache_specs(cache)
    assert specs.k == P(None, "data", "model", None, None)  # S over model


@requires_mesh
def test_multipod_batch_uses_pod_axis():
    cfg = get_config("granite-3-2b")
    mesh = _host_mesh(multi_pod=True)
    assert data_axes(mesh) == ("pod", "data")
    part = Partitioner(mesh)
    batch = {"tokens": jax.ShapeDtypeStruct((256, 4096), jnp.int32)}
    specs = part.batch_specs(batch)
    assert specs["tokens"] == P(("pod", "data"), None)
