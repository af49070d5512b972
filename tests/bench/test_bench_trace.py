"""The trace reduction (bench/trace_reduce.py) on small traces."""

import bench_tiny  # noqa: F401  (puts the repo root on sys.path)
import pytest

from bench import trace_reduce

KERNELS = {"verify_kernel": ["spec_verify_fused"]}


def _trace(device_events, host_events):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": device_events}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host_events}]},
    ]}


def test_busy_is_the_union_clipped_to_the_window():
    dev = [["fusion.1", 0, 50], ["fusion.2", 100, 100], ["copy", 150, 100],  # overlap 150-200
           ["spec_verify_fused.1", 300, 200], ["late", 950, 100]]
    host = [["bench.window", 100, 900], ["bench.fused_inputs", 250, 50], ["bench.fused_verify", 250, 600]]
    r = trace_reduce.reduce_trace(_trace(dev, host), KERNELS)
    assert r["window_s"] == pytest.approx(900e-9)
    # inside [100, 1000): [100, 250) + [300, 500) + [950, 1000) = 150 + 200 + 50
    assert r["busy_s"] == pytest.approx(400e-9)
    assert r["kernel_s"]["verify_kernel"] == pytest.approx(200e-9)
    assert r["kernel_count"]["verify_kernel"] == 1
    gaps = dict(r["idle_gaps"])
    # [250, 300) lies inside fused_inputs (the shorter span wins); [500, 950) inside fused_verify
    assert gaps["bench.fused_inputs"] == pytest.approx(50e-9)
    assert gaps["bench.fused_verify"] == pytest.approx(450e-9)
    assert r["device_ops"][0][0] == "spec_verify_fused.1"


def test_gap_with_no_span_is_named_so():
    r = trace_reduce.reduce_trace(_trace([["a", 0, 10]], [["bench.window", 0, 100]]), KERNELS)
    assert dict(r["idle_gaps"]) == {"no bench span": pytest.approx(90e-9)}
    assert r["kernel_s"]["verify_kernel"] == 0


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce_trace(_trace([["a", 0, 10]], []), KERNELS)


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent cache off."""
    import os

    import jax
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
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


def test_kernel_names_match_the_compiled_op(one_chip):
    """The served module, compiled for a v5e, names its kernel op as bench/kernels.json says.

    The trace's op line carries the compiled module's instruction names, so
    a name in ``bench/kernels.json`` that the module does not carry would
    leave the kernel's device time unread.
    """
    import re

    import jax
    import jax.numpy as jnp

    from repro.kernels.spec_verify import spec_verify_fused

    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    B, K, H, Hkv, hd, bs, V, P, G = 2, 2, 32, 8, 64, 16, 49_155, 513, 64  # granite-3-2b widths
    i32 = jnp.int32
    args = (sd((B, K + 1, H, hd)), sd((P, bs, Hkv, hd)), sd((P, bs, Hkv, hd)), sd((H * hd, V)),
            sd((B, G), i32), sd((B, K + 1), i32), sd((B, K), i32), sd((B,), i32))
    text = spec_verify_fused.lower(*args, impl="pallas", block_v=512, window=8).compile().as_text()
    kernel_ops = [m.group(1) for m in re.finditer(r"%(\S+) = .*custom_call_target=\"tpu_custom_call\"", text)]
    assert len(kernel_ops) == 1, kernel_ops
    pats = trace_reduce.kernel_names()["verify_kernel"]
    assert any(p in kernel_ops[0] for p in pats), (kernel_ops, pats)
    others = re.findall(r"^\s*(?:ROOT )?%(\S+) = ", text, re.M)
    assert [o for o in others if o != kernel_ops[0] and any(p in o for p in pats)] == []
