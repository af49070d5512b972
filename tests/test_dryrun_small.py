"""Dry-run machinery end-to-end on a small CPU mesh (subprocess: the 8-device
host-platform flag must be set before jax initializes, and the main test
process must keep seeing 1 device)."""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding
    sys.path.insert(0, "src")
    from repro.configs import get_config
    from repro.launch.steps import TrainState, build_train_step, build_decode_step
    from repro.models import zoo
    from repro.optim import adamw
    from repro.sharding.partition import Partitioner
    from repro.launch.dryrun import collective_census, _as_cost_dict

    from repro.launch.mesh import make_test_mesh
    mesh = make_test_mesh(2, 4)
    cfg = get_config("granite-3-2b", reduced=True)
    part = Partitioner(mesh)
    params_spec = jax.eval_shape(lambda: zoo.init(jax.random.PRNGKey(0), cfg))
    params_sh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), part.param_specs(params_spec))
    batch = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32), "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
    batch_sh = part.batch_shardings(batch)
    opt = adamw(1e-3)
    opt_spec = jax.eval_shape(opt.init, params_spec)
    opt_sh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), part.param_specs(opt_spec))
    state_spec = TrainState(params_spec, opt_spec, jax.ShapeDtypeStruct((), jnp.int32))
    from jax.sharding import PartitionSpec as P
    state_sh = TrainState(params_sh, opt_sh, NamedSharding(mesh, P()))
    step = build_train_step(cfg, opt)
    with jax.set_mesh(mesh):
        compiled = jax.jit(step, in_shardings=(state_sh, batch_sh), out_shardings=(state_sh, None)).lower(state_spec, batch).compile()
        cost = _as_cost_dict(compiled.cost_analysis())
        mem = compiled.memory_analysis()
        hlo = compiled.as_text()
    coll = collective_census(hlo)
    print(json.dumps({
        "flops": float(cost.get("flops", 0)),
        "temp": int(mem.temp_size_in_bytes),
        "collectives": sorted(coll),
        "coll_bytes": int(sum(v["bytes"] for v in coll.values())),
    }))
    """
)


@pytest.mark.slow
def test_dryrun_compiles_on_8_device_mesh():
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, timeout=600, cwd=Path(__file__).parent.parent,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["flops"] > 0
    assert rec["coll_bytes"] > 0  # TP/DP must produce collectives
    assert "all-reduce" in rec["collectives"]


@pytest.mark.slow
def test_production_dryrun_cell_has_artifacts():
    """If the background sweep already produced cells, validate their schema."""
    results = Path(__file__).parent.parent / "dryrun_results"
    if not results.exists() or not list(results.glob("*.json")):
        pytest.skip("no dry-run artifacts yet")
    rec = json.loads(sorted(results.glob("*.json"))[0].read_text())
    assert {"arch", "shape", "mesh", "ok"} <= set(rec)
    if rec.get("ok") and not rec.get("skipped"):
        assert rec["per_device_bytes"] > 0
        assert rec["flops_scaled"] > 0
