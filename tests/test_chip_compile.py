"""The chip's compiler, without the chip: the jit entries the 100k-pod x
10k-node capacity plan (BASELINE.json config 5) compiles, lowered for a
described TPU v5e at the plan's node rung, and the node-sharded program
that ``simon apply --devices 4`` runs, on a described 2x2 v5e mesh.

Nothing here runs on a device; a compile that passes is not a chip run.
The topology is described inside a module fixture (one process may load
libtpu at a time, so never at import), and every test of this kind stays
in this one file.
"""

import importlib
import inspect

import pytest

PLAN_ENTRIES = (
    "ops.fast:build_trajectory",
    "ops.fast:sort_select",
    "ops.fast:cur_at",
    "ops.fast:domain_select",
    "ops.fast:light_reasons",
    "ops.fast:gather_takes",
    "ops.fast:exit_carry",
    "ops.grouped:_group_jit",
)
#: per-dispatch scan length of the plan's grouped scan
#: (ops.grouped.DEFAULT_GROUP_CHUNK)
POD_CHUNK = 16384


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    """Programs compiled for a described chip are written to the persistent
    cache but cannot be read back without one; keep them out of it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def captures():
    """First-call args of each plan entry, recorded while the trajectory
    dispatcher and the grouped scan run over the audit's canonical state."""
    from open_simulator_tpu.analysis import jaxpr_audit
    from open_simulator_tpu.ops import fast, grouped, kernels

    caps, patches = {}, []
    for name in PLAN_ENTRIES:
        mod_name, attr = name.split(":")
        module = importlib.import_module(f"open_simulator_tpu.{mod_name}")
        original = getattr(module, attr)

        def recorder(*a, _o=original, _n=name, **k):
            if _n not in caps and jaxpr_audit._is_concrete((a, k)):
                caps[_n] = jaxpr_audit._Captured(_n, _o, a, k)
            return _o(*a, **k)

        setattr(module, attr, recorder)
        patches.append((module, attr, original))
    try:
        ns, carry, batch = jaxpr_audit.canonical_state()
        w = kernels.weights_array()
        fast.schedule_batch_fast(ns, carry, batch, w, force_fast=True)
        grouped.schedule_batch_grouped(ns, carry, batch, w)
    finally:
        for module, attr, original in patches:
            setattr(module, attr, original)
    missing = set(PLAN_ENTRIES) - set(caps)
    assert not missing, f"canonical state no longer reaches {missing}"
    return caps


def _abstract(cap, device=None, mesh=None):
    """The captured args at the config-5 node rung, every array leaf placed
    on one described `device` or node-sharded over `mesh` (the rest
    replicated); the grouped scan gets the real pod chunk."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from open_simulator_tpu.analysis.hlo_audit import abstract_args
    from open_simulator_tpu.ops.encode import node_bucket

    args, kwargs = abstract_args(cap, node_bucket(10_000), mesh)
    fill = (
        NamedSharding(mesh, P()) if mesh is not None
        else SingleDeviceSharding(device)
    )

    def place(leaf):
        if not isinstance(leaf, jax.ShapeDtypeStruct):
            return leaf
        sh = leaf.sharding if leaf.sharding is not None else fill
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=sh)

    args = jax.tree.map(place, args)
    kwargs = jax.tree.map(place, kwargs)
    if cap.name == "ops.grouped:_group_jit":
        bound = inspect.signature(cap.fn).bind(*args, **kwargs)
        bound.arguments["group_size"] = POD_CHUNK
        args, kwargs = bound.args, bound.kwargs
    return args, kwargs


@pytest.mark.parametrize("entry", PLAN_ENTRIES)
def test_plan_entry_compiles_for_v5e(entry, topo, captures,
                                     no_persistent_cache):
    from open_simulator_tpu.analysis.hlo_audit import HBM_BYTES_BY_KIND

    cap = captures[entry]
    args, kwargs = _abstract(cap, device=topo.devices[0])
    compiled = cap.fn.trace(*args, **kwargs).lower().compile()
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert 0 < peak <= HBM_BYTES_BY_KIND["TPU v5 lite"]


@pytest.mark.parametrize(
    "entry", ["ops.fast:build_trajectory", "ops.grouped:_group_jit"]
)
def test_node_sharded_plan_compiles_on_four_v5e(entry, topo, captures,
                                                no_persistent_cache):
    """`--devices 4` shards the node axis (parallel.mesh.node_sharding):
    each device holds a quarter of the node rows, and the per-pod choice
    over all nodes is an all-reduce across the mesh."""
    from jax.sharding import Mesh

    from open_simulator_tpu.analysis.hlo_audit import (
        HBM_BYTES_BY_KIND,
        collective_census,
    )
    from open_simulator_tpu.parallel.mesh import NODE_AXIS

    mesh = Mesh(topo.devices, (NODE_AXIS,))
    assert len(topo.devices) == 4
    cap = captures[entry]
    args, kwargs = _abstract(cap, mesh=mesh)
    compiled = cap.fn.trace(*args, **kwargs).lower().compile()
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert 0 < peak <= HBM_BYTES_BY_KIND["TPU v5 lite"]
    kinds, _, _ = collective_census(compiled.as_text())
    assert kinds.get("all-reduce", 0) > 0, kinds
