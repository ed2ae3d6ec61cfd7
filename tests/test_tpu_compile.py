"""The main path's device programs, compiled for a described v5e.

The sandbox has no chip but it has the chip's compiler: these tests
hand it the four programs a default-knob resolver can dispatch (the
single-step full variant on the jnp lanes and with the Pallas ring
kernel, the B=8 backlog scan in its fast and full variants) at
``Knobs()`` shapes, and the four-lane ``shard_map`` step of
``--resolvers 4`` over the described 2x2 mesh. All five must compile.
A compile that passes is not a chip run — ``chip_smoke.py`` is.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and every xdist
worker imports every test file.
"""

import math
import os
import re

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from foundationdb_tpu.core.options import Knobs
from foundationdb_tpu.ops import conflict as ck
from foundationdb_tpu.parallel import mesh as pm
from foundationdb_tpu.resolver.packing import BatchPacker, ShardRouter
from foundationdb_tpu.resolver.resolver import (
    BACKLOG_B, fast_params_of, params_from_knobs)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent
    # cache but cannot be read back without a chip: keep it off here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_branch(monkeypatch):
    """ops/conflict.py asks ``jax.default_backend()`` to choose between
    compiling a Pallas kernel and interpreting it; the sandbox answers
    "cpu". Steer it here, in the test."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _shapes(tree, sharding, lead=()):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(lead + tuple(x.shape), x.dtype,
                                       sharding=sharding), tree)


def _compile(make_fn, params, sharding, lead=()):
    state = _shapes(jax.eval_shape(lambda: ck.init_state(params)), sharding)
    batch = _shapes(BatchPacker(params).pack_empty(0, 1, 0), sharding, lead)
    return make_fn(params).lower(state, batch).compile()


def _default_params(**flags):
    knobs = Knobs()
    assert (knobs.batch_txn_capacity, knobs.hash_table_bits,
            knobs.range_ring_capacity, knobs.coarse_buckets_bits) == (
                1024, 22, 4096, 14)
    return params_from_knobs(knobs, **flags)


@pytest.mark.parametrize("program", [
    "step_full_jnp", "step_full_pallas_ring", "scan_fast", "scan_full"])
def test_default_knob_programs_compile_for_v5e(one_chip, tpu_branch, program):
    if program == "step_full_jnp":
        compiled = _compile(ck.make_resolve_fn, _default_params(), one_chip)
    elif program == "step_full_pallas_ring":
        compiled = _compile(ck.make_resolve_fn,
                            _default_params(use_pallas=True), one_chip)
        assert "tpu_custom_call" in compiled.as_text()
    else:
        # the served backlog path: what Resolver._make_scan_fn builds on
        # a TPU, where every backlog pads to the one B=8 scan
        params = _default_params(use_pallas=True)
        if program == "scan_fast":
            params = fast_params_of(params)
        compiled = _compile(ck.make_resolve_scan_fn, params, one_chip,
                            lead=(BACKLOG_B,))
        assert "tpu_custom_call" not in compiled.as_text()
    assert compiled.memory_analysis() is not None


def test_the_four_lane_step_compiles_for_v5e_with_no_scatter_into_t_by_t(
        topo):
    """``PreshardedResolverKernel._step`` as ``fdbserver --resolvers 4``
    dispatches it: T = 1024, 1,792 point and 896 range slots a lane."""
    params = _default_params()
    mesh = Mesh(np.array(topo.devices), (pm.AXIS,))
    lanes = mesh.devices.size
    kern = pm.PreshardedResolverKernel(params, mesh=mesh, make_state=False)

    def placed(x, spec, lead=1):
        shape = tuple(x.shape)
        if spec != pm.P():  # a lane's share → the global array
            shape = (lead * (shape[0] if shape else 1),) + shape[1:]
        return jax.ShapeDtypeStruct(
            shape, x.dtype, sharding=NamedSharding(mesh, spec))

    state = ck.ResolverState(*(
        placed(x, spec, lanes) for x, spec in zip(
            jax.eval_shape(lambda: ck.init_state(params)),
            pm._state_specs(pm.AXIS))))
    empty = BatchPacker(params).pack_empty(0, 1, 0)
    routed, _, _ = ShardRouter(params, lanes).split(
        jax.tree.map(lambda a: np.asarray(a)[None], empty))
    # the one array the step takes: row j is lane j's share of the batch
    layout = ck.arg_layout(jax.tree.map(lambda a: a[0], routed), lanes)
    batch = jax.ShapeDtypeStruct(
        (lanes, ck.arg_words(layout)), np.uint32,
        sharding=NamedSharding(mesh, pm.P(pm.AXIS)))
    text = kern._step.jitted.lower(state, batch, layout).compile().as_text()
    assert "HloModule jit_resolve_batch_presharded" in text
    assert "all-reduce" in text  # the verdict fold over ICI
    scattered = [math.prod(map(int, dims.split(",")))
                 for dims in re.findall(r"= \w+\[([\d,]+)\]\S* scatter\(", text)]
    assert scattered and params.txns ** 2 not in scattered
