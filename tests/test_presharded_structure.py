"""What the four-lane step is made of, read off its traced program on
the CPU (no clock): at the cell's shape (default ``Knobs()``, four
lanes: T = 1024, 1,792 point and 896 range slots a side a lane) no
scatter writes a ``[T, T]`` operand or takes more updates than the
largest slot array the step holds; the programs keep the names the
benchmark's trace metrics find them by (the one-lane step with range
lanes has one of its own since PR 35, ``jit_resolve_full``; the
point-only one is still ``jit__lambda``); and the one-lane step's body,
``resolve_batch`` on the fields, which shares ``_overlap_matrix`` with
it, lowers to the text it had before.
"""

import hashlib
import math

import jax
import numpy as np
import pytest

from foundationdb_tpu.core.options import Knobs
from foundationdb_tpu.ops import conflict as ck
from foundationdb_tpu.parallel import mesh as pm
from foundationdb_tpu.resolver.packing import BatchPacker, ShardRouter
from foundationdb_tpu.resolver.resolver import (
    fast_params_of, params_from_knobs)

LANES = 4

# sha256 of jit(resolve_batch on the fields).lower(state, batch)
# .as_text(), under the jax it was taken with: what ``make_resolve_fn``
# built until PR 40 and what its program still runs behind
# ``unpack_args``. "fast" (the point-only step, the program four of the
# benchmark's cells run): on the parent of PR 31 (commit 11e6431),
# and "full" re-taken when it was renamed (on the parent
# c14e2d5 it was dba61d84…f1b24ab), which changed that program by
# design: it is named ``resolve_full``, keeps the exact lanes' hits
# apart from the coarse summaries' and returns CONFLICT_COARSE where
# only a summary refused (ops/conflict.py ``_mark_coarse_only``).
# Both re-taken once every step returned CONFLICT_BATCH where only an
# earlier transaction of the batch refused (ops/conflict.py
# ``_verdicts``: one select more over the verdicts, every other line
# the same but for its numbering; on commit 5f92650 "full" was
# 70658cc8…92247778 and "fast" c1d846a5…f4cc6335).
ONE_LANE_TEXT = {
    "jax": "0.9.0",
    "full": "642f6f088f470b2417e5c4428a645e3089ef2a6264e58274edc1c5f511ab6a7d",
    "fast": "f0907f901702e4e8cf6edb5f75cbb23f54d539758b632dbe8f24fd37ff306416",
}


@pytest.fixture(scope="module")
def mesh_step():
    """(params, kernel, one routed ShardBatch of shapes) at the cell's."""
    params = params_from_knobs(Knobs())
    kern = pm.PreshardedResolverKernel(
        params, mesh=pm.default_mesh(LANES), make_state=False)
    empty = BatchPacker(params).pack_empty(0, 1, 0)
    packed, _, _ = ShardRouter(params, LANES).split(
        jax.tree.map(lambda a: np.asarray(a)[None], empty))
    return params, kern, ck.unpack_args_host(packed)


def _scatters(jaxpr):
    """Every scatter of a jaxpr and of the jaxprs inside it →
    (operand shape, rows of indices = updates it applies)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith("scatter"):
            operand, indices = eqn.invars[0].aval, eqn.invars[1].aval
            yield operand.shape, math.prod(indices.shape[:-1])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scatters(sub)


@pytest.mark.parametrize("program", ["step", "scan", "one_lane_step",
                                     "one_lane_fast_step"])
def test_no_scatter_over_slot_pairs_and_the_programs_keep_their_names(
        mesh_step, program):
    params, kern, sb = mesh_step
    state = jax.eval_shape(kern.init_state)
    if program == "step":
        fn, name = kern._step, "jit_resolve_batch_presharded"
        batch = jax.tree.map(lambda a: a[0], sb)
    elif program == "scan":
        fn, name = kern._scan_step, "jit_scan_step"
        batch = sb
    else:
        one, name = params, "jit_resolve_full"
        if program == "one_lane_fast_step":
            one, name = fast_params_of(params), "jit__lambda"
        fn = ck.make_resolve_fn(one)
        state = jax.eval_shape(lambda: ck.init_state(one))
        batch = BatchPacker(one).pack_empty(0, 1, 0)
    T = params.txns
    # the widest slot array a lane holds: its point sides, or its ring
    most = max(sb.pr_hash.shape[-1] // LANES, params.ring_capacity)
    # (a mesh program packs its batch on the way in: trace it as it
    # lowers, through the one array, not through ``make_jaxpr``)
    found = list(_scatters(fn.trace(state, batch).jaxpr.jaxpr))
    # the walk reached the step's body (the point-only one has three:
    # the hash table, the coarse point summary, and nothing of a ring)
    assert len(found) > (8 if program != "one_lane_fast_step" else 1)
    assert not [s for s in found if s[0] == (T, T) or s[1] > most], found
    assert fn.lower(state, batch).as_text().startswith(f"module @{name} ")


@pytest.mark.parametrize("variant", ["full", "fast"])
def test_the_one_lane_step_on_the_fields_lowers_to_the_text_it_had(variant):
    if jax.__version__ != ONE_LANE_TEXT["jax"]:
        pytest.skip(f"the text was hashed under jax {ONE_LANE_TEXT['jax']}; "
                    "another jax writes other text for the same program")
    params = params_from_knobs(Knobs())
    if variant == "fast":
        params = fast_params_of(params)
    state = jax.eval_shape(lambda: ck.init_state(params))
    batch = BatchPacker(params).pack_empty(0, 1, 0)
    fn = lambda state, batch: ck.resolve_batch(state, batch, params)
    if variant == "full":
        fn.__name__ = "resolve_full"
    text = jax.jit(fn, donate_argnums=(0,)).lower(state, batch).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == ONE_LANE_TEXT[variant]
