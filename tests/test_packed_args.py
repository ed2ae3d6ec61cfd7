"""One argument a dispatch (ops/conflict.py ``pack_args`` /
``unpack_args`` / ``PackedProgram``): every resolve program, one
device's or a mesh's, takes its batch as ONE uint32 array. What goes in
comes out, field for field (a ``ResolveBatch``, a ``ShardBatch``, with a
batch axis ahead); the programs' verdicts and state are those of
``resolve_batch`` / ``resolve_batch_presharded`` called on the fields;
every lowered program has one parameter beside the state's and the name
the benchmark's trace metrics find it by; and a served commit counts the
host arrays it handed over (``h2d_args``).
"""

import functools
import re
import types

import jax
import numpy as np
import pytest

import foundationdb_tpu as fdb
from foundationdb_tpu.core.options import Knobs
from foundationdb_tpu.ops import conflict as ck
from foundationdb_tpu.parallel import mesh as pm
from foundationdb_tpu.resolver import resolver as resolver_mod
from foundationdb_tpu.resolver.meshresolver import MeshResolver
from foundationdb_tpu.resolver.packing import BatchPacker, ShardRouter
from foundationdb_tpu.resolver.resolver import fast_params_of
from foundationdb_tpu.resolver.skiplist import TxnRequest
from foundationdb_tpu.rpc.service import serve_cluster
from foundationdb_tpu.server.cluster import Cluster

from conftest import TEST_KNOBS

# odd T·K products: a mask's bytes do not fill their last word
PARAMS = ck.ResolverParams(
    txns=6, point_reads=3, point_writes=3, range_reads=1, range_writes=1,
    key_width=3, hash_bits=10, ring_capacity=16, bucket_bits=6)
LANES = 4
P = pm.P


def _random_fields(cls, shapes, rng, lead=()):
    """A batch of ``cls`` with every field random: masks too."""
    out = []
    for name, (char, shape) in zip(cls._fields, shapes):
        shape = lead + shape
        if char == "?":
            out.append(rng.integers(0, 2, shape).astype(np.bool_))
        elif char == "i":
            out.append(rng.integers(-2**31, 2**31, shape).astype(np.int32))
        else:
            out.append(rng.integers(0, 2**32, shape, dtype=np.uint64)
                       .astype(np.uint32))
    return cls(*out)


def _resolve_batch_shapes(p):
    T, W = p.txns, p.key_width
    side = lambda k: [("I", (T, k)), ("I", (T, k, W)), ("i", (T, k)),
                      ("?", (T, k))]
    rng_side = lambda k: [("I", (T, k, W)), ("I", (T, k, W)), ("i", (T, k)),
                          ("i", (T, k)), ("?", (T, k))]
    return ([("I", (T,)), ("?", (T,))] + side(p.point_reads)
            + side(p.point_writes) + rng_side(p.range_reads)
            + rng_side(p.range_writes) + [("I", ()), ("I", ())])


def _shard_batch_shapes(p, q, n):
    """Global shapes of a ShardBatch of ``n`` lanes, ``q`` slots a side
    a lane (odd on purpose)."""
    T, W, Q = p.txns, p.key_width, n * q
    side = [("I", (Q,)), ("I", (Q, W)), ("i", (Q,)), ("i", (Q,)), ("?", (Q,))]
    rng_side = [("I", (Q, W)), ("I", (Q, W)), ("i", (Q,)), ("i", (Q,)),
                ("i", (Q,)), ("?", (Q,))]
    return ([("I", (T,)), ("?", (T,))] + side + side + rng_side + rng_side
            + [("I", ()), ("I", ())])


def _unpacked(buf, layout):
    return jax.jit(lambda b: ck.unpack_args(b, layout))(buf)


def _assert_same(got, want):
    assert type(got) is type(want)
    for name, g, w in zip(want._fields, got, want):
        g = np.asarray(g)
        assert g.dtype == w.dtype and g.shape == w.shape, name
        assert np.array_equal(g, w), name


@pytest.mark.parametrize("variant,lead", [
    ("full", ()), ("fast", ()), ("full", (3,)), ("fast", (2,))])
def test_a_resolve_batch_comes_out_as_it_went_in(variant, lead):
    p = PARAMS if variant == "full" else fast_params_of(PARAMS)
    rng = np.random.default_rng(5)
    batch = _random_fields(ck.ResolveBatch, _resolve_batch_shapes(p), rng,
                           lead)
    layout = ck.arg_layout(batch)
    buf = ck.pack_args(batch, layout)
    assert buf.dtype == np.uint32
    assert buf.shape == lead + (ck.arg_words(layout),)
    # the fields' own bytes (four mask bytes a word), each field padded
    # to a whole word: under 4 bytes more for each of the 22
    rows = int(np.prod(lead, dtype=int))
    assert 0 <= 4 * buf.size - sum(a.nbytes for a in batch) < 4 * 22 * rows
    _assert_same(_unpacked(buf, layout), batch)


@pytest.mark.parametrize("lead", [(), (2,)])
def test_a_shard_batch_comes_out_lane_by_lane(lead):
    rng = np.random.default_rng(6)
    q = 5
    sb = _random_fields(ck.ShardBatch, _shard_batch_shapes(PARAMS, q, LANES),
                        rng, lead)
    layout = ck.arg_layout(sb, LANES)
    buf = ck.pack_args(sb, layout)
    assert buf.shape == lead + (LANES, ck.arg_words(layout))
    got = _unpacked(buf, layout)  # every field with a lane axis
    axis = len(lead)
    for name, g, w in zip(sb._fields, got, sb):
        g = np.asarray(g)
        assert g.dtype == w.dtype, name
        for j in range(LANES):
            lane = np.take(g, j, axis=axis)
            if name in ck.SHARD_REPLICATED:  # whole, in every row
                assert np.array_equal(lane, w), name
            else:  # lane j's q slots
                want = np.take(w, range(j * q, (j + 1) * q), axis=axis)
                assert np.array_equal(lane, want), name


# ── the packed programs against the fields ──────────────────────────
def _txns(rng, n, version, ranges):
    out = []
    for _ in range(n):
        t = TxnRequest(read_version=version - int(rng.integers(0, 12)))
        for _ in range(int(rng.integers(0, 3))):
            t.point_reads.append(b"k%03d" % int(rng.integers(0, 30)))
        for _ in range(int(rng.integers(0, 3))):
            t.point_writes.append(b"k%03d" % int(rng.integers(0, 30)))
        if ranges and rng.integers(0, 3) == 0:
            b = int(rng.integers(0, 28))
            t.range_reads.append((b"k%03d" % b, b"k%03d" % (b + 2)))
        if ranges and rng.integers(0, 4) == 0:
            b = int(rng.integers(0, 28))
            t.range_writes.append((b"k%03d" % b, b"k%03d" % (b + 2)))
        out.append(t)
    return out


def _batches(p, seed, ranges):
    rng = np.random.default_rng(seed)
    packer = BatchPacker(p, use_native=False)
    v = 100
    for _ in range(8):
        v += int(rng.integers(1, 8))
        yield packer.pack(
            _txns(rng, int(rng.integers(1, p.txns + 1)), v, ranges),
            0, v, max(0, v - 30))


def _assert_same_run(packed, fieldwise, state_of, batches):
    sp, sf = state_of(), state_of()
    seen = set()
    for b in batches:
        st_p, acc_p, sp = packed(sp, b)
        st_f, acc_f, sf = fieldwise(sf, b)
        assert np.array_equal(np.asarray(st_p), np.asarray(st_f))
        assert np.array_equal(np.asarray(acc_p), np.asarray(acc_f))
        seen.update(np.asarray(st_p).tolist())
    assert len(seen) > 1  # the script drew more than one verdict
    for name, x, y in zip(sp._fields, sp, sf):
        assert np.array_equal(np.asarray(x), np.asarray(y)), name


def _fieldwise(body, mesh, batch_specs):
    """The program a mesh kernel ran before it took its batch packed:
    ``jit(shard_map(body))`` over the batch's fields."""
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(pm._state_specs(pm.AXIS), batch_specs),
        out_specs=(P(), P(), pm._state_specs(pm.AXIS)), check_vma=False))


@pytest.mark.parametrize("variant", ["full", "fast"])
def test_the_packed_hash_step_is_resolve_batch_on_the_fields(variant):
    p = PARAMS if variant == "full" else fast_params_of(PARAMS)
    mesh = pm.default_mesh(LANES)
    kern = pm.ShardedResolverKernel(p, mesh=mesh, donate=False,
                                    make_state=False)
    fieldwise = _fieldwise(
        functools.partial(ck.resolve_batch, params=p, axis_name=pm.AXIS,
                          n_shards=LANES),
        mesh, jax.tree.map(lambda _: P(),
                           ck.ResolveBatch(*ck.ResolveBatch._fields)))
    _assert_same_run(kern._step, fieldwise, kern.init_state,
                     _batches(p, 31, ranges=variant == "full"))


def test_the_packed_four_lane_step_is_the_presharded_step_on_the_fields():
    mesh = pm.default_mesh(LANES)
    kern = pm.PreshardedResolverKernel(PARAMS, mesh=mesh, donate=False,
                                       make_state=False)
    router = ShardRouter(PARAMS, LANES)
    fieldwise = _fieldwise(
        functools.partial(ck.resolve_batch_presharded, params=PARAMS,
                          axis_name=pm.AXIS),
        mesh, ck.ShardBatch(*(
            P() if f in ck.SHARD_REPLICATED else P(pm.AXIS)
            for f in ck.ShardBatch._fields)))

    def routed():
        for b in _batches(PARAMS, 32, ranges=True):
            sb, k, _ = router.split(
                jax.tree.map(lambda a: np.asarray(a)[None], b))
            assert k == 1
            yield jax.tree.map(lambda a: a[0], sb)

    _assert_same_run(kern._step, fieldwise, kern.init_state, routed())


@pytest.mark.parametrize("variant", ["full", "fast"])
def test_the_packed_one_device_step_is_resolve_batch_on_the_fields(variant):
    p = PARAMS if variant == "full" else fast_params_of(PARAMS)
    fieldwise = jax.jit(functools.partial(ck.resolve_batch, params=p))
    _assert_same_run(ck.make_resolve_fn(p, donate=False), fieldwise,
                     lambda: ck.init_state(p),
                     _batches(p, 33, ranges=variant == "full"))


@pytest.mark.parametrize("variant", ["full", "fast"])
def test_the_packed_one_device_scan_is_resolve_batch_batch_by_batch(variant):
    """Lead axis ``(B,)``: the scan over a stack of packed rows gives
    every batch the verdicts, and leaves the state, that the step on
    the fields gives them one after another."""
    p = PARAMS if variant == "full" else fast_params_of(PARAMS)
    batches = list(_batches(p, 34, ranges=variant == "full"))
    stacked = jax.tree.map(lambda *xs: np.stack(xs), *batches)
    state, statuses = ck.make_resolve_scan_fn(p, donate=False)(
        ck.init_state(p), stacked)
    fieldwise = jax.jit(functools.partial(ck.resolve_batch, params=p))
    want = ck.init_state(p)
    for b, batch in enumerate(batches):
        status, _, want = fieldwise(want, batch)
        assert np.array_equal(np.asarray(statuses[b]), np.asarray(status)), b
    assert len(set(np.asarray(statuses).ravel().tolist())) > 1
    for name, x, y in zip(state._fields, state, want):
        assert np.array_equal(np.asarray(x), np.asarray(y)), name


# ── one parameter beside the state's, under the name it had ─────────
def _programs():
    stacked = lambda b: jax.tree.map(lambda a: np.stack([a, a]), b)
    empty = BatchPacker(PARAMS).pack_empty(0, 1, 0)
    mesh = pm.default_mesh(LANES)
    hashed = pm.ShardedResolverKernel(PARAMS, mesh=mesh, make_state=False)
    yield "hash_step", hashed._step, jax.eval_shape(hashed.init_state), empty
    yield "hash_scan", hashed._scan_step, \
        jax.eval_shape(hashed.init_state), stacked(empty)
    ranged = pm.PreshardedResolverKernel(PARAMS, mesh=mesh, make_state=False)
    sb, _, _ = ShardRouter(PARAMS, LANES).split(stacked(empty))
    yield "range_step", ranged._step, jax.eval_shape(ranged.init_state), \
        jax.tree.map(lambda a: a[0], sb)
    yield "range_scan", ranged._scan_step, \
        jax.eval_shape(ranged.init_state), sb
    # one device: the three modules benchmark/metrics/resolve_step*,
    # range_step* and scan.resolve_step* read by name
    fast = fast_params_of(PARAMS)
    one = jax.eval_shape(lambda: ck.init_state(PARAMS))
    yield "jit__lambda", ck.make_resolve_fn(fast), one, \
        BatchPacker(fast).pack_empty(0, 1, 0)
    yield "jit_resolve_full", ck.make_resolve_fn(PARAMS), one, empty
    yield "jit_scan_step", ck.make_resolve_scan_fn(PARAMS), one, \
        stacked(empty)


@pytest.mark.parametrize("program", [
    "hash_step", "hash_scan", "range_step", "range_scan",
    "jit__lambda", "jit_resolve_full", "jit_scan_step"])
def test_a_lowered_program_takes_the_state_and_one_array(program):
    fn, state, batch = next(
        (f, s, b) for name, f, s, b in _programs() if name == program)
    assert isinstance(fn, ck.PackedProgram)
    text = fn.lower(state, batch).as_text()
    if program.startswith("jit_"):
        assert text.startswith(f"module @{program} ")
    main = re.search(r"func\.func public @main\((.*?)\) ->", text, re.S)
    params = re.findall(r"%arg\d+: tensor<([^>]*)>", main.group(1))
    assert len(params) == len(jax.tree.leaves(state)) + 1
    words = ck.arg_words(ck.arg_layout(batch, fn.lanes))
    assert params[-1].startswith(
        "x".join(map(str, batch.rv.shape[:-1] + (
            (fn.lanes,) if fn.lanes else ()) + (words,))) + "xui32")


# ── what the 22 arguments did beside carrying the batch ─────────────
@pytest.mark.parametrize("lanes", [1, LANES])
def test_a_one_lane_dispatch_offers_the_interpreter_once_a_transaction(
        monkeypatch, lanes):
    """The 22-array call gave the interpreter lock up 22 times; the
    one-array call of one device offers it instead, once a transaction
    of the batch, after the launch. A mesh dispatch never did."""
    knobs = Knobs(resolver_backend="tpu", **TEST_KNOBS)
    r = (resolver_mod.Resolver(knobs) if lanes == 1
         else MeshResolver(knobs, n_lanes=lanes))
    offers = []
    monkeypatch.setattr(resolver_mod, "time",
                        types.SimpleNamespace(sleep=offers.append))
    txns = [TxnRequest(read_version=5, point_reads=[b"a%d" % i],
                       point_writes=[b"b%d" % i]) for i in range(5)]
    assert r.resolve(txns, 10, 0) == [ck.COMMITTED] * 5
    assert offers == ([0] * 5 if lanes == 1 else [])


# ── the counter that says so ────────────────────────────────────────
def _increment(tr):
    v = tr.get(b"n")
    tr.set(b"n", b"%d" % (int(v or b"0") + 1))


@pytest.mark.parametrize("resolvers", [1, LANES])
def test_a_served_commit_counts_the_host_arrays_it_handed_over(resolvers):
    """One array a dispatch, on one device as on a mesh."""
    cluster = Cluster(resolver_backend="tpu", commit_pipeline="thread",
                      n_resolvers=resolvers, **TEST_KNOBS)
    server = serve_cluster(cluster)
    db = fdb.open(address=server.address)
    try:
        for _ in range(4):
            db.run(_increment)
        agg = db.status()["cluster"]["device"]["aggregate"]
    finally:
        db._cluster.close()
        server.close()
        cluster.close()
    assert agg["dispatches"] >= 4
    assert agg["h2d_args"] == agg["dispatches"]
    assert (agg["route_dispatches"] > 0) == (resolvers > 1)
