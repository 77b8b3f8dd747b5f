"""Hypothesis property tests on the system's invariants (brief deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dep: skip cleanly, don't break collection
from hypothesis import given, settings, strategies as st

from repro.api.pipeline import (AggregationContext, ClipStage, MaskStage,
                                PrivacyPipeline, QuantizeStage, TopKStage,
                                fuse_pipeline)
from repro.checkpoint import load_state, pack_tree, save_state, unpack_tree
from repro.engine import EventQueue, synthetic_trace, trace_hash
from repro.engine import traces as engine_traces
from repro.fl.paramspace import ParamSpace
from repro.kernels import compress as compress_mod
from repro.privacy import quantize, secure_agg
from repro.topo import graph as topo_graph
from repro.utils import clip_by_global_norm, tree_ravel, tree_unravel

SET = dict(max_examples=25, deadline=None)

# -- random pytree strategy for the ParamSpace invariants -------------------

_DTYPES = (np.float32, np.float16, np.int32)

_leaf_shape = st.lists(st.integers(min_value=1, max_value=5), min_size=0, max_size=3).map(tuple)


@st.composite
def _pytrees(draw):
    """Nested dict pytrees with mixed dtypes and 0-d/1-d/2-d/3-d leaves."""
    rng = np.random.default_rng(draw(st.integers(0, 2**31 - 1)))
    n_leaves = draw(st.integers(min_value=1, max_value=6))
    tree: dict = {}
    for i in range(n_leaves):
        shape = draw(_leaf_shape)
        dtype = draw(st.sampled_from(_DTYPES))
        if np.issubdtype(dtype, np.integer):
            leaf = rng.integers(-1000, 1000, shape).astype(dtype)  # exact in f32
        else:
            leaf = rng.normal(0, 2, shape).astype(dtype)
        node, depth = tree, draw(st.integers(0, 2))
        for d in range(depth):
            node = node.setdefault(f"sub{d}", {})  # "sub*" names never hold leaves
        node[f"leaf{i}"] = jnp.asarray(leaf)
    return tree


@given(
    st.integers(min_value=2, max_value=12).map(lambda b: 1 << b),  # vector size
    st.integers(min_value=10, max_value=24),                        # bits
    st.floats(min_value=0.1, max_value=16.0),                       # clip
    st.integers(min_value=0, max_value=2**31 - 1),                  # seed
)
@settings(**SET)
def test_quantize_roundtrip_always_within_bound(n, bits, clip, seed):
    rng = np.random.default_rng(seed)
    x = rng.normal(0, clip / 2, n).astype(np.float32)
    q = quantize.encode(jnp.asarray(x), clip, bits)
    back = np.asarray(quantize.decode_sum(q, clip, bits, 1))
    assert np.max(np.abs(back - np.clip(x, -clip, clip))) <= quantize.quant_error_bound(clip, bits) * 1.01


@given(
    st.integers(min_value=2, max_value=12),    # n clients
    st.integers(min_value=1, max_value=500),   # dim
    st.integers(min_value=0, max_value=2**31 - 1),
)
@settings(**SET)
def test_pairwise_masks_always_cancel(n, dim, seed):
    """sum_i mask_i == 0 in the ring, for any roster and session."""
    total = np.zeros(dim, np.uint32)
    clients = list(range(n))
    for i in clients:
        total = total + secure_agg.pairwise_mask(i, clients, dim, session=seed)
    assert np.array_equal(total, np.zeros(dim, np.uint32))


@given(
    st.integers(min_value=2, max_value=10),
    st.integers(min_value=4, max_value=200),
    st.integers(min_value=0, max_value=2**31 - 1),
)
@settings(**SET)
def test_masked_aggregation_linearity(n, dim, seed):
    """decode(sum(encode(x_i))) ~= sum(x_i): the additive-HE contract."""
    rng = np.random.default_rng(seed)
    ups = rng.normal(0, 0.2, (n, dim)).astype(np.float32)
    got = secure_agg.aggregate_floats_bonawitz(
        {i: ups[i] for i in range(n)}, clip=4.0, bits=20, session=seed
    )
    bound = n * quantize.quant_error_bound(4.0, 20) + 1e-6
    assert np.max(np.abs(got - ups.sum(0))) <= bound


@given(
    st.floats(min_value=0.01, max_value=100.0),
    st.integers(min_value=0, max_value=2**31 - 1),
)
@settings(**SET)
def test_clip_never_exceeds_bound_and_preserves_direction(max_norm, seed):
    rng = np.random.default_rng(seed)
    tree = {"a": jnp.asarray(rng.normal(0, 5, 64).astype(np.float32)),
            "b": jnp.asarray(rng.normal(0, 5, (4, 4)).astype(np.float32))}
    clipped, pre = clip_by_global_norm(tree, max_norm)
    flat_c, _ = tree_ravel(clipped)
    flat_o, _ = tree_ravel(tree)
    post = float(jnp.linalg.norm(flat_c))
    assert post <= max_norm * 1.001
    if float(pre) > 0:
        cos = float(jnp.dot(flat_c, flat_o) / (jnp.linalg.norm(flat_c) * jnp.linalg.norm(flat_o) + 1e-12))
        assert cos > 0.9999  # clipping only rescales


@given(_pytrees())
@settings(**SET)
def test_paramspace_ravel_roundtrip_any_tree(tree):
    """unravel(ravel(t)) == t for arbitrary nesting, shapes and dtypes."""
    ps = ParamSpace.build(tree)
    row = ps.ravel(tree)
    assert row.shape == (ps.dim,) and row.dtype == jnp.float32
    back = ps.unravel(row)
    assert jax.tree.structure(back) == jax.tree.structure(tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@given(_pytrees(), st.integers(min_value=1, max_value=4))
@settings(**SET)
def test_paramspace_stack_roundtrip_and_padding(tree, k):
    """stack/unstack round-trips k-cohorts; pad_rows only appends zeros."""
    ps = ParamSpace.build(tree)
    stacked = jax.tree.map(lambda x: jnp.stack([x + i for i in range(k)]).astype(x.dtype)
                           if jnp.issubdtype(x.dtype, jnp.floating)
                           else jnp.stack([x] * k), tree)
    rows = ps.stack(stacked)
    assert rows.shape == (k, ps.dim)
    back = ps.unstack(rows)
    for a, b in zip(jax.tree.leaves(stacked), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    padded = ps.pad_rows(rows)
    assert padded.shape == (k, ps.padded_dim) and ps.padded_dim % ps.align == 0
    np.testing.assert_array_equal(np.asarray(padded[:, ps.dim:]), 0.0)
    # unravel ignores the padding entirely
    for a, b in zip(jax.tree.leaves(ps.unravel(padded[0])),
                    jax.tree.leaves(ps.unravel(rows[0]))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@given(st.integers(min_value=0, max_value=2**31 - 1))
@settings(**SET)
def test_tree_ravel_roundtrip(seed):
    rng = np.random.default_rng(seed)
    tree = {
        "w": jnp.asarray(rng.normal(size=(3, 5)).astype(np.float32)),
        "nested": {"b": jnp.asarray(rng.normal(size=(7,)).astype(np.float32))},
    }
    flat, td = tree_ravel(tree)
    back = tree_unravel(td, flat)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


# -- fused delta-to-wire compression (kernels/compress.py) ------------------


def _flat_space(dim: int) -> ParamSpace:
    return ParamSpace.build({"w": jnp.zeros((dim,), jnp.float32)})


@given(
    st.integers(min_value=1, max_value=9),          # cohort size k
    st.integers(min_value=2, max_value=6000),       # dim (unpadded params)
    st.floats(min_value=0.05, max_value=20.0),      # clip
    st.integers(min_value=10, max_value=24),        # ring bits
    st.integers(min_value=0, max_value=2**31 - 1),  # seed
)
@settings(max_examples=15, deadline=None)
def test_fused_compress_bitwise_equals_staged_stages(k, dim, clip, bits, seed):
    """The fused Pallas kernel (interpret mode) reproduces the staged
    ClipStage -> QuantizeStage -> MaskStage composition through the real
    pipeline executor: same StageRecords, and ciphertexts that decode within
    one quantization step.  Not bitwise: the fused norm is a different
    reduction program from ClipStage's, so the clip factor may move by an
    ulp and a value on a rounding boundary may round the other way (e.g.
    k=1, dim=22, clip=1.0, bits=22, seed=1)."""
    ps = _flat_space(dim)
    rng = np.random.default_rng(seed)
    rows = jnp.asarray(rng.normal(0, clip, (k, dim)).astype(np.float32))
    stages = (ClipStage(clip), QuantizeStage(clip, bits), MaskStage())
    staged = PrivacyPipeline(stages, weighting="uniform")
    fused = fuse_pipeline(staged)
    assert [s.name for s in fused.stages] == ["fused_compress"]
    assert fused.describe() == staged.describe()

    def run_rows(pipe):
        ctx = AggregationContext(
            ps, k, [1.0] * k, jax.random.PRNGKey(seed % 997),
            jax.random.PRNGKey(1), lambda r, w: jnp.einsum("kp,k->p", r, w),
        )
        out = rows
        for s in pipe.stages:
            out = s.apply(out, ctx)
        return np.asarray(out), ctx.records, ctx.masks

    def ring_steps(a, b):  # |a - b| in quantization steps (same pads)
        return np.abs((np.asarray(a) - np.asarray(b)).view(np.int32)).max()

    c_staged, rec_staged, masks = run_rows(staged)
    c_fused, rec_fused, _ = run_rows(fused)
    assert c_fused.shape == c_staged.shape
    assert ring_steps(c_fused, c_staged) <= 1
    assert rec_fused == rec_staged
    # and the Pallas interpreter itself agrees with both
    interp = compress_mod.clip_quant_mask(
        ps.pad_rows(rows), masks, clip, bits, dim=dim, interpret=True
    )
    assert ring_steps(interp, c_staged) <= 1


@given(
    st.integers(min_value=1, max_value=8),          # cohort size k
    st.integers(min_value=2, max_value=3000),       # dim
    st.floats(min_value=0.01, max_value=1.0),       # density
    st.integers(min_value=0, max_value=2**31 - 1),  # seed
    st.integers(min_value=1, max_value=5),          # participation rounds
)
@settings(max_examples=15, deadline=None)
def test_ef_topk_residuals_preserve_mean(k, dim, density, seed, rounds):
    """Error feedback drops nothing: after any number of participations,
    what was sent plus what is still banked equals everything that was ever
    produced — so mean(compressed) + mean(residual_delta) == mean(delta)."""
    ps = _flat_space(dim)
    rng = np.random.default_rng(seed)
    stage = TopKStage(density)
    clients = np.arange(k, dtype=np.int32)
    residuals = jnp.zeros((k, dim), jnp.float32)
    sent_total = np.zeros(dim, np.float64)
    delta_total = np.zeros(dim, np.float64)
    for r in range(rounds):
        deltas = jnp.asarray(rng.normal(0, 1, (k, dim)).astype(np.float32))
        ctx = AggregationContext(
            ps, k, [1.0] * k, jax.random.PRNGKey(0), jax.random.PRNGKey(1),
            lambda rw, w: jnp.einsum("kp,k->p", rw, w),
            clients=clients, residuals=residuals,
        )
        sparse = stage.apply(deltas, ctx)
        residuals = ctx.residuals
        # per-round exact invariant: sparse + residual_new = delta + residual_old
        sent_total += np.asarray(sparse, np.float64).mean(0)
        delta_total += np.asarray(deltas, np.float64).mean(0)
        (rec,) = [x for x in ctx.records if x.stage == "topk"]
        assert rec.info["k_kept"] == max(1, round(density * dim))
        nnz = np.count_nonzero(np.asarray(sparse), axis=1)
        assert (nnz <= rec.info["k_kept"]).all()  # zeros in top-k stay zero
    residual_mean = np.asarray(residuals, np.float64).mean(0)
    np.testing.assert_allclose(sent_total + residual_mean, delta_total,
                               rtol=1e-4, atol=1e-4)


# -- mixing-matrix invariants (repro.topo) ----------------------------------


@given(
    st.sampled_from(sorted(topo_graph.GRAPHS)),
    st.integers(min_value=1, max_value=24),        # nodes
    st.integers(min_value=0, max_value=50),        # round (time-varying graphs)
    st.integers(min_value=0, max_value=2**31 - 1),  # seed (erdos)
    st.floats(min_value=0.05, max_value=1.0),      # edge probability (erdos)
)
@settings(**SET)
def test_metropolis_mixing_matrix_invariants(name, n, rnd, seed, p):
    """Every registered topology yields symmetric, doubly-stochastic,
    nonnegative Metropolis weights, and contracts (SLEM < 1) whenever the
    round's graph is connected."""
    plan = topo_graph.plan(name, n, rnd, seed=seed, p=p)
    W = np.asarray(plan.mixing, np.float64)
    assert W.shape == (n, n)
    np.testing.assert_allclose(W, W.T, atol=1e-7)           # symmetric
    np.testing.assert_allclose(W.sum(axis=1), 1.0, atol=1e-6)  # rows sum to 1
    np.testing.assert_allclose(W.sum(axis=0), 1.0, atol=1e-6)  # cols sum to 1
    assert (W >= -1e-9).all()                                # nonnegative
    if n > 1 and topo_graph.is_connected(plan.adjacency):
        assert plan.slem < 1.0 - 1e-9
        assert 0.0 < plan.spectral_gap <= 1.0 + 1e-9
        assert plan.consensus_rounds() < float("inf")


@given(
    st.integers(min_value=2, max_value=16),
    st.integers(min_value=0, max_value=2**31 - 1),
)
@settings(**SET)
def test_mixing_preserves_average_for_any_connected_graph(n, seed):
    """x <- Wx keeps the fleet mean invariant (doubly-stochastic contract)
    and never expands disagreement."""
    rng = np.random.default_rng(seed)
    name = ("ring", "torus", "full", "one_peer")[seed % 4]
    W = np.asarray(topo_graph.plan(name, n, rnd=seed % 7).mixing, np.float64)
    x = rng.normal(0, 1, (n, 32))
    mixed = W @ x
    np.testing.assert_allclose(mixed.mean(axis=0), x.mean(axis=0), atol=1e-9)
    dev = lambda y: np.linalg.norm(y - y.mean(axis=0, keepdims=True))
    assert dev(mixed) <= dev(x) * (1.0 + 1e-9)


@given(
    st.integers(min_value=1, max_value=6),
    st.integers(min_value=0, max_value=2**31 - 1),
)
@settings(max_examples=10, deadline=None)
def test_stochastic_rounding_unbiased(k, seed):
    """E[decode(encode_stochastic(x))] -> x (quantizer unbiasedness)."""
    x = jnp.full((256,), 0.1234567 * k)
    acc = np.zeros(256)
    trials = 64
    for i in range(trials):
        q = quantize.encode(x, 1.0, 10, key=jax.random.fold_in(jax.random.PRNGKey(seed), i))
        acc += np.asarray(quantize.decode_sum(q, 1.0, 10, 1))
    mean = acc / trials
    step = quantize.quant_error_bound(1.0, 10)
    assert np.max(np.abs(mean - np.clip(0.1234567 * k, -1, 1))) < step


# -- federation-state store: save -> load is the identity -------------------
# (the fault-tolerance contract: ANY strategy state container round-trips
# bitwise through the msgpack+npz checkpoint store)

_STATE_DTYPES = (np.float32, np.float16, np.int32, np.uint32)

_state_scalars = st.one_of(
    st.none(), st.booleans(),
    st.integers(min_value=-(2**60), max_value=2**60),
    st.floats(allow_nan=False),       # inf round-trips; NaN breaks == by design
    st.text(max_size=12),
)


@st.composite
def _state_arrays(draw):
    rng = np.random.default_rng(draw(st.integers(0, 2**31 - 1)))
    shape = draw(_leaf_shape)
    dtype = draw(st.sampled_from(_STATE_DTYPES))
    if np.issubdtype(dtype, np.integer):
        info = np.iinfo(dtype)
        return rng.integers(info.min, info.max, shape, dtype=dtype, endpoint=True)
    return rng.normal(0, 2, shape).astype(dtype)


_state_keys = st.text(max_size=8).filter(lambda k: k != "__ndarray__")

_state_containers = st.recursive(
    st.one_of(_state_scalars, _state_arrays()),
    lambda kids: st.one_of(
        st.lists(kids, max_size=4),
        st.dictionaries(_state_keys, kids, max_size=4),
    ),
    max_leaves=12,
)


def _state_eq(a, b):
    """Structural equality after a store round-trip (tuples load as lists;
    array identity is dtype + shape + bitwise values)."""
    if isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
        a, b = np.asarray(a), np.asarray(b)
        return a.dtype == b.dtype and a.shape == b.shape and np.array_equal(a, b)
    if isinstance(a, dict):
        return (isinstance(b, dict) and set(a) == set(b)
                and all(_state_eq(a[k], b[k]) for k in a))
    if isinstance(a, (list, tuple)):
        return (isinstance(b, (list, tuple)) and len(a) == len(b)
                and all(_state_eq(x, y) for x, y in zip(a, b)))
    return type(a) is type(b) and a == b


@given(_state_containers, st.integers(min_value=0, max_value=10**6))
@settings(max_examples=20, deadline=None)
def test_state_store_roundtrip_identity(tmp_path_factory, state, rnd):
    path = str(tmp_path_factory.getbasetemp() / "state-prop")
    save_state(path, state, metadata={"round": rnd})  # overwrites: atomic swap
    back, meta = load_state(path)
    assert meta == {"round": rnd}
    assert _state_eq(state, back)


@given(_pytrees())
@settings(**SET)
def test_pack_tree_roundtrip_identity(tree):
    back = unpack_tree(pack_tree(tree), jax.tree.map(jnp.zeros_like, tree))
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert np.asarray(a).dtype == np.asarray(b).dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@given(
    _pytrees(),
    st.sampled_from(["dtype", "shape", "rename", "drop"]),
    st.integers(min_value=0, max_value=10**6),
)
@settings(**SET)
def test_unpack_tree_rejects_any_single_mutation(tree, mode, pick):
    """Restore is all-or-nothing: mutating ANY one stored leaf (dtype, shape,
    name, or presence) makes unpack_tree raise instead of restoring."""
    packed = pack_tree(tree)
    name = sorted(packed["leaves"])[pick % len(packed["leaves"])]
    arr = packed["leaves"][name]
    if mode == "dtype":
        packed["leaves"][name] = arr.astype(
            np.float64 if arr.dtype != np.float64 else np.float32
        )
    elif mode == "shape":
        packed["leaves"][name] = np.concatenate(
            [arr.reshape(-1), np.zeros(1, arr.dtype)]
        )
    elif mode == "rename":
        packed["leaves"][name + "_x"] = packed["leaves"].pop(name)
    else:
        del packed["leaves"][name]
    with pytest.raises(ValueError):
        unpack_tree(packed, tree)


# ---------------------------------------------------------------------------
# repro.engine: trace round-trip identity + event-queue ordering (PR 9)
# ---------------------------------------------------------------------------
@given(
    st.integers(min_value=4, max_value=40),             # n_clients
    st.floats(min_value=0.1, max_value=6.0),            # sim_hours
    st.integers(min_value=1, max_value=4),              # n_regions
    st.floats(min_value=0.2, max_value=8.0),            # arrivals/client/h
    st.integers(min_value=0, max_value=10**6),          # seed
    st.sampled_from(["jsonl", "npz"]),
)
@settings(**SET)
def test_trace_roundtrip_identity(tmp_path_factory, n, hours, regions, rate,
                                  seed, ext):
    """save→load is the identity for BOTH on-disk forms: header equal,
    every array bitwise equal (jsonl floats survive via shortest-repr),
    and the content hash — the resume guard — unchanged."""
    trace = synthetic_trace(n, hours, n_regions=regions,
                            rate_per_client_per_h=rate, seed=seed)
    path = str(tmp_path_factory.getbasetemp() / f"trace-prop.{ext}")
    trace.save(path)
    back = engine_traces.load(path)
    assert back.header == trace.header
    for f in ("arrival_t_s", "arrival_client", "arrival_latency_s",
              "carbon_t_s", "carbon_intensity"):
        a, b = getattr(trace, f), getattr(back, f)
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(a, b)
    assert trace_hash(back) == trace_hash(trace)


# few distinct times -> many ties, exercising the FIFO tie-break contract
_event_times = st.one_of(
    st.sampled_from([0.0, 1.0, 2.0, 3.0]),
    st.floats(min_value=0.0, max_value=100.0,
              allow_nan=False, allow_infinity=False),
)


@given(st.lists(_event_times, max_size=120))
@settings(**SET)
def test_event_queue_time_ordered_with_stable_ties(times):
    """Pops are globally time-ordered and, among equal times, FIFO in
    insertion order — for ANY push sequence."""
    q = EventQueue()
    for k, t in enumerate(times):
        q.push(t, k)  # payload = insertion index
    popped = [q.pop() for _ in range(len(q))]
    assert not q and q.peek_time() is None
    ts = [t for t, _, _ in popped]
    assert ts == sorted(ts)
    for (t1, _, k1), (t2, _, k2) in zip(popped, popped[1:]):
        if t1 == t2:
            assert k1 < k2  # stable: earlier push pops first
    # nothing lost, nothing duplicated
    assert sorted(k for _, _, k in popped) == list(range(len(times)))


@given(st.lists(_event_times, max_size=80), st.integers(0, 80))
@settings(**SET)
def test_event_queue_checkpoint_pops_identically(times, consume):
    """state_dict→load_state_dict at ANY point mid-drain: the restored
    queue pops the identical remaining (t, seq, payload) sequence."""
    q = EventQueue()
    for k, t in enumerate(times):
        q.push(t, k)
    for _ in range(min(consume, len(q))):
        q.pop()
    q2 = EventQueue()
    q2.load_state_dict(q.state_dict())
    assert [q2.pop() for _ in range(len(q2))] == \
           [q.pop() for _ in range(len(q))]
