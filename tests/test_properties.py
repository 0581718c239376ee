"""Property-based tests (hypothesis) on the system's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis", reason="optional dev dep (see requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.core.embedding.strategies import _bucket_by_owner
from repro.kernels import ops, ref
from repro.optim.optimizers import clip_by_global_norm

SETTINGS = dict(max_examples=25, deadline=None)


# ---------------------------------------------------------------------------
# Pallas lookup kernel: linearity + permutation/padding invariances
# ---------------------------------------------------------------------------

@settings(**SETTINGS)
@given(st.integers(1, 40), st.integers(1, 6), st.integers(8, 200),
       st.integers(0, 2 ** 31 - 1))
def test_lookup_matches_oracle_random_shapes(b, h, v, seed):
    d = 16
    key = jax.random.PRNGKey(seed)
    table = jax.random.normal(key, (v, d), jnp.float32)
    rows = jax.random.randint(jax.random.fold_in(key, 1), (b, h), -1, v)
    got = ops.fused_embedding_lookup(table, rows)
    want = ref.embedding_lookup_ref(table, rows)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@settings(**SETTINGS)
@given(st.integers(0, 2 ** 31 - 1))
def test_lookup_is_linear_in_table(seed):
    v, d, b, h = 64, 8, 9, 3
    key = jax.random.PRNGKey(seed)
    t1 = jax.random.normal(key, (v, d))
    t2 = jax.random.normal(jax.random.fold_in(key, 1), (v, d))
    rows = jax.random.randint(jax.random.fold_in(key, 2), (b, h), -1, v)
    lhs = ops.fused_embedding_lookup(t1 + 2.0 * t2, rows)
    rhs = (ops.fused_embedding_lookup(t1, rows)
           + 2.0 * ops.fused_embedding_lookup(t2, rows))
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs),
                               rtol=1e-4, atol=1e-4)


@settings(**SETTINGS)
@given(st.integers(0, 2 ** 31 - 1))
def test_lookup_hotness_permutation_invariant(seed):
    """Sum pooling must not care about the order of ids within a sample."""
    v, d, b, h = 50, 8, 6, 5
    key = jax.random.PRNGKey(seed)
    table = jax.random.normal(key, (v, d))
    rows = jax.random.randint(jax.random.fold_in(key, 1), (b, h), -1, v)
    perm = jax.random.permutation(jax.random.fold_in(key, 2), h)
    np.testing.assert_allclose(
        np.asarray(ops.fused_embedding_lookup(table, rows)),
        np.asarray(ops.fused_embedding_lookup(table, rows[:, perm])),
        rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Bucketing (all-to-all id routing): conservation + capacity laws
# ---------------------------------------------------------------------------

@settings(**SETTINGS)
@given(st.integers(1, 64), st.integers(1, 8), st.integers(1, 16),
       st.integers(0, 2 ** 31 - 1))
def test_bucket_by_owner_invariants(m, n_shards, capacity, seed):
    rng = np.random.default_rng(seed)
    flat = jnp.asarray(rng.integers(-1, n_shards * 13, m), jnp.int32)
    send, slot_of, valid, fill = jax.jit(
        _bucket_by_owner, static_argnums=(1, 2))(flat, n_shards, capacity)
    send = np.asarray(send)
    slot_of = np.asarray(slot_of)
    valid = np.asarray(valid)
    fill = np.asarray(fill)
    flat = np.asarray(flat)

    # 1. every valid id landed in its owner's bucket at the slot recorded
    for i in range(m):
        if valid[i]:
            owner, pos = divmod(int(slot_of[i]), capacity)
            assert owner == flat[i] % n_shards
            assert send[owner, pos] == flat[i] // n_shards
    # 2. capacity respected: per owner, at most `capacity` valid entries
    for s in range(n_shards):
        assert (send[s] >= 0).sum() <= capacity
    # 3. padding ids are never valid
    assert not valid[flat < 0].any() if (flat < 0).any() else True
    # 4. an id is dropped ONLY if its owner bucket is full
    for i in range(m):
        if flat[i] >= 0 and not valid[i]:
            assert (send[flat[i] % n_shards] >= 0).sum() == capacity
    # 5. fill counts each owner's ids before the cut; what the cut drops
    #    is each owner's excess over capacity
    owners = flat[flat >= 0] % n_shards
    np.testing.assert_array_equal(fill, np.bincount(owners,
                                                    minlength=n_shards))
    dropped = int(((flat >= 0) & ~valid).sum())
    assert dropped == int(np.maximum(fill - capacity, 0).sum())


# ---------------------------------------------------------------------------
# Optimizer invariants
# ---------------------------------------------------------------------------

@settings(**SETTINGS)
@given(st.floats(0.1, 10.0), st.integers(0, 2 ** 31 - 1))
def test_clip_by_global_norm_bound(max_norm, seed):
    key = jax.random.PRNGKey(seed)
    g = {"a": jax.random.normal(key, (7, 3)) * 100,
         "b": jax.random.normal(jax.random.fold_in(key, 1), (5,)) * 100}
    clipped, norm = clip_by_global_norm(g, max_norm)
    total = float(jnp.sqrt(sum(jnp.sum(x ** 2)
                               for x in jax.tree.leaves(clipped))))
    assert total <= max_norm * 1.01


@settings(**SETTINGS)
@given(st.integers(0, 2 ** 31 - 1))
def test_rowwise_adagrad_touches_only_accessed_rows(seed):
    """Rows with zero gradient must not move (sparse-update semantics)."""
    from repro.configs.base import TrainConfig
    from repro.optim.sparse import rowwise_adagrad

    opt = rowwise_adagrad(TrainConfig(learning_rate=0.1))
    key = jax.random.PRNGKey(seed)
    p = {"t": jax.random.normal(key, (20, 4))}
    state = opt.init(p)
    g = jnp.zeros((20, 4)).at[3].set(1.0).at[7].set(-2.0)
    new_p, new_state = opt.update({"t": g}, state, p)
    moved = np.abs(np.asarray(new_p["t"]) - np.asarray(p["t"])).sum(axis=1)
    assert moved[3] > 0 and moved[7] > 0
    untouched = [i for i in range(20) if i not in (3, 7)]
    np.testing.assert_allclose(moved[untouched], 0.0, atol=1e-7)


# ---------------------------------------------------------------------------
# Checkpoint roundtrip property
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_checkpoint_roundtrip_random_trees(seed):
    import tempfile
    from repro.train import checkpoint as ck
    rng = np.random.default_rng(seed)
    tree = {
        "w": rng.normal(size=(rng.integers(1, 8), rng.integers(1, 8)))
        .astype(np.float32),
        "nested": {"k": rng.integers(0, 100, size=(3,)).astype(np.int64)},
    }
    with tempfile.TemporaryDirectory() as d:
        ck.save(d, 0, tree)
        flat, _ = ck.load(d, 0)
        out = ck.unflatten_like(tree, flat)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b),
                     tree, out)


# ---------------------------------------------------------------------------
# Synthetic data: determinism + Zipf shape
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(st.integers(0, 1000))
def test_synthetic_batches_are_deterministic(step):
    from repro.configs.registry import RECSYS_ARCHS, reduce_recsys_for_smoke
    from repro.data.synthetic import SyntheticCTR
    cfg = reduce_recsys_for_smoke(RECSYS_ARCHS["dlrm-criteo"])
    a = SyntheticCTR(cfg, 8).batch(step)
    b = SyntheticCTR(cfg, 8).batch(step)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])


def test_synthetic_ids_are_zipf_distributed():
    from repro.configs.registry import RECSYS_ARCHS
    from repro.data.synthetic import SyntheticCTR
    cfg = RECSYS_ARCHS["dlrm-criteo"]
    ds = SyntheticCTR(cfg, 4096)
    cat = ds.batch(0)["cat"]
    big = cat[:, 2, 0]      # a 10M-vocab table
    # rank 0 must dominate: top-1% of ids should cover >> 1% of accesses
    frac_small = (big < cfg.tables[2].vocab_size // 100).mean()
    assert frac_small > 0.5
