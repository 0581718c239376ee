"""Per-kernel allclose vs the pure-jnp oracles (interpret mode on CPU),
with shape/dtype sweeps per the assignment."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import ref
from repro.kernels import embedding_lookup as el
from repro.kernels import dot_interaction as di
from repro.kernels import hps_gather as hg


# ---------------------------------------------------------------------------
# fused_embedding_lookup
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("v,d,b,h", [
    (64, 8, 16, 1),        # one-hot
    (1000, 64, 37, 3),     # multi-hot, non-aligned batch
    (513, 16, 8, 7),       # vocab not multiple of block
    (2048, 128, 128, 2),   # aligned, MXU-shaped
])
def test_lookup_matches_oracle(v, d, b, h):
    table = jax.random.normal(jax.random.PRNGKey(0), (v, d), jnp.float32)
    rows = jax.random.randint(jax.random.PRNGKey(1), (b, h), -1, v)
    out = ops.fused_embedding_lookup(table, rows)
    expected = ref.embedding_lookup_ref(table, rows)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lookup_dtypes(dtype):
    v, d, b, h = 256, 32, 24, 2
    table = jax.random.normal(jax.random.PRNGKey(0), (v, d)).astype(dtype)
    rows = jax.random.randint(jax.random.PRNGKey(1), (b, h), -1, v)
    out = ops.fused_embedding_lookup(table, rows)
    expected = ref.embedding_lookup_ref(table.astype(jnp.float32), rows)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=tol, atol=tol)


def test_lookup_grad_matches_oracle():
    v, d, b, h = 300, 24, 19, 4
    table = jax.random.normal(jax.random.PRNGKey(0), (v, d), jnp.float32)
    rows = jax.random.randint(jax.random.PRNGKey(1), (b, h), -1, v)

    def loss_k(t):
        return (ops.fused_embedding_lookup(t, rows) ** 2).sum()

    def loss_r(t):
        return (ref.embedding_lookup_ref(t, rows) ** 2).sum()

    g1 = jax.grad(loss_k)(table)
    g2 = jax.grad(loss_r)(table)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-5, atol=1e-5)


def test_lookup_all_padding_rows():
    table = jnp.ones((64, 8), jnp.float32)
    rows = jnp.full((4, 3), -1, jnp.int32)
    out = ops.fused_embedding_lookup(table, rows)
    np.testing.assert_array_equal(np.asarray(out), 0.0)


def test_lookup_duplicate_ids_count_semantics():
    table = jax.random.normal(jax.random.PRNGKey(0), (32, 4))
    rows = jnp.asarray([[5, 5, 5]], jnp.int32)
    out = ops.fused_embedding_lookup(table, rows)
    np.testing.assert_allclose(np.asarray(out)[0], 3 * np.asarray(table)[5],
                               rtol=1e-6)


def test_lookup_bwd_kernel_direct():
    """The raw bwd kernel equals the scatter-add oracle."""
    v, d, b, h = 512, 16, 128, 2
    rows = jax.random.randint(jax.random.PRNGKey(1), (b, h), -1, v)
    dpool = jax.random.normal(jax.random.PRNGKey(2), (b, d), jnp.float32)
    got = el.lookup_bwd((v, d), rows, dpool, block_b=64, block_v=128,
                        interpret=True)
    want = ref.embedding_grad_ref((v, d), rows, dpool)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("block_b,block_v", [(8, 64), (64, 512), (128, 128)])
def test_lookup_block_shape_sweep(block_b, block_v):
    v, d, b, h = 640, 32, 96, 2
    table = jax.random.normal(jax.random.PRNGKey(0), (v, d), jnp.float32)
    rows = jax.random.randint(jax.random.PRNGKey(1), (b, h), -1, v)
    out = ops.fused_embedding_lookup(table, rows, block_b, block_v)
    expected = ref.embedding_lookup_ref(table, rows)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# hps_gather: f16 payloads
# ---------------------------------------------------------------------------

def test_f16_bits_widen_exactly():
    """Every uint16 pattern widens to the f32 numpy gives for that f16
    (zeros, subnormals, normals, inf; nan stays nan with its sign)."""
    bits = np.arange(65536, dtype=np.uint16)
    want = bits.view(np.float16).astype(np.float32)
    got = np.asarray(hg._f16_bits_to_f32(jnp.asarray(bits)))
    nan = np.isnan(want)
    np.testing.assert_array_equal(np.isnan(got), nan)
    np.testing.assert_array_equal(np.signbit(got[nan]), np.signbit(want[nan]))
    np.testing.assert_array_equal(got[~nan].view(np.uint32),
                                  want[~nan].view(np.uint32))


def _f16_payload(c, d):
    """Finite f16 rows spanning normals, subnormals and signed zeros."""
    rng = np.random.default_rng(0)
    x = rng.standard_normal((c, d)).astype(np.float16)
    x[::7] *= np.float16(2.0 ** -16)             # subnormal rows
    x[3] = np.float16(-0.0)
    x[5] = np.float16(65504.0)                  # largest finite
    return jnp.asarray(x)


def _slots(n, c):
    s = np.random.default_rng(1).integers(-1, c, size=(n, 1))
    s[:4, 0] = [0, 3, 5, 7]
    return jnp.asarray(s, jnp.int32)


def test_gather_rows_f16_interpret():
    c, d, n = 1024, 16, 64
    payload, slots = _f16_payload(c, d), _slots(n, c)
    got = hg.gather_rows(payload, slots, block_n=32, block_c=256,
                         interpret=True)
    want = ref.cache_gather_ref(payload, slots[:, 0])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_dequant_gather_rows_f16_interpret():
    c, d, n = 1024, 16, 64
    payload, slots = _f16_payload(c, d), _slots(n, c)
    scales = jax.random.uniform(jax.random.PRNGKey(2), (c,), jnp.float32,
                                0.5, 2.0)
    got = hg.dequant_gather_rows(payload, scales[:, None], slots,
                                 block_n=32, block_c=256, interpret=True)
    want = ref.dequant_gather_ref(payload, scales, slots[:, 0])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# dot_interaction
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,f,d", [(8, 4, 16), (37, 27, 128), (64, 14, 16)])
def test_interaction_matches_oracle(b, f, d):
    x = jax.random.normal(jax.random.PRNGKey(0), (b, f, d), jnp.float32)
    out = ops.dot_interaction(x)
    expected = ref.dot_interaction_ref(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=1e-4, atol=1e-4)


def test_interaction_self_interaction():
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 5, 16), jnp.float32)
    out = ops.dot_interaction(x, True)
    expected = ref.dot_interaction_ref(x, self_interaction=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=1e-4, atol=1e-4)


def test_interaction_grad_matches_oracle():
    x = jax.random.normal(jax.random.PRNGKey(0), (12, 9, 8), jnp.float32)

    def lk(x):
        return (ops.dot_interaction(x) ** 2).sum()

    def lr(x):
        return (ref.dot_interaction_ref(x) ** 2).sum()

    g1, g2 = jax.grad(lk)(x), jax.grad(lr)(x)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_interaction_dtypes(dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 8, 32)).astype(dtype)
    out = ops.dot_interaction(x)
    expected = ref.dot_interaction_ref(x.astype(jnp.float32))
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("causal,window", [(True, None), (False, None),
                                           (True, 24)])
def test_flash_attention_fwd(causal, window):
    b, s, hq, hkv, d = 2, 64, 4, 2, 32
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, s, hq, d), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, hkv, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, hkv, d))
    o = ops.flash_attention(q, k, v, causal, window, 16, 16)
    want = ref.flash_attention_ref(q, k, v, causal, window)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("hq,hkv,s,d,bq,bk", [
    (4, 4, 32, 16, 8, 8),      # MHA
    (6, 2, 64, 32, 16, 32),    # GQA, uneven blocks
    (8, 1, 32, 64, 32, 16),    # MQA
])
def test_flash_attention_shape_sweep(hq, hkv, s, d, bq, bk):
    b = 2
    key = jax.random.PRNGKey(1)
    q = jax.random.normal(key, (b, s, hq, d), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, hkv, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, hkv, d))
    o = ops.flash_attention(q, k, v, True, None, bq, bk)
    want = ref.flash_attention_ref(q, k, v, True, None)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_bf16():
    b, s, hq, hkv, d = 1, 32, 2, 2, 16
    key = jax.random.PRNGKey(2)
    q = jax.random.normal(key, (b, s, hq, d)).astype(jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 1),
                          (b, s, hkv, d)).astype(jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2),
                          (b, s, hkv, d)).astype(jnp.bfloat16)
    o = ops.flash_attention(q, k, v, True, None, 16, 16)
    want = ref.flash_attention_ref(q, k, v, True, None)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_flash_attention_grads():
    b, s, hq, hkv, d = 2, 32, 4, 2, 16
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (b, s, hq, d), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, hkv, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, hkv, d))

    def lk(q, k, v):
        return (ops.flash_attention(q, k, v, True, None, 16, 16) ** 2).sum()

    def lr(q, k, v):
        return (ref.flash_attention_ref(q, k, v, True, None) ** 2).sum()

    gk = jax.grad(lk, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
    for a, b_, n in zip(gk, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-3, atol=2e-3,
                                   err_msg=f"d{n} mismatch")


def test_flash_matches_chunked_attention():
    """The Pallas kernel and the jnp chunked path are interchangeable."""
    from repro.models.lm.transformer import chunked_attention
    b, s, hq, hkv, d = 2, 48, 4, 2, 16
    key = jax.random.PRNGKey(4)
    q = jax.random.normal(key, (b, s, hq, d), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, hkv, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, hkv, d))
    o1 = ops.flash_attention(q, k, v, True, None, 16, 16)
    o2 = chunked_attention(q, k, v, causal=True, q_chunk=16, k_chunk=16)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=5e-3, atol=5e-3)


def test_kernel_pool_matches_collection_pool():
    """kernel_pool is a drop-in for pooled_local_lookup."""
    from repro.core.embedding.common import pooled_local_lookup
    mega = jax.random.normal(jax.random.PRNGKey(0), (400, 16))
    rows = jax.random.randint(jax.random.PRNGKey(1), (6, 5, 3), -1, 400)
    got = ops.kernel_pool(mega, rows)
    want = pooled_local_lookup(mega, rows)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
