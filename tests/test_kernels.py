"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention.ref import mha_ref
from repro.kernels.kmeans_distance import kernel as kd_kernel
from repro.kernels.kmeans_distance import ops as kd_ops
from repro.kernels.kmeans_distance.ref import assign_ref, pairwise_sq_dists_ref
from repro.kernels.ssd_scan import ops as ssd_ops
from repro.kernels.ssd_scan.ref import ssd_ref

KEY = jax.random.PRNGKey(0)


# -- kmeans_distance ----------------------------------------------------------

@pytest.mark.parametrize("n,k,d", [(64, 16, 9), (256, 128, 9), (128, 300, 32),
                                   (512, 64, 130)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kmeans_dists_matches_ref(n, k, d, dtype):
    kx, kc = jax.random.split(KEY)
    x = jax.random.normal(kx, (n, d), dtype)
    c = jax.random.normal(kc, (k, d), dtype)
    got = kd_ops.pairwise_sq_dists(x, c, use_pallas=True, interpret=True)
    want = pairwise_sq_dists_ref(x.astype(jnp.float32), c.astype(jnp.float32))
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol * d)


@pytest.mark.parametrize("n,k,d", [(64, 16, 9), (256, 100, 17)])
def test_kmeans_assign_matches_ref(n, k, d):
    kx, kc = jax.random.split(KEY)
    x = jax.random.normal(kx, (n, d), jnp.float32)
    c = jax.random.normal(kc, (k, d), jnp.float32)
    labels, best = kd_ops.assign(x, c, use_pallas=True, interpret=True)
    ref_labels, ref_best = assign_ref(x, c)
    np.testing.assert_allclose(np.asarray(best), np.asarray(ref_best),
                               rtol=1e-5, atol=1e-5)
    # ties can flip labels; verify via distance equality instead of identity
    d2 = pairwise_sq_dists_ref(x, c)
    np.testing.assert_allclose(
        np.asarray(d2[np.arange(n), np.asarray(labels)]), np.asarray(ref_best),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n,k,c_blocks,dups", [
    (300, 1000, 1, ()),               # n not a multiple of block_n
    (256, 128, 1, ()),                # c of one block
    (256, 4608, 3, ()),               # c of several blocks
    (256, 4500, 3, ()),               # c padded with sentinels
    (256, 4608, 3, (1541, 3100)),     # copies of centroid 5 in later blocks
], ids=["ragged-n", "one-block", "three-blocks", "sentinels", "ties"])
def test_kmeans_assign_labels_equal_argmin_of_dists(n, k, c_blocks, dups):
    """At the model's own block sizes, the fused kernel's labels are the
    argmin of the distance matrix, lowest index first at ties."""
    bn, bc = kd_ops.assign_blocks(n, k)
    assert (n % bn != 0) == (n == 300)
    assert -(-k // bc) == c_blocks and (k % bc != 0) == (k in (1000, 4500))
    kx, kc = jax.random.split(KEY)
    x = jax.random.normal(kx, (n, 9), jnp.float32)
    c = jax.random.normal(kc, (k, 9), jnp.float32)
    if dups:
        c = c.at[jnp.asarray(dups)].set(c[5])
        x = x.at[:32].set(c[5] + 0.01 * x[:32])
    labels, best = kd_ops.assign(x, c, use_pallas=True, interpret=True)
    d2 = np.asarray(kd_ops.pairwise_sq_dists(x, c, use_pallas=True,
                                             interpret=True))
    np.testing.assert_array_equal(np.asarray(labels), np.argmin(d2, axis=1))
    np.testing.assert_allclose(np.asarray(best), d2.min(axis=1),
                               rtol=1e-6, atol=1e-6)
    if dups:
        assert (np.asarray(labels)[:32] == 5).all()


# the packed single pass (d <= 14) on the benchmark's kind of data: blob
# centres uniform in +-10, unit noise, three centroid panels, near ties
PACKED_N, PACKED_K = 2000, 4608


@pytest.fixture(scope="module")
def blobs():
    bn, bc = kd_ops.assign_blocks(PACKED_N, PACKED_K)
    assert -(-PACKED_K // bc) == 3
    rng = np.random.default_rng(20171)
    centres = rng.uniform(-10.0, 10.0, (64, 9))
    x = centres[rng.integers(0, 64, PACKED_N)] + rng.normal(size=(PACKED_N, 9))
    c = centres[rng.integers(0, 64, PACKED_K)] + 0.5 * rng.normal(size=(PACKED_K, 9))
    # near ties across the three panels: centroid 3000 beside centroid 7,
    # 4100 a near copy of it; points beside 7, and halfway to 3000
    c[3000] = c[7] + 0.02 * rng.normal(size=9)
    c[4100] = c[7] + 1e-6
    x[:16] = c[7] + 0.01 * rng.normal(size=(16, 9))
    x[16:32] = (c[7] + c[3000]) / 2
    x, c = x.astype(np.float32), c.astype(np.float32)
    x64, c64 = x.astype(np.float64), c.astype(np.float64)
    d64 = ((x64[:, None, :] - c64[None, :, :]) ** 2).sum(-1)
    xj, cj = jnp.asarray(x), jnp.asarray(c)
    labels, best = kd_ops.assign(xj, cj, use_pallas=True, interpret=True)
    packed = kd_ops.pairwise_sq_dists(xj, cj, use_pallas=True, interpret=True)
    # the float32 HIGHEST formula, as d > 14 runs it
    pad = lambda a: kd_ops.pad_to_multiple(kd_ops.pad_to_multiple(a, 1, 128), 0, 256)
    highest = kd_kernel.pairwise_sq_dists_pallas(pad(xj), pad(cj), interpret=True)
    return dict(x=x, cn=(c64 * c64).sum(1), d64=d64,
                labels=np.asarray(labels), best=np.asarray(best),
                packed=np.asarray(packed),
                highest=np.asarray(highest)[:PACKED_N, :PACKED_K])


def test_kmeans_packed_labels_equal_float64_argmin(blobs):
    d64 = blobs["d64"]
    part = np.partition(d64, 1, axis=1)
    sure = part[:, 1] - part[:, 0] > 1e-4
    assert 16 <= (~sure).sum() < PACKED_N * 0.1
    np.testing.assert_array_equal(blobs["labels"][sure], d64.argmin(1)[sure])
    # at a near tie the kernel takes one of the nearest
    rows = np.arange(PACKED_N)
    assert (d64[rows, blobs["labels"]] - part[:, 0] <= 1e-4).all()


@pytest.mark.parametrize("which", ["best", "packed"])
def test_kmeans_packed_dists_near_float64(blobs, which):
    """Within 8 float32 ulps of the norms that cancel (2**-20 (|x|^2 +
    |c|^2), 1e-4 where they sum to 105): no float32 evaluation of
    |x|^2 + |c|^2 - 2 x.c gets much closer, however exact its dot."""
    d64, x = blobs["d64"], blobs["x"].astype(np.float64)
    scale = (x * x).sum(1)[:, None] + blobs["cn"][None, :]
    if which == "best":
        rows = np.arange(PACKED_N)
        got, want = blobs["best"], d64[rows, blobs["labels"]]
        scale = scale[rows, blobs["labels"]]
    else:
        got, want = blobs["packed"], d64
    assert (np.abs(got - want) <= 2.0 ** -20 * scale).all()


def test_kmeans_packed_error_no_larger_than_highest(blobs):
    d64 = blobs["d64"]
    packed_err = np.abs(blobs["packed"] - d64).max()
    highest_err = np.abs(blobs["highest"] - d64).max()
    assert packed_err <= highest_err


def test_kmeans_split_bf16_sums_back(blobs):
    x = blobs["x"]
    pieces = kd_ops.split_bf16(jnp.asarray(x))
    assert all(p.dtype == jnp.bfloat16 for p in pieces)
    total = sum(np.asarray(p, np.float64) for p in pieces)
    assert (np.abs(total - x) <= 2.0 ** -24 * np.abs(x)).all()


def _kernel_operand_dtypes(d):
    """The dtypes and widths the fused assignment's pallas_call receives."""
    jaxpr = jax.make_jaxpr(lambda a, b: kd_ops.assign(
        a, b, use_pallas=True, interpret=True))(jnp.zeros((64, d)),
                                                 jnp.zeros((16, d)))

    def find(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                return eqn
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns") and (hit := find(inner)):
                    return hit
        return None

    eqn = find(jaxpr.jaxpr)
    return [(str(v.aval.dtype), v.aval.shape[-1]) for v in eqn.invars]


@pytest.mark.parametrize("d,packed", [(9, True), (14, True), (15, False),
                                      (17, False), (130, False)])
def test_kmeans_packed_path_chosen_from_d(d, packed):
    assert kd_ops.packs(d) == packed
    operands = _kernel_operand_dtypes(d)
    if packed:
        assert operands[:2] == [("bfloat16", 128)] * 2 and len(operands) == 4
    else:
        assert len(operands) == 2
        assert all(dt == "float32" and w % 128 == 0 for dt, w in operands)


# -- flash_attention -----------------------------------------------------------

@pytest.mark.parametrize("bh,bkv,s,dh", [(4, 4, 128, 64), (8, 2, 256, 64),
                                         (2, 1, 64, 128), (6, 3, 96, 40)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(bh, bkv, s, dh, dtype):
    kq, kk, kv = jax.random.split(KEY, 3)
    q = jax.random.normal(kq, (bh, s, dh), dtype)
    k = jax.random.normal(kk, (bkv, s, dh), dtype)
    v = jax.random.normal(kv, (bkv, s, dh), dtype)
    got = fa_ops.flash_attention(q, k, v, use_pallas=True, interpret=True,
                                 block_q=32, block_k=32)
    want = mha_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                   v.astype(jnp.float32))
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=tol, atol=tol)


def test_flash_attention_long_context_blocks():
    """Bigger-than-block sequences exercise the multi-block online softmax."""
    kq, kk, kv = jax.random.split(KEY, 3)
    q = jax.random.normal(kq, (2, 512, 64), jnp.float32)
    k = jax.random.normal(kk, (1, 512, 64), jnp.float32)
    v = jax.random.normal(kv, (1, 512, 64), jnp.float32)
    got = fa_ops.flash_attention(q, k, v, use_pallas=True, interpret=True)
    want = mha_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# -- ssd_scan -------------------------------------------------------------------

@pytest.mark.parametrize("b,s,h,p,n,chunk", [(2, 64, 3, 16, 8, 16),
                                             (1, 128, 2, 32, 16, 32),
                                             (2, 96, 4, 8, 4, 32)])
def test_ssd_scan_matches_naive_recurrence(b, s, h, p, n, chunk):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    Bm = jax.random.normal(ks[3], (b, s, n), jnp.float32)
    Cm = jax.random.normal(ks[4], (b, s, n), jnp.float32)
    y, hT = ssd_ops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk,
                             use_pallas=True, interpret=True)
    y_ref, h_ref = ssd_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(h_ref),
                               rtol=2e-4, atol=2e-4)


def test_ssd_chunked_jax_matches_naive():
    """The pure-JAX chunked SSD (model path) against the recurrence."""
    from repro.models.ssm import ssd_chunked
    ks = jax.random.split(KEY, 5)
    b, s, h, p, n = 2, 64, 3, 16, 8
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    Bm = jax.random.normal(ks[3], (b, s, n), jnp.float32)
    Cm = jax.random.normal(ks[4], (b, s, n), jnp.float32)
    y, hT = ssd_chunked(x, dt, A, Bm, Cm, 16)
    y_ref, h_ref = ssd_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(h_ref),
                               rtol=2e-4, atol=2e-4)


def test_ssd_chunked_initial_state_threading():
    """Chunked SSD with h0 equals running the recurrence over a longer seq."""
    from repro.models.ssm import ssd_chunked
    ks = jax.random.split(KEY, 5)
    b, s, h, p, n = 1, 64, 2, 8, 4
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    Bm = jax.random.normal(ks[3], (b, s, n), jnp.float32)
    Cm = jax.random.normal(ks[4], (b, s, n), jnp.float32)
    half = s // 2
    y1, h1 = ssd_chunked(x[:, :half], dt[:, :half], A, Bm[:, :half],
                         Cm[:, :half], 16)
    y2, h2 = ssd_chunked(x[:, half:], dt[:, half:], A, Bm[:, half:],
                         Cm[:, half:], 16, h0=h1)
    y_full, h_full = ssd_chunked(x, dt, A, Bm, Cm, 16)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], axis=1)),
                               np.asarray(y_full), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full),
                               rtol=2e-4, atol=2e-4)
