"""Pallas kernel validation (interpret mode on CPU) vs pure-jnp oracles:
shape/dtype sweeps per kernel + custom-vjp gradient checks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.crms_grid import crms_grid_eval

KEY = jax.random.PRNGKey(0)


def _qkv(key, B, Sq, Skv, KV, G, hd, dtype):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Sq, KV, G, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, Skv, KV, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, Skv, KV, hd), jnp.float32).astype(dtype)
    return q, k, v


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize(
    "B,Sq,KV,G,hd",
    [(1, 128, 1, 2, 64), (2, 256, 2, 2, 64), (1, 256, 4, 1, 128), (1, 128, 1, 8, 256)],
)
def test_flash_pallas_interpret_vs_naive(B, Sq, KV, G, hd, causal):
    q, k, v = _qkv(KEY, B, Sq, Sq, KV, G, hd, jnp.float32)
    out = ops.flash_attention(q, k, v, causal=causal, backend="interpret")
    want = ref.attention_naive(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_reference_dtype_sweep(dtype):
    q, k, v = _qkv(KEY, 2, 192, 192, 2, 3, 64, dtype)
    out = ref.flash_attention(q, k, v, True, 64, 64)
    want = ref.attention_naive(q, k, v, True)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), atol=tol, rtol=tol
    )


def test_flash_ref_nonsquare_and_padding():
    # Sq != Skv and sizes not divisible by blocks exercise the padding path
    q, k, v = _qkv(KEY, 1, 70, 130, 2, 2, 32, jnp.float32)
    out = ref.flash_attention(q, k, v, False, 32, 64)
    want = ref.attention_naive(q, k, v, False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_flash_custom_vjp_grads():
    q, k, v = _qkv(KEY, 1, 96, 96, 2, 2, 32, jnp.float32)
    f_flash = lambda q, k, v: (ref.flash_attention(q, k, v, True, 32, 32) ** 2).sum()
    f_naive = lambda q, k, v: (ref.attention_naive(q, k, v, True) ** 2).sum()
    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-4)


# ---------------------------------------------------------------------------
# SSD
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,S,H,P,N,chunk", [(1, 128, 2, 32, 16, 64), (2, 256, 4, 64, 32, 128)])
def test_ssd_pallas_interpret_vs_ref(B, S, H, P, N, chunk):
    ks = jax.random.split(KEY, 4)
    xh = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    bm = jax.random.normal(ks[1], (B, S, N), jnp.float32) * 0.5
    cm = jax.random.normal(ks[2], (B, S, N), jnp.float32) * 0.5
    da = -jax.nn.softplus(jax.random.normal(ks[3], (B, S, H), jnp.float32))
    from repro.models.mamba import _ssd_chunks_ref

    y1, s1 = ops.ssd_chunks(xh, bm, cm, da, chunk=chunk, backend="interpret")
    y2, s2 = _ssd_chunks_ref(xh, bm, cm, da, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-5, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=2e-5, rtol=2e-4)


def test_ssd_chunked_equals_sequential_recurrence():
    """Chunked SSD == token-by-token linear recurrence (ground truth)."""
    B, S, H, P, N = 1, 64, 2, 8, 4
    ks = jax.random.split(KEY, 4)
    xh = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    bm = jax.random.normal(ks[1], (B, S, N), jnp.float32) * 0.5
    cm = jax.random.normal(ks[2], (B, S, N), jnp.float32) * 0.5
    da = -jax.nn.softplus(jax.random.normal(ks[3], (B, S, H), jnp.float32))
    from repro.models.mamba import _ssd_chunks_ref

    y_chunk, s_chunk = _ssd_chunks_ref(xh, bm, cm, da, chunk=16)
    s = np.zeros((B, H, P, N), np.float32)
    ys = []
    for t in range(S):
        dec = np.exp(np.asarray(da[:, t]))
        s = dec[..., None, None] * s + np.einsum(
            "bhp,bn->bhpn", np.asarray(xh[:, t]), np.asarray(bm[:, t])
        )
        ys.append(np.einsum("bhpn,bn->bhp", s, np.asarray(cm[:, t])))
    y_seq = np.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_chunk), y_seq, atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s_chunk), s, atol=1e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# CRMS grid
# ---------------------------------------------------------------------------
def _grid_inputs(M, B, seed):
    """kappa, lam, xbar (per app) and n, c, m (per candidate) of a random node."""
    rng = np.random.default_rng(seed)
    kappa = np.stack(
        [rng.uniform(20, 120, M), rng.uniform(0.8, 2.5, M), rng.uniform(0.2, 0.5, M)], axis=1
    )
    lam = rng.uniform(4, 12, M)
    xbar = rng.uniform(4, 6, M)
    n = rng.integers(3, 12, (B, M)).astype(float)
    c = rng.uniform(0.5, 3.0, (B, M))
    m = rng.uniform(0.25, 0.5, (B, M))
    return kappa, lam, xbar, n, c, m


@pytest.mark.parametrize("M,B", [(4, 200), (7, 64)])
def test_crms_grid_interpret_vs_oracle(M, B):
    kappa, lam, xbar, n, c, m = _grid_inputs(M, B, seed=0)
    kw = dict(caps_cpu=30.0, power_span=150.0, alpha=1.4, beta=0.2)
    u_int = np.asarray(ops.crms_grid(kappa, lam, xbar, n, c, m, backend="interpret", **kw))
    u_ref = np.asarray(ops.crms_grid(kappa, lam, xbar, n, c, m, backend="reference", **kw))
    finite = u_ref < 1e8
    assert finite.sum() >= 4  # joint stability is rare for many apps
    np.testing.assert_allclose(u_int[finite], u_ref[finite], rtol=1e-4)
    # unstable candidates flagged huge in both
    assert np.all(u_int[~finite] > 1e6)


@pytest.mark.parametrize("M,B", [(4, 96), (7, 40)])
def test_crms_grid_per_app_interpret_vs_oracle(M, B):
    """Per-app output mode (grid seeding's argmin input) vs the jnp oracle."""
    kappa, lam, xbar, n, c, m = _grid_inputs(M, B, seed=1)
    kw = dict(caps_cpu=30.0, power_span=150.0, alpha=1.4, beta=0.2)
    t_int = np.asarray(
        ops.crms_grid(kappa, lam, xbar, n, c, m, backend="interpret", reduce="per_app", **kw)
    )
    t_ref = np.asarray(
        ops.crms_grid(kappa, lam, xbar, n, c, m, backend="reference", reduce="per_app", **kw)
    )
    assert t_int.shape == (B, M) and t_ref.shape == (B, M)
    finite = np.isfinite(t_ref) & (t_ref < 1e8)
    assert finite.sum() > 0
    np.testing.assert_allclose(t_int[finite], t_ref[finite], rtol=1e-4)
    # unstable lanes flagged huge in both (inf in the f64 oracle, 1e9 kernel sentinel)
    assert np.all(t_int[~finite] > 1e6)
    # summed mode is the row-sum of per-app mode
    u_int = np.asarray(ops.crms_grid(kappa, lam, xbar, n, c, m, backend="interpret", **kw))
    np.testing.assert_allclose(u_int, t_int.sum(axis=1), rtol=1e-5)


@pytest.mark.parametrize("reduce", ["sum", "per_app"])
def test_crms_grid_scalars_are_operands(reduce):
    """Two sets of caps, power span and weights, back to back at one shape (one
    built kernel): each result follows its own scalars, none the first call's."""
    M, B = 4, 56
    args = _grid_inputs(M, B, seed=2)
    oracle = ref.crms_grid_terms if reduce == "per_app" else ref.crms_grid_utility
    for kw in (dict(caps_cpu=30.0, power_span=150.0, alpha=1.4, beta=0.2),
               dict(caps_cpu=120.0, power_span=40.0, alpha=0.6, beta=2.5)):
        got = np.asarray(crms_grid_eval(*args, interpret=True, reduce=reduce, **kw))
        want = np.asarray(oracle(*map(jnp.asarray, args), **kw))
        finite = np.isfinite(want) & (want < 1e8)
        assert finite.sum() >= 4
        np.testing.assert_allclose(got[finite], want[finite], rtol=1e-4)
        assert np.all(got[~finite] > 1e6)


def test_crms_grid_eager_and_jitted_agree_bitwise():
    """The eager call (its own built kernel) and the kernel inlined into an
    outer jit, with the inputs and the four scalars traced, give the same bits."""
    args = _grid_inputs(4, 72, seed=3)
    kw = dict(caps_cpu=108.0, power_span=150.0, alpha=1.4, beta=0.2)
    eager = np.asarray(crms_grid_eval(*args, interpret=True, reduce="per_app", **kw))
    jitted = jax.jit(lambda args, kw: crms_grid_eval(
        *args, interpret=True, reduce="per_app", **kw))(args, kw)
    np.testing.assert_array_equal(eager, np.asarray(jitted))
