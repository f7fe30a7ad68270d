"""Per-kernel shape/dtype sweeps, allclose against the ref.py oracles."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref

RNG = np.random.RandomState(42)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 1e-4


@pytest.mark.parametrize("b,h,sq,sk,d", [
    (1, 1, 128, 128, 64),
    (2, 2, 256, 256, 64),
    (1, 4, 256, 512, 128),
    (2, 1, 512, 512, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 64), (False, None)])
def test_flash_attention_sweep(b, h, sq, sk, d, dtype, causal, window):
    if not causal and sq != sk:
        pytest.skip("non-causal cross shapes covered elsewhere")
    q = jnp.asarray(RNG.randn(b, h, sq, d), dtype)
    k = jnp.asarray(RNG.randn(b, h, sk, d), dtype)
    v = jnp.asarray(RNG.randn(b, h, sk, d), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              interpret=True)
    expected = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


@pytest.mark.parametrize("b,h,s,d", [
    (1, 1, 512, 64),
    (2, 4, 1024, 64),
    (1, 8, 512, 128),
    (4, 2, 2048, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(b, h, s, d, dtype):
    q = jnp.asarray(RNG.randn(b, h, d), dtype)
    k = jnp.asarray(RNG.randn(b, s, h, d), dtype)
    v = jnp.asarray(RNG.randn(b, s, h, d), dtype)
    lengths = jnp.asarray(RNG.randint(1, s + 1, b), jnp.int32)
    out = ops.decode_attention(q, k, v, lengths, interpret=True)
    expected = ref.decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


@pytest.mark.parametrize("b,h,s,d", [
    (8, 2, 8, 16),     # pow-2 padded batch over a window-8 ring cache
    (8, 4, 16, 32),    # the serve default: window 16, 4 heads post-GQA
    (4, 2, 8, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_policy_serve_shapes(b, h, s, d, dtype):
    """Kernel-vs-ref at the exact shapes the transformer-policy serve step
    emits: tiny window-length ring caches (s == sliding_window, far below
    the LLM-serving sweep above), the batch padded to a power-of-two bucket
    with scratch-slot rows, and cache-offset ``lengths`` mixing mid-episode
    rows (length == s after the ring wraps), fresh prefixes, and length-1
    pad/restart rows."""
    q = jnp.asarray(RNG.randn(b, h, d), dtype)
    k = jnp.asarray(RNG.randn(b, s, h, d), dtype)
    v = jnp.asarray(RNG.randn(b, s, h, d), dtype)
    lengths = np.full((b,), s, np.int32)
    lengths[1::3] = RNG.randint(2, s, len(lengths[1::3]))  # mid-prefix rows
    lengths[2::3] = 1                         # pad / episode-restart rows
    lengths = jnp.asarray(lengths)
    out = ops.decode_attention(q, k, v, lengths, block_k=min(512, s),
                               interpret=True)
    expected = ref.decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


@pytest.mark.parametrize("b,h,kv,s,d", [
    (8, 4, 2, 8, 32),       # the default policy: 4 heads over 2 KV heads
    (4, 8, 1, 16, 16),      # multi-query
    (2, 32, 4, 1024, 128),  # Yi-6B head layout, two kv blocks
])
def test_decode_attention_gqa_matches_repeated_kv(b, h, kv, s, d):
    """Grouped query heads read their KV head in place: the same result as
    MHA over a cache with every KV head repeated ``h // kv`` times."""
    q = jnp.asarray(RNG.randn(b, h, d), jnp.float32)
    k = jnp.asarray(RNG.randn(b, s, kv, d), jnp.float32)
    v = jnp.asarray(RNG.randn(b, s, kv, d), jnp.float32)
    lengths = jnp.asarray(RNG.randint(1, s + 1, b), jnp.int32)
    out = ops.decode_attention(q, k, v, lengths, interpret=True)
    expected = ref.decode_attention_ref(q, jnp.repeat(k, h // kv, axis=2),
                                        jnp.repeat(v, h // kv, axis=2),
                                        lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(ref.decode_attention_ref(q, k, v, lengths)),
        np.asarray(expected), atol=1e-5, rtol=1e-5)


def test_decode_attention_refuses_unblockable_cache():
    """A cache longer than one k-block that the block does not divide is an
    error naming its length — never a silent switch to another path."""
    q = jnp.zeros((1, 2, 16), jnp.float32)
    k = jnp.zeros((1, 600, 2, 16), jnp.float32)
    with pytest.raises(ValueError, match="length 600"):
        ops.decode_attention(q, k, k, jnp.ones((1,), jnp.int32),
                             interpret=True)


def test_auto_decode_backend_is_ref_off_tpu():
    from repro.models.attention import resolve_decode_backend
    assert jax.default_backend() != "tpu"
    assert resolve_decode_backend("auto") == "ref"
    assert resolve_decode_backend("kernel") == "kernel"
    with pytest.raises(ValueError, match="unknown decode backend"):
        resolve_decode_backend("pallas")


@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (1, 256, 2, 32, 16, 64),
    (2, 512, 4, 64, 32, 128),
    (1, 512, 2, 64, 64, 256),
])
def test_ssd_scan_sweep(b, s, h, p, n, chunk):
    x = jnp.asarray(RNG.randn(b, s, h, p), jnp.float32)
    dt = jnp.asarray(np.abs(RNG.randn(b, s, h)) * 0.1 + 0.01, jnp.float32)
    A = -jnp.asarray(np.abs(RNG.randn(h)) + 0.5, jnp.float32)
    B = jnp.asarray(RNG.randn(b, s, n), jnp.float32)
    C = jnp.asarray(RNG.randn(b, s, n), jnp.float32)
    y = ops.ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=True)
    y_ref, _ = ref.ssd_scan_ref(x, dt, A, B, C, chunk)
    scale = float(jnp.max(jnp.abs(y_ref))) + 1.0
    np.testing.assert_allclose(np.asarray(y) / scale,
                               np.asarray(y_ref) / scale, atol=1e-5)


def test_ssd_matches_naive_recurrence():
    """SSD chunked == direct per-token SSM recurrence (duality check)."""
    b, s, h, p, n = 1, 64, 2, 16, 8
    x = jnp.asarray(RNG.randn(b, s, h, p), jnp.float32)
    dt = jnp.asarray(np.abs(RNG.randn(b, s, h)) * 0.1 + 0.01, jnp.float32)
    A = -jnp.asarray(np.abs(RNG.randn(h)) + 0.5, jnp.float32)
    B = jnp.asarray(RNG.randn(b, s, n), jnp.float32)
    C = jnp.asarray(RNG.randn(b, s, n), jnp.float32)
    y_ref, final = ref.ssd_scan_ref(x, dt, A, B, C, 16)

    state = np.zeros((b, h, n, p))
    ys = []
    for t in range(s):
        a = np.exp(np.asarray(dt[:, t]) * np.asarray(A)[None])      # (b,h)
        Bx = np.einsum("bn,bhp,bh->bhnp", np.asarray(B[:, t]),
                       np.asarray(x[:, t]), np.asarray(dt[:, t]))
        state = state * a[..., None, None] + Bx
        ys.append(np.einsum("bn,bhnp->bhp", np.asarray(C[:, t]), state))
    y_naive = np.stack(ys, 1)
    np.testing.assert_allclose(np.asarray(y_ref), y_naive, atol=1e-4)
    np.testing.assert_allclose(np.asarray(final), state, atol=1e-4)


@pytest.mark.parametrize("T,B", [(16, 128), (64, 256), (100, 128)])
def test_vtrace_sweep(T, B):
    vals = jnp.asarray(RNG.randn(T, B), jnp.float32)
    nvals = jnp.asarray(RNG.randn(T, B), jnp.float32)
    rew = jnp.asarray(RNG.randn(T, B), jnp.float32)
    disc = jnp.asarray(RNG.rand(T, B) * 0.99, jnp.float32)
    rhos = jnp.asarray(np.abs(RNG.randn(T, B)) + 0.1, jnp.float32)
    vs, adv = ops.vtrace(vals, nvals, rew, disc, rhos, interpret=True)
    vs_ref, adv_ref = ref.vtrace_ref(vals, nvals, rew, disc, rhos)
    np.testing.assert_allclose(np.asarray(vs), np.asarray(vs_ref), atol=1e-4)
    np.testing.assert_allclose(np.asarray(adv), np.asarray(adv_ref), atol=1e-4)


def test_vtrace_ref_matches_python_loop():
    T, B = 12, 3
    vals = RNG.randn(T, B).astype(np.float32)
    nvals = RNG.randn(T, B).astype(np.float32)
    rew = RNG.randn(T, B).astype(np.float32)
    disc = (RNG.rand(T, B) * 0.9).astype(np.float32)
    rhos = (np.abs(RNG.randn(T, B)) + 0.1).astype(np.float32)
    vs_ref, _ = ref.vtrace_ref(*map(jnp.asarray, (vals, nvals, rew, disc, rhos)))
    rho_c = np.minimum(rhos, 1.0)
    cs = np.minimum(rhos, 1.0)
    deltas = rho_c * (rew + disc * nvals - vals)
    acc = np.zeros(B, np.float32)
    out = np.zeros((T, B), np.float32)
    for t in reversed(range(T)):
        acc = deltas[t] + disc[t] * cs[t] * acc
        out[t] = vals[t] + acc
    np.testing.assert_allclose(np.asarray(vs_ref), out, atol=1e-5)
