"""Per-kernel allclose validation: Pallas (interpret mode on CPU) and the
jnp chunked fallbacks against the pure-jnp oracles, swept over shapes and
dtypes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as fa
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.decode_attention import ops as da
from repro.kernels.decode_attention import ref as da_ref
from repro.kernels.rwkv6 import ops as rk
from repro.kernels.rwkv6 import ref as rk_ref
from repro.kernels.ssm import ops as sk
from repro.kernels.ssm import ref as sk_ref

TOL = dict(rtol=2e-2, atol=2e-2)    # bf16-friendly
TOL32 = dict(rtol=2e-4, atol=2e-4)


def _tol(dtype):
    return TOL if dtype == jnp.bfloat16 else TOL32


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,sq,skv,h,kvh,d", [
    (2, 128, 128, 4, 2, 32),
    (1, 256, 256, 4, 4, 64),
    (2, 96, 96, 2, 1, 16),       # non-multiple-of-block seq
])
@pytest.mark.parametrize("mask", ["causal", "window", "chunk"])
def test_flash_attention(b, sq, skv, h, kvh, d, dtype, mask):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(ks[0], (b, sq, h, d), dtype)
    k = _rand(ks[1], (b, skv, kvh, d), dtype)
    v = _rand(ks[2], (b, skv, kvh, d), dtype)
    kw = dict(causal=True,
              window=48 if mask == "window" else None,
              chunk=64 if mask == "chunk" else None)
    want = fa_ref.mha_reference(q, k, v, **kw)
    got_jnp = fa.flash_attention(q, k, v, impl="jnp", **kw)
    np.testing.assert_allclose(np.asarray(got_jnp, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))
    got_pl = fa.flash_attention(q, k, v, impl="pallas", interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(got_pl, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_flash_attention_q_offset():
    """Prefill continuation: q block positioned mid-sequence."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _rand(ks[0], (1, 64, 2, 32), jnp.float32)
    k = _rand(ks[1], (1, 192, 2, 32), jnp.float32)
    v = _rand(ks[2], (1, 192, 2, 32), jnp.float32)
    want = fa_ref.mha_reference(q, k, v, causal=True, q_offset=128)
    got = fa.flash_attention(q, k, v, causal=True, q_offset=128, impl="jnp")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL32)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,smax,h,kvh,d", [
    (2, 128, 4, 2, 32),
    (3, 64, 2, 2, 64),
])
def test_decode_attention(b, smax, h, kvh, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = _rand(ks[0], (b, h, d), dtype)
    ck = _rand(ks[1], (b, smax, kvh, d), dtype)
    cv = _rand(ks[2], (b, smax, kvh, d), dtype)
    valid = jnp.asarray([smax // 2, smax, smax - 7][:b] or [smax // 2])
    valid = valid[:b]
    want = da_ref.decode_reference(q, ck, cv, valid)
    got = da.decode_attention(q, ck, cv, valid)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("impl", ["pallas", "jnp"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_latent(impl, dtype):
    """Latent attention's absorbed decode: an explicit scale, one cache
    "head" of latent + rotary lanes, and values that are its first ``dv``
    lanes, against the reference given those values as a V cache."""
    b, smax, h, dk, dv = 3, 64, 4, 96, 64
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    q = _rand(ks[0], (b, h, dk), dtype)
    ck = _rand(ks[1], (b, smax, 1, dk), dtype)
    valid = jnp.asarray([5, smax, 33])
    scale = 48 ** -0.5
    want = da_ref.decode_reference(q, ck, ck[..., :dv], valid, scale=scale)
    got = da.decode_attention(q, ck, None, valid, scale=scale, dv=dv,
                              impl=impl, interpret=True)
    assert got.shape == (b, h, dv)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_decode_attention_qwen2_call_unchanged():
    """The kernel at qwen2's call (14 query heads over 2 KV heads of 64, no
    scale given, a V cache) gives the result it gave before it learned the
    latent form, bit for bit (interpret mode; the digest was recorded from
    the kernel as it was)."""
    import hashlib

    from repro.kernels.decode_attention.kernel import decode_attention_pallas

    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    b, s, h, kvh, d = 8, 256, 14, 2, 64
    q = jax.random.normal(ks[0], (b, h, d)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, s, kvh, d)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, s, kvh, d)).astype(jnp.bfloat16)
    valid = jnp.arange(1, b + 1, dtype=jnp.int32) * 3
    out = decode_attention_pallas(q, k, v, valid, interpret=True)
    digest = hashlib.sha256(
        np.asarray(out.astype(jnp.float32)).tobytes()).hexdigest()
    assert digest == ("ba477549849f0d8b1c8305feee3ad4c3"
                      "c973def352a98e3a76178a71dbc26f30")


# ---------------------------------------------------------------------------
# rwkv6
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,t,h,k,bt", [
    (2, 64, 3, 16, 16),
    (1, 128, 2, 32, 32),
    (2, 32, 1, 64, 32),
])
def test_rwkv6(b, t, h, k, bt, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    r = _rand(ks[0], (b, t, h, k), dtype)
    kk = _rand(ks[1], (b, t, h, k), dtype)
    v = _rand(ks[2], (b, t, h, k), dtype)
    w = jax.nn.sigmoid(_rand(ks[3], (b, t, h, k), jnp.float32)
                       ).astype(dtype) * 0.98 + 0.01
    u = _rand(ks[4], (h, k), jnp.float32)
    want, _ = rk_ref.rwkv6_reference(r, kk, v, w, u)
    got_jnp = rk.rwkv6(r, kk, v, w, u, impl="jnp", block_t=bt)
    np.testing.assert_allclose(np.asarray(got_jnp, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))
    got_pl = rk.rwkv6(r, kk, v, w, u, impl="pallas", block_t=bt,
                      interpret=True)
    np.testing.assert_allclose(np.asarray(got_pl, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_rwkv6_strong_decay_stability():
    """Near-zero decays (w -> 0) must not overflow the chunked form."""
    b, t, h, k = 1, 64, 1, 16
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    r = _rand(ks[0], (b, t, h, k), jnp.float32)
    kk = _rand(ks[1], (b, t, h, k), jnp.float32)
    v = _rand(ks[2], (b, t, h, k), jnp.float32)
    w = jnp.full((b, t, h, k), 1e-30, jnp.float32)
    u = _rand(ks[3], (h, k), jnp.float32)
    want, _ = rk_ref.rwkv6_reference(r, kk, v, w, u)
    for kw in (dict(impl="jnp"), dict(impl="pallas", interpret=True)):
        got = rk.rwkv6(r, kk, v, w, u, block_t=16, **kw)
        assert bool(jnp.isfinite(got).all()), kw
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   **TOL32)


def test_rwkv6_decode_matches_scan():
    b, t, h, k = 2, 8, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    r = _rand(ks[0], (b, t, h, k), jnp.float32)
    kk = _rand(ks[1], (b, t, h, k), jnp.float32)
    v = _rand(ks[2], (b, t, h, k), jnp.float32)
    w = jax.nn.sigmoid(_rand(ks[3], (b, t, h, k), jnp.float32))
    u = _rand(ks[4], (h, k), jnp.float32)
    want, _ = rk_ref.rwkv6_reference(r, kk, v, w, u)
    state = jnp.zeros((b, h, k, k))
    outs = []
    for i in range(t):
        o, state = rk.rwkv6_decode_step(state, r[:, i], kk[:, i], v[:, i],
                                        w[:, i], u)
        outs.append(o)
    got = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL32)


# ---------------------------------------------------------------------------
# mamba2 ssd
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,t,h,p,n,bt", [
    (2, 64, 3, 8, 16, 16),
    (1, 128, 2, 16, 32, 32),
])
def test_ssd(b, t, h, p, n, bt, dtype):
    ks = jax.random.split(jax.random.PRNGKey(6), 6)
    x = _rand(ks[0], (b, t, h, p), dtype)
    dt = jnp.abs(_rand(ks[1], (b, t, h), jnp.float32)) * 0.5
    a_log = _rand(ks[2], (h,), jnp.float32) * 0.5
    bb = _rand(ks[3], (b, t, n), dtype)
    cc = _rand(ks[4], (b, t, n), dtype)
    d = _rand(ks[5], (h,), jnp.float32)
    want, _ = sk_ref.ssd_reference(x, dt, a_log, bb, cc, d)
    got_jnp = sk.ssd(x, dt, a_log, bb, cc, d, impl="jnp", block_t=bt)
    np.testing.assert_allclose(np.asarray(got_jnp, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))
    got_pl = sk.ssd(x, dt, a_log, bb, cc, d, impl="pallas", block_t=bt,
                    interpret=True)
    np.testing.assert_allclose(np.asarray(got_pl, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_ssd_decode_matches_scan():
    b, t, h, p, n = 2, 8, 2, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    x = _rand(ks[0], (b, t, h, p), jnp.float32)
    dt = jnp.abs(_rand(ks[1], (b, t, h), jnp.float32)) * 0.5
    a_log = _rand(ks[2], (h,), jnp.float32) * 0.5
    bb = _rand(ks[3], (b, t, n), jnp.float32)
    cc = _rand(ks[4], (b, t, n), jnp.float32)
    d = _rand(ks[5], (h,), jnp.float32)
    want, _ = sk_ref.ssd_reference(x, dt, a_log, bb, cc, d)
    state = jnp.zeros((b, h, n, p))
    outs = []
    for i in range(t):
        y, state = sk.ssd_decode_step(state, x[:, i], dt[:, i], a_log,
                                      bb[:, i], cc[:, i], d)
        outs.append(y)
    got = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL32)


# ---------------------------------------------------------------------------
# gradients: a train step differentiates through the Pallas path
# ---------------------------------------------------------------------------

def _grad_case(kernel):
    """(fn(impl, *args), args) for a small float32 case of ``kernel``."""
    ks = jax.random.split(jax.random.PRNGKey(8), 6)
    if kernel == "flash":
        args = (_rand(ks[0], (1, 64, 4, 16), jnp.float32),
                _rand(ks[1], (1, 64, 2, 16), jnp.float32),
                _rand(ks[2], (1, 64, 2, 16), jnp.float32))
        return (lambda impl, *a: fa.flash_attention(
            *a, causal=True, window=24, impl=impl,
            interpret=True if impl == "pallas" else None)), args
    if kernel == "rwkv6":
        b, t, h, k = 1, 32, 2, 16
        args = (_rand(ks[0], (b, t, h, k), jnp.float32),
                _rand(ks[1], (b, t, h, k), jnp.float32),
                _rand(ks[2], (b, t, h, k), jnp.float32),
                jax.nn.sigmoid(_rand(ks[3], (b, t, h, k), jnp.float32)),
                _rand(ks[4], (h, k), jnp.float32))
        return (lambda impl, *a: rk.rwkv6(
            *a, impl=impl, block_t=16,
            interpret=True if impl == "pallas" else None)), args
    b, t, h, p, n = 1, 32, 2, 8, 16
    args = (_rand(ks[0], (b, t, h, p), jnp.float32),
            jnp.abs(_rand(ks[1], (b, t, h), jnp.float32)) * 0.5,
            _rand(ks[2], (h,), jnp.float32) * 0.5,
            _rand(ks[3], (b, t, n), jnp.float32),
            _rand(ks[4], (b, t, n), jnp.float32),
            _rand(ks[5], (h,), jnp.float32))
    return (lambda impl, *a: sk.ssd(
        *a, impl=impl, block_t=16,
        interpret=True if impl == "pallas" else None)), args


@pytest.mark.parametrize("kernel", ["flash", "rwkv6", "ssd"])
def test_pallas_grad_matches_reference(kernel):
    """``pallas_call`` has no transpose rule: the kernels' VJP comes from
    their jnp form, and must equal the gradient of the plain reference."""
    fn, args = _grad_case(kernel)
    argnums = tuple(range(len(args)))

    def loss(impl):
        return lambda *a: (fn(impl, *a) ** 2).sum()

    got = jax.grad(loss("pallas"), argnums)(*args)
    want = jax.grad(loss("ref"), argnums)(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-3, atol=2e-3)
