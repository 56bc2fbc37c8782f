"""Per-kernel correctness: shape/dtype sweeps vs the pure-jnp oracles in
ref.py, including Pallas (interpret=True) and the blocked custom-VJP
backward vs autodiff of the naive reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

ATOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}
RTOL = {jnp.float32: 2e-5, jnp.bfloat16: 3e-2}


def _qkv(bh, sq, sk, d, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (bh, sq, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (bh, sk, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (bh, sk, d), jnp.float32).astype(dtype)
    return q, k, v


# -- flash attention: blocked jnp path ----------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bh,sq,sk,d,block", [
    (2, 64, 64, 32, 16),
    (1, 128, 128, 64, 64),
    (3, 32, 128, 16, 32),     # cross-attn style (sq != sk, non-causal only)
    (2, 256, 256, 128, 128),
])
def test_blocked_fwd_matches_naive(dtype, bh, sq, sk, d, block):
    q, k, v = _qkv(bh, sq, sk, d, dtype)
    causal = sq == sk
    want = ref.naive_attention(q, k, v, causal=causal)
    got, _ = ops._blocked_fwd(q, k, v, causal, 1.0 / np.sqrt(d), block)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=ATOL[dtype], rtol=RTOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_blocked_bwd_matches_naive_grad(dtype):
    bh, s, d, block = 2, 64, 32, 16
    q, k, v = _qkv(bh, s, s, d, dtype)

    def f_ref(q, k, v):
        return (ref.naive_attention(q, k, v, causal=True)
                .astype(jnp.float32).sum())

    def f_blk(q, k, v):
        return ops._flash(q, k, v, True, 1.0 / np.sqrt(d), block, block,
                          False).astype(jnp.float32).sum()

    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    g_blk = jax.grad(f_blk, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_blk):
        np.testing.assert_allclose(np.asarray(b, np.float32),
                                   np.asarray(a, np.float32),
                                   atol=5e-2 if dtype == jnp.bfloat16
                                   else 1e-3, rtol=5e-2)


# -- flash attention: Pallas kernel (interpret mode) ---------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bh,s,d,qb,kb", [
    (2, 128, 64, 64, 64),
    (1, 256, 128, 128, 64),
    (2, 64, 32, 32, 32),
])
def test_pallas_flash_matches_naive(dtype, bh, s, d, qb, kb):
    from repro.kernels.flash_attention import flash_attention_fwd
    q, k, v = _qkv(bh, s, s, d, dtype)
    want = ref.naive_attention(q, k, v, causal=True)
    got = flash_attention_fwd(q, k, v, causal=True, q_block=qb, kv_block=kb,
                              interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=ATOL[dtype], rtol=RTOL[dtype])


@pytest.mark.parametrize("qb,kb", [(32, 32), (32, 64), (64, 32), (128, 128),
                                   (128, 64), (64, 128)])
def test_pallas_flash_block_sweep(qb, kb):
    """The kernel-config dimension: every (q_block, kv_block) tile pair the
    tuner can emit must produce identical attention output."""
    from repro.kernels.flash_attention import flash_attention_fwd
    q, k, v = _qkv(2, 128, 128, 64, jnp.float32)
    want = ref.naive_attention(q, k, v, causal=True)
    got = flash_attention_fwd(q, k, v, causal=True, q_block=qb, kv_block=kb,
                              interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("qb,kb", [(32, 64), (64, 32), (128, 128)])
def test_attention_block_sweep_fwd_bwd(qb, kb):
    """fwd AND bwd through the dispatch wrapper at asymmetric tile pairs:
    the gradient must match autodiff of the naive reference regardless of
    the tuned tiling (tiles change the schedule, never the math)."""
    b, s, h, hd = 2, 128, 4, 32
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (b, s, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, h, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, h, hd), jnp.float32)

    def f(impl, q_block=None, kv_block=None):
        def g(q, k, v):
            return ops.attention(q, k, v, impl=impl, q_block=q_block,
                                 kv_block=kv_block) \
                .astype(jnp.float32).sum()
        return g

    got = ops.attention(q, k, v, impl="pallas", q_block=qb, kv_block=kb)
    want = ops.attention(q, k, v, impl="naive")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4,
                               rtol=1e-4)
    g_ref = jax.grad(f("naive"), argnums=(0, 1, 2))(q, k, v)
    g_tile = jax.grad(f("pallas", qb, kb), argnums=(0, 1, 2))(q, k, v)
    for a, bb_ in zip(g_ref, g_tile):
        np.testing.assert_allclose(np.asarray(bb_), np.asarray(a),
                                   atol=1e-3, rtol=1e-3)


def test_pallas_flash_noncausal():
    from repro.kernels.flash_attention import flash_attention_fwd
    q, k, v = _qkv(2, 128, 128, 64, jnp.float32)
    want = ref.naive_attention(q, k, v, causal=False)
    got = flash_attention_fwd(q, k, v, causal=False, q_block=64, kv_block=64,
                              interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


# -- flash attention: Pallas backward kernels (interpret mode) -----------------


def _path_counts(fn):
    """Run ``fn`` and return (result, backward paths it took)."""
    before = ops.backward_paths.copy()
    out = fn()
    return out, ops.backward_paths - before


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("qb,kb", [(128, 128), (256, 128)])
def test_pallas_lse_matches_blocked(causal, qb, kb):
    from repro.kernels.flash_attention import flash_lse
    q, k, v = _qkv(2, 512, 512, 128, jnp.bfloat16)
    scale = 1.0 / np.sqrt(128)
    _, want = ops._blocked_fwd(q, k, v, causal, scale, kb)
    got = flash_lse(q, k, causal=causal, q_block=qb, kv_block=kb,
                    scale=scale, interpret=True)
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("qb,kb", [(128, 128), (256, 128), (128, 256)])
def test_pallas_bwd_matches_blocked(dtype, causal, qb, kb):
    """The kernels against ``_blocked_bwd`` on the same residuals: the same
    products in the same precisions, so only the order of f32 sums and
    the dtype's final rounding differ."""
    from repro.kernels.flash_attention import flash_attention_bwd
    q, k, v = _qkv(2, 512, 512, 128, dtype)
    dout = jax.random.normal(jax.random.PRNGKey(9), q.shape,
                             jnp.float32).astype(dtype)
    scale = 1.0 / np.sqrt(128)
    out, lse = ops._blocked_fwd(q, k, v, causal, scale, kb)
    want = ops._blocked_bwd(q, k, v, out, lse, dout, causal, scale, kb)
    got = flash_attention_bwd(q, k, v, out, lse, dout, causal=causal,
                              q_block=qb, kv_block=kb, scale=scale,
                              interpret=True)
    tol = 2e-3 if dtype == jnp.bfloat16 else 1e-5
    for a, b in zip(want, got):
        assert b.shape == a.shape and b.dtype == a.dtype
        a = np.asarray(a, np.float32)
        np.testing.assert_allclose(np.asarray(b, np.float32), a,
                                   atol=tol * np.abs(a).max(), rtol=0)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("qb,kb", [(128, 128), (256, 128)])
def test_pallas_flash_grad_matches_naive(causal, qb, kb):
    """``_flash``'s VJP with the Pallas kernels against autodiff of the
    naive reference, and the backward it took."""
    bh, s, d = 2, 256, 128
    q, k, v = _qkv(bh, s, s, d, jnp.float32)
    scale = 1.0 / np.sqrt(d)

    def f_ref(q, k, v):
        return ref.naive_attention(q, k, v, causal=causal).sum()

    def f_pallas(q, k, v):
        return ops._flash(q, k, v, causal, scale, qb, kb, True).sum()

    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    g_pal, paths = _path_counts(
        lambda: jax.grad(f_pallas, argnums=(0, 1, 2))(q, k, v))
    assert paths == {"pallas": 1}
    for a, b in zip(g_ref, g_pal):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-4,
                                   rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("h,kv", [(4, 1), (8, 2)])
def test_attention_pallas_bwd_gqa(dtype, h, kv):
    """Through ``ops.attention`` with H = 4 KV heads per group: the Pallas
    backward against autodiff of the naive path and against the blocked
    backward."""
    b, s, hd = 1, 256, 128
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (b, s, h, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, s, kv, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, s, kv, hd), jnp.float32).astype(dtype)

    def grad(impl):
        def f(q, k, v):
            return ops.attention(q, k, v, impl=impl, q_block=128,
                                 kv_block=128).astype(jnp.float32).sum()
        return _path_counts(lambda: jax.grad(f, argnums=(0, 1, 2))(q, k, v))

    g_pal, paths = grad("pallas")
    assert paths == {"pallas": 1}
    g_blk, paths = grad("blocked")
    assert paths == {"jnp": 1}
    g_ref = jax.grad(lambda q, k, v: ref.naive_attention(
        q.reshape(b, s, kv, h // kv, hd).transpose(0, 2, 3, 1, 4)
        .reshape(b * h, s, hd).astype(jnp.float32),
        jnp.repeat(k, h // kv, axis=2).transpose(0, 2, 1, 3)
        .reshape(b * h, s, hd).astype(jnp.float32),
        jnp.repeat(v, h // kv, axis=2).transpose(0, 2, 1, 3)
        .reshape(b * h, s, hd).astype(jnp.float32), causal=True).sum(),
        argnums=(0, 1, 2))(q, k, v)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    for a, bl, pa in zip(g_ref, g_blk, g_pal):
        assert pa.shape == a.shape and pa.dtype == dtype
        a = np.asarray(a, np.float32)
        for got in (pa, bl):
            np.testing.assert_allclose(np.asarray(got, np.float32), a,
                                       atol=tol * np.abs(a).max(), rtol=0)


@pytest.mark.parametrize("sq,sk,d,causal,block", [
    (256, 256, 64, True, 128),      # head_dim off the 128-lane tiling
    (64, 64, 128, True, 64),        # blocks shorter than 128 lanes
    (128, 256, 128, False, 128),    # cross attention: Sq != Sk
])
def test_pallas_bwd_fallback_shapes(sq, sk, d, causal, block):
    """Shapes the kernels do not tile take the blocked backward, and its
    gradient is still autodiff's."""
    q, k, v = _qkv(2, sq, sk, d, jnp.float32)
    scale = 1.0 / np.sqrt(d)

    def f_ref(q, k, v):
        return ref.naive_attention(q, k, v, causal=causal).sum()

    def f_pallas(q, k, v):
        return ops._flash(q, k, v, causal, scale, block, block, True).sum()

    g_pal, paths = _path_counts(
        lambda: jax.grad(f_pallas, argnums=(0, 1, 2))(q, k, v))
    assert paths == {"jnp": 1}
    for a, b in zip(jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v), g_pal):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-4,
                                   rtol=1e-4)


def _pallas_kernels(jaxpr) -> list:
    """The ``name`` of each pallas_call in a jaxpr, sub-jaxprs included
    (None for the unnamed forward kernel)."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names += _pallas_kernels(sub)
    return names


def test_pallas_primal_runs_forward_kernel_only():
    """Serving prefill calls the primal alone: one forward kernel, no lse
    pass; the gradient adds the lse, dK/dV and dQ kernels."""
    q = jnp.zeros((1, 256, 4, 128), jnp.bfloat16)
    kv = jnp.zeros((1, 256, 1, 128), jnp.bfloat16)

    def fwd(q, k, v):
        return ops.attention(q, k, v, impl="pallas", q_block=128,
                             kv_block=128)

    primal = _pallas_kernels(jax.make_jaxpr(fwd)(q, kv, kv).jaxpr)
    assert primal == [None]
    grad = _pallas_kernels(jax.make_jaxpr(jax.grad(
        lambda *a: fwd(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2)))(
            q, kv, kv).jaxpr)
    assert sorted(grad, key=str) == [None, "flash_bwd_dkv", "flash_bwd_dq",
                                     "flash_lse"], grad


# -- GQA wrapper ----------------------------------------------------------------


@pytest.mark.parametrize("impl", ["naive", "blocked", "pallas"])
@pytest.mark.parametrize("h,kv", [(8, 8), (8, 2), (4, 1)])
def test_attention_gqa_wrapper(impl, h, kv):
    b, s, hd = 2, 64, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, s, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kv, hd), jnp.float32)
    got = ops.attention(q, k, v, impl=impl, block=32)
    # reference: expand kv heads then run naive per head
    g = h // kv
    kx = jnp.repeat(k, g, axis=2)
    vx = jnp.repeat(v, g, axis=2)
    want = jnp.stack([
        ref.naive_attention(q[:, :, i].reshape(b, s, hd).reshape(b, s, hd),
                            kx[:, :, i], vx[:, :, i], causal=True)
        for i in range(h)], axis=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4,
                               rtol=1e-4)


# -- RMSNorm ---------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(4, 128), (2, 64, 256), (1, 8, 512)])
def test_rmsnorm_pallas_matches_ref(dtype, shape):
    x = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.float32) \
        .astype(dtype)
    scale = jax.random.normal(jax.random.PRNGKey(1), shape[-1:],
                              jnp.float32).astype(dtype)
    want = ref.rmsnorm_ref(x, scale)
    got = ops.rmsnorm(x, scale, impl="pallas")
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=ATOL[dtype], rtol=RTOL[dtype])


@pytest.mark.parametrize("block", [32, 64, 128])
def test_rmsnorm_block_sweep(block):
    """The rmsnorm row-block is a tuned knob; output is block-invariant."""
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 64, 128), jnp.float32)
    scale = jax.random.normal(jax.random.PRNGKey(5), (128,), jnp.float32)
    want = ref.rmsnorm_ref(x, scale)
    got = ops.rmsnorm(x, scale, impl="pallas", block=block)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# -- Mamba2 SSD chunk scan ---------------------------------------------------------


@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (1, 64, 2, 16, 8, 16),
    (2, 128, 4, 32, 16, 32),
    (1, 256, 2, 64, 32, 64),
])
def test_ssd_scan_matches_sequential_ref(b, s, h, p, n, chunk):
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    xh = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h), jnp.float32))
    a = -jnp.exp(jax.random.normal(ks[2], (h,), jnp.float32))
    bb = jax.random.normal(ks[3], (b, s, h, n), jnp.float32)
    cc = jax.random.normal(ks[4], (b, s, h, n), jnp.float32)
    want = ref.ssd_ref(xh, dt, a, bb, cc)
    got, _ = ops.ssd_scan(xh, dt, a, bb, cc, chunk=chunk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4,
                               rtol=2e-4)


def test_ssd_scan_bf16():
    b, s, h, p, n = 1, 64, 2, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    xh = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h), jnp.float32))
    a = -jnp.exp(jax.random.normal(ks[2], (h,), jnp.float32))
    bb = jax.random.normal(ks[3], (b, s, h, n), jnp.float32)
    cc = jax.random.normal(ks[4], (b, s, h, n), jnp.float32)
    want = ref.ssd_ref(xh, dt, a, bb, cc)
    got, _ = ops.ssd_scan(xh.astype(jnp.bfloat16), dt, a,
                          bb.astype(jnp.bfloat16), cc.astype(jnp.bfloat16),
                          chunk=16)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=0.05, rtol=0.05)


# -- int8 KV cache -------------------------------------------------------------


def test_int8_kv_decode_close_to_bf16():
    """Quantized KV decode must track the bf16 decode closely."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import get_arch
    from repro.models import layers as L
    from repro.models.common import ExecConfig, ParamBuilder

    cfg = get_arch("granite-3-8b").reduced()
    pb = ParamBuilder(jax.random.PRNGKey(0), jnp.float32)
    L.init_attention(pb.scope("a"), cfg)
    p = {k.split("/", 1)[1]: v for k, v in pb.params.items()}
    ec = ExecConfig()
    B, S = 2, 12
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model),
                          jnp.float32) * 0.5

    def run(dtype):
        cache = L.init_self_kv_cache(cfg, B, S, dtype)
        outs = []
        for i in range(S):
            o, cache = L.attention(p, x[:, i:i + 1], cfg, ec, cache=cache)
            outs.append(o)
        return jnp.concatenate(outs, axis=1)

    ref_out = run(jnp.bfloat16)
    q_out = run(jnp.int8)
    err = float(jnp.max(jnp.abs(q_out.astype(jnp.float32)
                                - ref_out.astype(jnp.float32))))
    assert err < 0.1, err


def test_quantize_kv_roundtrip():
    import jax
    import jax.numpy as jnp
    from repro.models.layers import dequantize_kv, quantize_kv

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 1, 4, 64),
                          jnp.float32)
    q, s = quantize_kv(x)
    assert q.dtype == jnp.int8
    back = dequantize_kv(q, s, jnp.float32)
    np.testing.assert_allclose(np.asarray(back), np.asarray(x),
                               atol=float(np.max(np.abs(np.asarray(x))))
                               / 127 * 0.51 + 1e-6)
