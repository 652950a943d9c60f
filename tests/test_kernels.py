"""Pallas kernel validation: interpret-mode execution vs pure-jnp oracles,
swept over shapes and dtypes (the brief's per-kernel allclose contract)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.ssd_scan import ssd_scan
from repro.kernels.sbc import sbc_stats, sbc_apply
from repro.testing.proptest import given, settings, strategies as st

KEY = jax.random.key(42)


def tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bh,s,hd", [(2, 128, 64), (4, 256, 64),
                                     (1, 256, 128), (3, 512, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes_dtypes(bh, s, hd, dtype):
    q, k, v = (jax.random.normal(jax.random.fold_in(KEY, i), (bh, s, hd),
                                 dtype) for i in range(3))
    out = flash_attention_bhsd(q, k, v, causal=True, block_q=64, block_k=64,
                               interpret=True)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol(dtype), rtol=tol(dtype))


@pytest.mark.parametrize("window", [16, 64, 100])
def test_flash_attention_window(window):
    q, k, v = (jax.random.normal(jax.random.fold_in(KEY, i), (2, 256, 64))
               for i in range(3))
    out = flash_attention_bhsd(q, k, v, causal=True, window=window,
                               block_q=64, block_k=64, interpret=True)
    want = ref.attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


def test_flash_attention_noncausal():
    q, k, v = (jax.random.normal(jax.random.fold_in(KEY, i), (2, 128, 64))
               for i in range(3))
    out = flash_attention_bhsd(q, k, v, causal=False, block_q=64,
                               block_k=64, interpret=True)
    want = ref.attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


def test_flash_attention_gqa_wrapper():
    """ops.flash_attention expands GQA groups and agrees with the model's
    naive attention path."""
    from repro.models.attention import attend_naive
    B, S, Hq, Hkv, hd = 2, 128, 8, 2, 64
    q = jax.random.normal(KEY, (B, S, Hq, hd))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, Hkv, hd))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, Hkv, hd))
    pos = jnp.arange(S)
    out = ops.flash_attention(q, k, v, interpret=True, block_q=64,
                              block_k=64)
    want = attend_naive(q, k, v, pos, pos, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


# ---------------------------------------------------------------------------
# flash decode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("ctx,block_s,pos", [(256, 64, 100), (512, 128, 511),
                                             (128, 128, 0)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_vs_ref(ctx, block_s, pos, dtype):
    from repro.kernels.flash_decode import flash_decode_bhd
    BH, hd = 4, 64
    q = jax.random.normal(KEY, (BH, 1, hd), dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (BH, ctx, hd), dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (BH, ctx, hd), dtype)
    out = flash_decode_bhd(q, k, v, pos, block_s=block_s, interpret=True)
    want = ref.decode_attention_ref(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol(dtype), rtol=tol(dtype))


def test_flash_decode_ring_buffer_window():
    """Ring-buffer semantics: cache size == window, pos beyond ctx."""
    from repro.kernels.flash_decode import flash_decode_bhd
    BH, ctx, hd = 2, 128, 64
    q = jax.random.normal(KEY, (BH, 1, hd))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (BH, ctx, hd))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (BH, ctx, hd))
    pos = 1000                      # far past the ring size
    out = flash_decode_bhd(q, k, v, pos, window=ctx, block_s=64,
                           interpret=True)
    want = ref.decode_attention_ref(q, k, v, pos, window=ctx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


def test_flash_decode_gqa_wrapper_matches_model_decode_math():
    from repro.kernels import ops
    B, ctx, Hq, Hkv, hd = 2, 128, 8, 2, 64
    q = jax.random.normal(KEY, (B, 1, Hq, hd))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, ctx, Hkv, hd))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, ctx, Hkv, hd))
    out_i = ops.flash_decode(q, k, v, 64, interpret=True, block_s=64)
    out_r = ops.flash_decode(q, k, v, 64)          # ref fallback on CPU
    np.testing.assert_allclose(np.asarray(out_i), np.asarray(out_r),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,s,h,p,g,n,chunk", [
    (2, 128, 4, 32, 2, 16, 32),
    (1, 64, 2, 64, 1, 32, 16),
    (2, 256, 8, 32, 4, 64, 64),
    (1, 128, 4, 32, 4, 16, 128),   # single chunk
])
def test_ssd_scan_shapes(b, s, h, p, g, n, chunk):
    x = jax.random.normal(KEY, (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(KEY, 1),
                                           (b, s, h)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(KEY, 2), (h,)) * 0.3)
    Bm = jax.random.normal(jax.random.fold_in(KEY, 3), (b, s, g, n)) * 0.5
    Cm = jax.random.normal(jax.random.fold_in(KEY, 4), (b, s, g, n)) * 0.5
    y = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    want = ref.ssd_ref(x, dt, A, Bm, Cm, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=3e-4,
                               rtol=3e-4)


def test_ssd_scan_bf16():
    b, s, h, p, g, n = 1, 128, 2, 32, 1, 16
    x = jax.random.normal(KEY, (b, s, h, p), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(KEY, 1),
                                           (b, s, h))).astype(jnp.bfloat16)
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(KEY, 2), (h,)) * 0.3)
    Bm = (jax.random.normal(jax.random.fold_in(KEY, 3), (b, s, g, n)) * 0.5
          ).astype(jnp.bfloat16)
    Cm = (jax.random.normal(jax.random.fold_in(KEY, 4), (b, s, g, n)) * 0.5
          ).astype(jnp.bfloat16)
    y = ssd_scan(x, dt, A, Bm, Cm, chunk=32, interpret=True)
    want = ref.ssd_ref(x, dt, A, Bm, Cm, chunk=32)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(want, np.float32), atol=0.05,
                               rtol=0.05)


def test_ssd_matches_sequential_recurrence():
    """Chunked SSD == the literal per-token SSM recurrence (the decode
    path's update rule) — the strongest correctness anchor."""
    b, s, h, p, g, n = 1, 64, 2, 8, 1, 4
    x = jax.random.normal(KEY, (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(KEY, 1),
                                           (b, s, h)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(KEY, 2), (h,)) * 0.3)
    Bm = jax.random.normal(jax.random.fold_in(KEY, 3), (b, s, g, n)) * 0.5
    Cm = jax.random.normal(jax.random.fold_in(KEY, 4), (b, s, g, n)) * 0.5
    y_chunk = ref.ssd_ref(x, dt, A, Bm, Cm, chunk=16)

    # sequential: h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t ; y_t = C_t h_t
    state = np.zeros((b, h, p, n), np.float32)
    ys = []
    rep = h // g
    for t in range(s):
        dA = np.exp(np.asarray(dt[:, t]) * np.asarray(A))       # (b,h)
        Bt = np.repeat(np.asarray(Bm[:, t]), rep, axis=1)        # (b,h,n)
        Ct = np.repeat(np.asarray(Cm[:, t]), rep, axis=1)
        upd = (np.asarray(dt[:, t])[:, :, None, None]
               * np.asarray(x[:, t])[..., None] * Bt[:, :, None, :])
        state = state * dA[:, :, None, None] + upd
        ys.append(np.einsum("bhpn,bhn->bhp", state, Ct))
    y_seq = np.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_chunk), y_seq, atol=2e-4,
                               rtol=2e-4)


def test_ssd_chunk_invariance():
    """The chunked algorithm must be exact: chunk size cannot change y."""
    b, s, h, p, g, n = 1, 128, 2, 16, 1, 8
    x = jax.random.normal(KEY, (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(KEY, 1),
                                           (b, s, h)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(KEY, 2), (h,)) * 0.3)
    Bm = jax.random.normal(jax.random.fold_in(KEY, 3), (b, s, g, n)) * 0.5
    Cm = jax.random.normal(jax.random.fold_in(KEY, 4), (b, s, g, n)) * 0.5
    y32 = ref.ssd_ref(x, dt, A, Bm, Cm, chunk=32)
    y128 = ref.ssd_ref(x, dt, A, Bm, Cm, chunk=128)
    np.testing.assert_allclose(np.asarray(y32), np.asarray(y128), atol=1e-4,
                               rtol=1e-4)


# ---------------------------------------------------------------------------
# SBC kernels
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,ratio,block", [(2048, 0.01, 256),
                                           (4096, 0.005, 512),
                                           (1000, 0.05, 128),
                                           (65536, 0.001, 8192)])
def test_sbc_pipeline_vs_oracle(n, ratio, block):
    g = jax.random.normal(KEY, (n,)) * jnp.linspace(0.1, 3.0, n)
    out = ops.sbc_compress(g, ratio, block=block, interpret=True)
    want = ref.sbc_ref(g, ratio)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-6)


def test_sbc_stats_kernel():
    x = jnp.asarray([3.0, -4.0, 1.0, -0.5, 2.5, -2.5, 0.1, 0.0])
    slab = jnp.pad(x, (0, 16 * 128 - 8)).reshape(16, 128)
    thr = jnp.full((1, 128), 2.0)
    st = sbc_stats(slab, thr, block_rows=8, interpret=True)[:4].sum(axis=1)
    assert float(st[0]) == pytest.approx(5.5)    # pos magnitudes 3 + 2.5
    assert float(st[1]) == pytest.approx(6.5)    # neg magnitudes 4 + 2.5
    assert float(st[2]) == 2 and float(st[3]) == 2


def test_sbc_apply_kernel():
    x = jnp.asarray([3.0, -4.0, 1.0, -0.5, 2.5, -2.5, 0.1, 0.0])
    slab = jnp.pad(x, (0, 16 * 128 - 8)).reshape(16, 128)
    scal = jnp.asarray([2.0, 0.0, -3.25])        # thr, vpos(drop), vneg
    out = sbc_apply(slab, jnp.broadcast_to(scal[:, None], (3, 128)),
                    block_rows=8, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out).reshape(-1)[:8], [0, -3.25, 0, 0, 0, -3.25, 0, 0],
        atol=1e-6)
    assert not np.asarray(out).reshape(-1)[8:].any()


def test_sbc_edge_semantics():
    """Pinned edge behavior, oracle and kernel pipeline agreeing: all-zero
    input stays all-zero (thr=0 keeps everything, but neither sign group
    has members and the count clamp prevents 0/0), a k=1 tiny leaf keeps
    exactly its largest magnitude, and boundary ties all survive with the
    positive group winning the >= tie-break."""
    z = jnp.zeros(512)
    np.testing.assert_array_equal(np.asarray(ref.sbc_ref(z, 0.01)),
                                  np.zeros(512))
    np.testing.assert_array_equal(
        np.asarray(ops.sbc_compress(z, 0.01, block=128, interpret=True)),
        np.zeros(512))

    tiny = jnp.asarray([0.1, -5.0, 0.2])         # n*ratio < 1 → k = 1
    np.testing.assert_allclose(np.asarray(ref.sbc_ref(tiny, 0.01)),
                               [0.0, -5.0, 0.0], atol=1e-7)

    ties = jnp.asarray([2.0, -2.0, 2.0, -2.0, 1.0, -1.0, 0.5, 0.0])
    want = [2.0, 0.0, 2.0, 0.0, 0.0, 0.0, 0.0, 0.0]
    out = ref.sbc_ref(ties, 0.25)                # k=2, four tied at thr=2
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-7)
    out = ops.sbc_compress(ties, 0.25, block=8, interpret=True)
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-7)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(32, 2048), ratio=st.floats(0.005, 0.1),
       seed=st.integers(0, 50))
def test_sbc_kernel_composition_matches_oracle(n, ratio, seed):
    """Property: the two-kernel composition (``sbc_stats`` + ``sbc_apply``
    through ``ops.sbc_compress``) reproduces the ``sbc_tensor`` oracle in
    interpret mode across sizes, ratios, and draws — including sizes that
    need block padding."""
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.normal(size=n) * np.linspace(0.1, 2.0, n),
                    jnp.float32)
    out = ops.sbc_compress(g, ratio, block=256, interpret=True)
    want = ref.sbc_ref(g, ratio)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-6)


# ---------------------------------------------------------------------------
# the chip path's repairs: custom VJPs, family-shape tiling, per-client vmap
# ---------------------------------------------------------------------------


def _family_ssd_inputs(lead, s=16):
    """SSD operands at the mamba2 family's per-head dims (hidden 256:
    P=8, G=1, N=16, chunk 4), with 8 of its 64 heads to keep interpret
    mode quick; ``lead`` are the leading batch dims."""
    from repro.fed.model_engine import family_arch
    ssm = family_arch("mamba2", 256, 3).ssm
    h, p, g, n = 8, ssm.head_dim, ssm.n_groups, ssm.d_state
    ks = [jax.random.fold_in(KEY, 10 + i) for i in range(5)]
    x = jax.random.normal(ks[0], lead + (s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], lead + (s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    Bm = jax.random.normal(ks[3], lead + (s, g, n)) * 0.5
    Cm = jax.random.normal(ks[4], lead + (s, g, n)) * 0.5
    return (x, dt, A, Bm, Cm), ssm.chunk


def test_flash_attention_custom_vjp_matches_reference_grad():
    """The kernel's forward + the oracle's recomputed VJP give the value and
    gradients of plain GQA attention (the model's naive path)."""
    from repro.models.attention import attend_naive
    B, S, Hq, Hkv, hd = 2, 16, 4, 2, 64
    q = jax.random.normal(jax.random.fold_in(KEY, 0), (B, S, Hq, hd))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, Hkv, hd))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, Hkv, hd))
    ct = jax.random.normal(jax.random.fold_in(KEY, 3), (B, S, Hq, hd))
    pos = jnp.arange(S)

    def kern(q, k, v):
        return jnp.sum(ops.flash_attention(q, k, v, interpret=True) * ct)

    def naive(q, k, v):
        return jnp.sum(attend_naive(q, k, v, pos, pos) * ct)

    got_v, got_g = jax.value_and_grad(kern, argnums=(0, 1, 2))(q, k, v)
    want_v, want_g = jax.value_and_grad(naive, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(got_v), float(want_v), rtol=2e-5)
    for got, want in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


def test_ssd_custom_vjp_matches_reference_grad():
    """``ops.ssd`` differentiates (kernel forward, reference VJP) to the
    gradients of ``ssd_reference`` in every operand, A included."""
    args, chunk = _family_ssd_inputs((2,))
    ct = jax.random.normal(jax.random.fold_in(KEY, 20), args[0].shape)

    def kern(*a):
        return jnp.sum(ops.ssd(*a, chunk=chunk, interpret=True) * ct)

    def oracle(*a):
        return jnp.sum(ref.ssd_ref(*a, chunk=chunk) * ct)

    argnums = tuple(range(5))
    got_v, got_g = jax.value_and_grad(kern, argnums=argnums)(*args)
    want_v, want_g = jax.value_and_grad(oracle, argnums=argnums)(*args)
    np.testing.assert_allclose(float(got_v), float(want_v), rtol=1e-4)
    for got, want in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("lead", [(2,), (2, 3)], ids=["clients", "rows-clients"])
def test_ssd_scan_family_chunk_under_vmap(lead):
    """The head-major ``ssd_scan`` at the family chunk matches the oracle
    when vmapped the way the engine runs it (over clients, then rows)."""
    args, chunk = _family_ssd_inputs(lead)
    A = args[2]
    kern = lambda x, dt, Bm, Cm: ssd_scan(x, dt, A, Bm, Cm,  # noqa: E731
                                          chunk=chunk, interpret=True)
    oracle = lambda x, dt, Bm, Cm: ref.ssd_ref(x, dt, A, Bm, Cm,  # noqa: E731
                                               chunk=chunk)
    for _ in range(len(lead) - 1):
        kern, oracle = jax.vmap(kern), jax.vmap(oracle)
    x, dt, _, Bm, Cm = args
    np.testing.assert_allclose(np.asarray(kern(x, dt, Bm, Cm)),
                               np.asarray(oracle(x, dt, Bm, Cm)),
                               atol=3e-4, rtol=3e-4)


@pytest.mark.parametrize("shape,lead", [((6, 256, 512), 1),
                                        ((2, 6, 40), 2),
                                        ((6, 3), 1)],
                         ids=["clients", "rows-clients", "tiny-leaf"])
def test_sbc_kernels_vmapped_match_oracle_per_row(shape, lead):
    """The two SBC kernels vmapped over clients (and scenario rows) give
    each client's leaf exactly its own ``sbc_tensor(exact=True)``."""
    g = jax.random.normal(jax.random.fold_in(KEY, 30), shape) * 2.0
    kern = lambda t: ops.sbc_compress(t, 0.005, interpret=True)  # noqa: E731
    for _ in range(lead):
        kern = jax.vmap(kern)
    leaves = np.asarray(g).reshape((-1,) + shape[lead:])
    outs = np.asarray(kern(g)).reshape(leaves.shape)
    for leaf, out in zip(leaves, outs):
        want = np.asarray(ref.sbc_ref(jnp.asarray(leaf), 0.005, exact=True))
        np.testing.assert_allclose(out, want, atol=1e-6)
        np.testing.assert_array_equal(out != 0, want != 0)
