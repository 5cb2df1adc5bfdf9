"""Per-kernel correctness: Pallas (interpret=True) vs ref.py oracles,
swept over shapes and dtypes.

Interpret-mode Pallas sweeps take minutes — the whole module is marked
``slow`` so the fast tier-1 CI job (``-m "not slow"``) skips it; the
dedicated slow job runs it.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

pytestmark = pytest.mark.slow

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.decode_attention import decode_attention
from repro.kernels.ssd_scan import ssd_scan
from repro.kernels.rglru_scan import rglru_scan

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _arr(rng, shape, dtype):
    return jnp.asarray(rng.normal(0, 1, shape), dtype)


@pytest.mark.parametrize("B,S,H,K,D", [
    (1, 32, 2, 2, 8),      # MHA
    (2, 64, 4, 2, 16),     # GQA g=2
    (1, 48, 8, 2, 16),     # GQA g=4, odd block tail avoided (48%16==0)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mode", ["causal", "bidi", "window"])
def test_flash_attention(rng, B, S, H, K, D, dtype, mode):
    q = _arr(rng, (B, S, H, D), dtype)
    k = _arr(rng, (B, S, K, D), dtype)
    v = _arr(rng, (B, S, K, D), dtype)
    kw = dict(causal=(mode != "bidi"), window=8 if mode == "window" else 0)
    want = ref.mha(q, k, v, **kw)
    got = flash_attention(q, k, v, block_q=16, block_k=16, interpret=True,
                          **kw)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_flash_attention_q_offset(rng):
    """Chunked prefill: absolute positions via q_offset."""
    q = _arr(rng, (1, 16, 2, 8), jnp.float32)
    k = _arr(rng, (1, 64, 2, 8), jnp.float32)
    v = _arr(rng, (1, 64, 2, 8), jnp.float32)
    want = ref.mha(q, k, v, causal=True, q_offset=48)
    got = flash_attention(q, k, v, causal=True, q_offset=48,
                          block_q=16, block_k=16, interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_flash_attention_mla_vdim(rng):
    """MLA-style: v head dim != qk head dim."""
    q = _arr(rng, (1, 32, 4, 24), jnp.float32)
    k = _arr(rng, (1, 32, 4, 24), jnp.float32)
    v = _arr(rng, (1, 32, 4, 16), jnp.float32)
    want = ref.mha(q, k, v, causal=True, scale=24 ** -0.5)
    got = flash_attention(q, k, v, causal=True, scale=24 ** -0.5,
                          block_q=16, block_k=16, interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("B,H,K,D,Smax", [
    (2, 4, 2, 16, 64),
    (3, 8, 1, 8, 32),      # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n_layers", [0, 3], ids=["cache", "stack"])
def test_decode_attention(rng, B, H, K, D, Smax, dtype, n_layers):
    """One layer's cache, or a stack of layers that hold different values
    read at one layer (so reading another layer fails); KV blocks of 16
    positions, fewer than ``Smax``."""
    q = _arr(rng, (B, H, D), dtype)
    stack = (n_layers,) if n_layers else ()
    kc = _arr(rng, stack + (B, Smax, K, D), dtype)
    vc = _arr(rng, stack + (B, Smax, K, D), dtype)
    lengths = jnp.asarray(rng.integers(1, Smax, (B,)), jnp.int32)
    if n_layers:
        layer = jnp.int32(n_layers - 2)
        want = ref.decode_attention(q, kc[layer], vc[layer], lengths)
        np.testing.assert_array_equal(
            ref.decode_attention(q, kc, vc, lengths, layer=layer), want)
    else:
        layer = None
        want = ref.decode_attention(q, kc, vc, lengths)
    got = decode_attention(q, kc, vc, lengths, layer=layer, block_s=16,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("smax,kv_bytes,want", [
    (512, 32 * 256 * 2, 512),    # deepseek-7b: 16 MiB of K and V tiles
    (1024, 32 * 256 * 4, 256),   # the same in f32: 512 would take 32 MiB
    (2048, 8 * 128 * 2, 512),    # granite-3-2b: capped at MAX_BLOCK_S
    (48, 2 * 32 * 4, 48),        # all of a short cache
    (600, 32 * 256 * 2, 200),    # the largest multiple of 8 that divides
])
def test_decode_attention_kv_block(smax, kv_bytes, want):
    from repro.kernels.decode_attention import KV_VMEM_BYTES, kv_block
    bs = kv_block(smax, kv_bytes)
    assert bs == want
    assert smax % bs == 0 and 2 * bs * kv_bytes <= KV_VMEM_BYTES


def test_decode_attention_window(rng):
    q = _arr(rng, (2, 4, 8), jnp.float32)
    kc = _arr(rng, (2, 32, 2, 8), jnp.float32)
    vc = _arr(rng, (2, 32, 2, 8), jnp.float32)
    lengths = jnp.array([20, 31], jnp.int32)
    want = ref.decode_attention(q, kc, vc, lengths, window=8)
    got = decode_attention(q, kc, vc, lengths, window=8, block_s=8,
                           interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("B,S,H,P,G,N,chunk", [
    (1, 16, 2, 4, 1, 8, 4),
    (2, 32, 4, 8, 2, 16, 8),
    (1, 24, 2, 8, 2, 8, 24),   # single chunk
])
def test_ssd_scan(rng, B, S, H, P, G, N, chunk):
    x = _arr(rng, (B, S, H, P), jnp.float32)
    dt = jnp.abs(_arr(rng, (B, S, H), jnp.float32)) * 0.5 + 0.01
    A = -jnp.abs(_arr(rng, (H,), jnp.float32))
    Bm = _arr(rng, (B, S, G, N), jnp.float32)
    Cm = _arr(rng, (B, S, G, N), jnp.float32)
    D = _arr(rng, (H,), jnp.float32)
    yw, sw = ref.ssd(x, dt, A, Bm, Cm, D, chunk=chunk)
    yg, sg = ssd_scan(x, dt, A, Bm, Cm, D, chunk=chunk, interpret=True)
    np.testing.assert_allclose(yg, yw, atol=5e-5, rtol=5e-5)
    np.testing.assert_allclose(sg, sw, atol=5e-5, rtol=5e-5)


def test_ssd_chunk_invariance(rng):
    """The chunked algorithm must not depend on the chunk size."""
    B, S, H, P, G, N = 1, 32, 2, 4, 1, 8
    x = _arr(rng, (B, S, H, P), jnp.float32)
    dt = jnp.abs(_arr(rng, (B, S, H), jnp.float32)) * 0.5 + 0.01
    A = -jnp.abs(_arr(rng, (H,), jnp.float32))
    Bm = _arr(rng, (B, S, G, N), jnp.float32)
    Cm = _arr(rng, (B, S, G, N), jnp.float32)
    y4, s4 = ref.ssd(x, dt, A, Bm, Cm, None, chunk=4)
    y32, s32 = ref.ssd(x, dt, A, Bm, Cm, None, chunk=32)
    np.testing.assert_allclose(y4, y32, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(s4, s32, atol=1e-4, rtol=1e-4)


def test_ssd_vs_sequential_decode(rng):
    """Chunked scan == step-by-step recurrent decode."""
    B, S, H, P, G, N = 1, 12, 2, 4, 1, 8
    x = _arr(rng, (B, S, H, P), jnp.float32)
    dt = jnp.abs(_arr(rng, (B, S, H), jnp.float32)) * 0.5 + 0.01
    A = -jnp.abs(_arr(rng, (H,), jnp.float32))
    Bm = _arr(rng, (B, S, G, N), jnp.float32)
    Cm = _arr(rng, (B, S, G, N), jnp.float32)
    D = _arr(rng, (H,), jnp.float32)
    y_chunk, s_chunk = ref.ssd(x, dt, A, Bm, Cm, D, chunk=4)
    state = jnp.zeros((B, H, P, N), jnp.float32)
    ys = []
    for t in range(S):
        y, state = ref.ssd_decode(x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t],
                                  D, state)
        ys.append(y)
    y_seq = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(y_chunk, y_seq, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(s_chunk, state, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("B,S,W,bs,bw", [
    (1, 16, 8, 4, 8),
    (2, 32, 24, 8, 8),
    (1, 8, 16, 8, 16),
])
@pytest.mark.parametrize("with_h0", [False, True])
def test_rglru_scan(rng, B, S, W, bs, bw, with_h0):
    a = jax.nn.sigmoid(_arr(rng, (B, S, W), jnp.float32)) * 0.95
    b = _arr(rng, (B, S, W), jnp.float32)
    h0 = _arr(rng, (B, W), jnp.float32) if with_h0 else None
    hw, fw = ref.rglru(a, b, h0)
    hg, fg = rglru_scan(a, b, h0, block_s=bs, block_w=bw, interpret=True)
    np.testing.assert_allclose(hg, hw, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(fg, fw, atol=2e-5, rtol=2e-5)


def test_rglru_matches_naive_loop(rng):
    """associative_scan oracle vs plain python recurrence."""
    B, S, W = 1, 10, 4
    a = jax.nn.sigmoid(_arr(rng, (B, S, W), jnp.float32))
    b = _arr(rng, (B, S, W), jnp.float32)
    hw, _ = ref.rglru(a, b)
    h = np.zeros((B, W), np.float32)
    for t in range(S):
        h = np.asarray(a[:, t]) * h + np.asarray(b[:, t])
        np.testing.assert_allclose(np.asarray(hw[:, t]), h, atol=1e-5)


def test_mha_q_chunk_invariance(rng):
    """q-block-chunked attention == dense attention."""
    q = _arr(rng, (2, 32, 4, 8), jnp.float32)
    k = _arr(rng, (2, 32, 2, 8), jnp.float32)
    v = _arr(rng, (2, 32, 2, 8), jnp.float32)
    dense = ref.mha(q, k, v, causal=True)
    chunked = ref.mha(q, k, v, causal=True, q_chunk=8)
    unrolled = ref.mha(q, k, v, causal=True, q_chunk=8, unroll=True)
    np.testing.assert_allclose(chunked, dense, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(unrolled, dense, atol=1e-5, rtol=1e-5)
