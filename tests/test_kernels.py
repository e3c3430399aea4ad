"""Per-kernel shape/dtype sweeps: every Pallas kernel (interpret=True on this
CPU box) asserted allclose against its ref.py pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention import decode_attention, paged_decode_attention
from repro.kernels.decode_attention.ref import (decode_attention_ref,
                                                paged_decode_attention_ref)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.lstm_cell import lstm_cell_fused
from repro.kernels.lstm_cell.ref import lstm_cell_ref
from repro.kernels.moe_gmm import moe_gmm
from repro.kernels.moe_gmm.ref import moe_gmm_ref
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.rglru_scan.ref import rglru_scan_ref
from repro.kernels.ssm_scan import ssm_scan
from repro.kernels.ssm_scan.ref import ssm_scan_ref


def tol_for(dt):
    return dict(rtol=3e-2, atol=3e-2) if dt == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


def allclose(a, b, dt):
    np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32), **tol_for(dt)
    )


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Sq,Skv,Hq,Hkv,hd,causal,window",
    [
        (2, 128, 128, 4, 1, 64, True, None),    # MQA causal
        (1, 256, 256, 8, 2, 32, True, 64),      # GQA sliding window
        (2, 64, 64, 4, 4, 16, False, None),     # MHA bidirectional
        (1, 128, 256, 4, 2, 64, False, None),   # cross-attn (Sq != Skv)
        (1, 192, 192, 2, 1, 128, True, None),   # non-pow2 seq, big head
    ],
)
def test_flash_attention(B, Sq, Skv, Hq, Hkv, hd, causal, window, dt):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, Sq, Hq, hd), dt)
    k = jax.random.normal(ks[1], (B, Skv, Hkv, hd), dt)
    v = jax.random.normal(ks[2], (B, Skv, Hkv, hd), dt)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_k=64, interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    allclose(out, ref, dt)


def test_flash_attention_matches_model_layer():
    """Kernel agrees with the chunked_attention the models actually run."""
    from repro.models.layers import chunked_attention

    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (2, 128, 4, 32), jnp.float32)
    k = jax.random.normal(ks[1], (2, 128, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (2, 128, 2, 32), jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64, interpret=True)
    ref = chunked_attention(q, k, v, causal=True, chunk=64, q_chunk=64)
    allclose(out, ref, jnp.float32)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,Hq,Hkv,hd,window,fill",
    [
        (2, 256, 8, 1, 64, None, 200),   # MQA partial cache
        (1, 512, 16, 4, 32, 128, 512),   # GQA ring/window
        (2, 128, 4, 4, 16, None, 60),
    ],
)
def test_decode_attention(B, S, Hq, Hkv, hd, window, fill, dt):
    ks = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(ks[0], (B, Hq, hd), dt)
    kc = jax.random.normal(ks[1], (B, S, Hkv, hd), dt)
    vc = jax.random.normal(ks[2], (B, S, Hkv, hd), dt)
    kv_pos = jnp.where(jnp.arange(S) < fill, jnp.arange(S), -1)
    q_pos = jnp.asarray(fill, jnp.int32)
    out = decode_attention(q, kc, vc, kv_pos, q_pos, window=window,
                           block_k=64, interpret=True)
    ref = decode_attention_ref(q, kc, vc, kv_pos, q_pos, window=window)
    allclose(out, ref, dt)


def test_decode_attention_ring_buffer():
    """Ring-buffer slots (shuffled absolute positions) mask correctly."""
    B, S, H, hd = 1, 64, 4, 32
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    kc = jax.random.normal(ks[1], (B, S, H, hd), jnp.float32)
    vc = jax.random.normal(ks[2], (B, S, H, hd), jnp.float32)
    # slot s holds absolute position (s + 17) % 96 — some beyond q_pos
    kv_pos = (jnp.arange(S) + 17) % 96
    q_pos = jnp.asarray(48, jnp.int32)
    out = decode_attention(q, kc, vc, kv_pos, q_pos, window=32, block_k=32, interpret=True)
    ref = decode_attention_ref(q, kc, vc, kv_pos, q_pos, window=32)
    allclose(out, ref, jnp.float32)


# ---------------------------------------------------------------------------
# paged decode attention
# ---------------------------------------------------------------------------

def _paged_case(key, B, L, P, ps, n_pt, Hq, Hkv, hd, q_pos, mapped, dt):
    """A pool [P, L, ps, Hkv, hd]; row b maps ``mapped[b]``
    distinct pages and decodes its own token at ``q_pos[b]``."""
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (B, Hq, hd), dt)
    kp = jax.random.normal(ks[1], (P, L, ps, Hkv, hd), dt)
    vp = jax.random.normal(ks[2], (P, L, ps, Hkv, hd), dt)
    k_new = jax.random.normal(ks[3], (B, Hkv, hd), dt)
    v_new = jax.random.normal(ks[4], (B, Hkv, hd), dt)
    table = np.full((B, n_pt), -1, np.int32)
    nxt = 0
    for b, n in enumerate(mapped):
        for j in range(n):
            table[b, j] = nxt % P
            nxt += 1
    return (q, k_new, v_new, kp, vp, jnp.asarray(table),
            jnp.asarray(q_pos, jnp.int32))


def _seeded_ref(q, k_new, v_new, k_pages, v_pages, table, q_pos, window):
    """ref.py on the layer's pool with each row's own token written in at
    q_pos; a row whose page there is unmapped gets a scratch page."""
    kp, vp = np.array(k_pages), np.array(v_pages)
    table = np.asarray(table).copy()
    ps = kp.shape[1]
    for b, pos in enumerate(np.asarray(q_pos)):
        j, t = divmod(int(pos), ps)
        if table[b, j] < 0:
            table[b, j] = len(kp)
            kp = np.concatenate([kp, np.zeros_like(kp[:1])])
            vp = np.concatenate([vp, np.zeros_like(vp[:1])])
        kp[table[b, j], t] = np.asarray(k_new[b])
        vp[table[b, j], t] = np.asarray(v_new[b])
    return paged_decode_attention_ref(q, jnp.asarray(kp), jnp.asarray(vp),
                                      jnp.asarray(table), q_pos, window=window)


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,P,ps,n_pt,Hq,Hkv,hd,q_pos,mapped,window",
    [
        # MQA: a row past its live pages, a token on a page boundary, idle
        (3, 16, 16, 4, 8, 1, 64, (49, 16, 0), (4, 2, 0), None),
        # GQA sliding window
        (1, 8, 32, 3, 4, 2, 32, (69,), (3,), 48),
        # MHA: full row, a token on an unmapped tail page, a mid-page token
        (3, 12, 8, 6, 4, 4, 16, (47, 24, 22), (6, 3, 3), None),
    ],
)
def test_paged_decode_attention(B, P, ps, n_pt, Hq, Hkv, hd, q_pos, mapped,
                                window, dt):
    q, k_new, v_new, kp, vp, table, qp = _paged_case(
        jax.random.key(10), B, 2, P, ps, n_pt, Hq, Hkv, hd, q_pos, mapped, dt)
    layer = jnp.int32(1)
    ref = _seeded_ref(q, k_new, v_new, kp[:, 1], vp[:, 1], table, qp, window)
    # the reference's None is the op's window 0: the whole context
    out = paged_decode_attention(q, k_new, v_new, kp, vp, layer, table, qp,
                                 window=window or 0, use_kernel=True,
                                 interpret=True)
    allclose(out, ref, dt)
    # the jnp fallback path must agree too (it is what captured graphs run)
    jnp_out = paged_decode_attention(q, k_new, v_new, kp, vp, layer, table,
                                     qp, window=window or 0, use_kernel=False)
    allclose(jnp_out, ref, dt)


def test_paged_decode_reads_only_its_layer():
    """Changing another layer's pages, or pool positions at or after
    q_pos, changes nothing: the pool is read by (layer, page) and strictly
    below each row's own token."""
    q, k_new, v_new, kp, vp, table, qp = _paged_case(
        jax.random.key(12), 2, 3, 8, 8, 3, 4, 2, 16, (13, 5), (3, 1),
        jnp.float32)
    for use_kernel in (True, False):
        def run(kp, vp):
            return paged_decode_attention(q, k_new, v_new, kp, vp,
                                          jnp.int32(1), table, qp,
                                          use_kernel=use_kernel,
                                          interpret=True)
        base = run(kp, vp)
        noisy = kp.at[:, 0].set(-kp[:, 0]).at[:, 2].set(kp[:, 2] * 3)
        np.testing.assert_array_equal(np.asarray(run(noisy, vp)),
                                      np.asarray(base))
        # row 0's positions 13.. (page 1, offsets 5..7; page 2) and row 1's
        # 5.. (page 3, offsets 5..7) are not yet written in the engine
        tbl = np.asarray(table)
        stale = (kp.at[tbl[0, 1], 1, 5:].set(9.0).at[tbl[0, 2], 1].set(9.0)
                 .at[tbl[1, 0], 1, 5:].set(9.0))
        np.testing.assert_array_equal(np.asarray(run(stale, vp)),
                                      np.asarray(base))


def test_paged_matches_linear_decode_attention():
    """A paged cache laid out contiguously == the linear-cache kernel."""
    B, S, ps, H, hd = 2, 64, 16, 4, 32
    ks = jax.random.split(jax.random.key(11), 3)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    kc = jax.random.normal(ks[1], (B, S, H, hd), jnp.float32)
    vc = jax.random.normal(ks[2], (B, S, H, hd), jnp.float32)
    fill = 50
    kv_pos = jnp.where(jnp.arange(S) < fill, jnp.arange(S), -1)
    q_pos = jnp.asarray(fill - 1, jnp.int32)
    ref = decode_attention_ref(q, kc, vc, kv_pos, q_pos)
    # repack row b's cache as pages b*n_pt + j of layer 0; the token at
    # fill-1 is the row's own, the pool holds what lies before it
    n_pt = S // ps
    kp = kc.reshape(B * n_pt, 1, ps, H, hd)
    vp = vc.reshape(B * n_pt, 1, ps, H, hd)
    table = jnp.arange(B * n_pt, dtype=jnp.int32).reshape(B, n_pt)
    out = paged_decode_attention(q, kc[:, fill - 1], vc[:, fill - 1], kp, vp,
                                 jnp.int32(0), table,
                                 jnp.full((B,), fill - 1, jnp.int32),
                                 use_kernel=True, interpret=True)
    allclose(out, ref, jnp.float32)


# ---------------------------------------------------------------------------
# lstm cell
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("N,H,bn,bh", [(64, 128, 32, 64), (32, 256, 32, 128), (128, 64, 64, 64)])
def test_lstm_cell(N, H, bn, bh, dt):
    ks = jax.random.split(jax.random.key(4), 4)
    gx = jax.random.normal(ks[0], (N, 4 * H), dt)
    gh = jax.random.normal(ks[1], (N, 4 * H), dt)
    b = jax.random.normal(ks[2], (4 * H,), dt)
    c = jax.random.normal(ks[3], (N, H), dt)
    h1, c1 = lstm_cell_fused(gx, gh, b, c, block_n=bn, block_h=bh, interpret=True)
    h2, c2 = lstm_cell_ref(gx, gh, b, c)
    allclose(h1, h2, dt)
    allclose(c1, c2, dt)


def test_lstm_cell_matches_wavefront_cell():
    """Kernel math == core.wavefront.lstm_cell (the scheduling demo's cell)."""
    from repro.core.wavefront import lstm_cell

    ks = jax.random.split(jax.random.key(5), 5)
    B, D, H = 8, 32, 32
    params = {
        "Wx": jax.random.normal(ks[0], (D, 4 * H)) * 0.1,
        "Wh": jax.random.normal(ks[1], (H, 4 * H)) * 0.1,
        "b": jax.random.normal(ks[2], (4 * H,)) * 0.1,
    }
    x = jax.random.normal(ks[3], (B, D))
    h = jax.random.normal(ks[4], (B, H))
    c = jnp.zeros((B, H))
    h_ref, c_ref = lstm_cell(params, x, h, c)
    h_k, c_k = lstm_cell_fused(
        x @ params["Wx"], h @ params["Wh"], params["b"], c,
        block_n=8, block_h=32, interpret=True,
    )
    allclose(h_k, h_ref, jnp.float32)
    allclose(c_k, c_ref, jnp.float32)


# ---------------------------------------------------------------------------
# ssm scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,D,St,bd,bs", [(2, 128, 64, 8, 32, 32), (1, 256, 32, 16, 32, 64)])
def test_ssm_scan(B, S, D, St, bd, bs):
    ks = jax.random.split(jax.random.key(6), 3)
    a = jax.random.uniform(ks[0], (B, S, D, St), jnp.float32, 0.5, 0.999)
    b = jax.random.normal(ks[1], (B, S, D, St), jnp.float32) * 0.1
    c = jax.random.normal(ks[2], (B, S, St), jnp.float32)
    y1, h1 = ssm_scan(a, b, c, block_d=bd, block_s=bs, interpret=True)
    y2, h2 = ssm_scan_ref(a, b, c, jnp.zeros((B, D, St), jnp.float32))
    allclose(y1, y2, jnp.float32)
    allclose(h1, h2, jnp.float32)


def test_ssm_scan_state_carries_across_chunks():
    """Decay ~1 makes early inputs visible at the end — catches chunk-reset bugs."""
    B, S, D, St = 1, 128, 8, 4
    a = jnp.full((B, S, D, St), 0.999, jnp.float32)
    b = jnp.zeros((B, S, D, St)).at[:, 0].set(1.0)
    c = jnp.ones((B, S, St), jnp.float32)
    y, h_last = ssm_scan(a, b, c, block_d=8, block_s=16, interpret=True)
    # h at t decays as 0.999^t; y_t = sum_s h_t
    expect = St * 0.999 ** (S - 1)
    np.testing.assert_allclose(float(y[0, -1, 0]), expect, rtol=1e-4)


# ---------------------------------------------------------------------------
# rglru scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,R,br,bs", [(2, 256, 128, 64, 64), (1, 128, 64, 64, 32)])
def test_rglru_scan(B, S, R, br, bs):
    ks = jax.random.split(jax.random.key(7), 2)
    a = jax.random.uniform(ks[0], (B, S, R), jnp.float32, 0.5, 0.999)
    b = jax.random.normal(ks[1], (B, S, R), jnp.float32) * 0.1
    hs1, hl1 = rglru_scan(a, b, block_r=br, block_s=bs, interpret=True)
    hs2, hl2 = rglru_scan_ref(a, b, jnp.zeros((B, R), jnp.float32))
    allclose(hs1, hs2, jnp.float32)
    allclose(hl1, hl2, jnp.float32)


def test_rglru_matches_model_recurrence():
    """Kernel == the chunked pure-jnp recurrence the models run."""
    from repro.models.layers import linear_recurrence_chunked

    ks = jax.random.split(jax.random.key(8), 2)
    B, S, R = 2, 128, 64
    a = jax.random.uniform(ks[0], (B, S, R), jnp.float32, 0.5, 0.999)
    b = jax.random.normal(ks[1], (B, S, R), jnp.float32) * 0.1
    hs_k, hl_k = rglru_scan(a, b, block_r=64, block_s=32, interpret=True)
    hs_m, hl_m = linear_recurrence_chunked(a, b, jnp.zeros((B, R), jnp.float32), chunk=64)
    allclose(hs_k, hs_m, jnp.float32)
    allclose(hl_k, hl_m, jnp.float32)


# ---------------------------------------------------------------------------
# moe gmm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("sizes,K,N", [
    ((10, 0, 45, 3, 20), 128, 96),     # an empty group; a group over tiles
    ((0, 0, 0, 0), 64, 64),            # no row in any group
    ((96, 0, 0), 32, 128),             # one group larger than two tiles
    ((0, 7, 0, 0, 0, 0, 0, 30), 64, 256),  # leading empty groups; rows left
])
def test_moe_gmm(sizes, K, N, dt):
    """The grouped matmul of rows sorted by group, kernel in interpret mode
    (tiles of 32 rows and 128 lanes where they divide) and the ragged_dot
    path, against ref.py: each group's rows times its matrix, rows past
    every group zero."""
    M = 100
    ks = jax.random.split(jax.random.key(9), 2)
    x = jax.random.normal(ks[0], (M, K), dt)
    w = jax.random.normal(ks[1], (len(sizes), K, N), dt) * (1.0 / np.sqrt(K))
    gs = jnp.asarray(sizes, jnp.int32)
    want = moe_gmm_ref(x, w, gs)
    for use_kernel in (True, False):
        got = moe_gmm(x, w, gs, block_m=32, block_n=128, use_kernel=use_kernel,
                      interpret=True)
        allclose(got, want, dt)
        assert not np.asarray(got[sum(sizes):]).any()
