"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.distill_loss import fused_distill_rows
from repro.kernels.flash_attention import flash_attention
from repro.kernels.lane_mlp import fused_lane_mlp2, fused_mlp2
from repro.kernels.probe import probe_grad_step
from repro.kernels.ref import (flash_attention_ref, fused_distill_loss_ref,
                               mlp2_ref, probe_grad_ref, ssd_chunk_ref)


@pytest.mark.parametrize("backend,interpret", [("cpu", True),
                                               ("tpu", False)])
def test_ops_mode_follows_the_backend_at_call_time(monkeypatch, backend,
                                                   interpret):
    """The wrappers compile on a TPU and interpret on the CPU, decided
    when called, not when ``kernels.ops`` was imported."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert ops._interpret() is interpret


def test_ops_refuses_a_backend_without_a_kernel_path(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        ops._interpret()


@pytest.mark.parametrize("S,hd,bq,bk", [
    (128, 64, 64, 64),
    (256, 64, 128, 64),
    (256, 128, 64, 128),
    (512, 32, 128, 128),
])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 128), (False, 0)])
def test_flash_attention_sweep(S, hd, bq, bk, causal, window):
    key = jax.random.PRNGKey(S + hd)
    B, H = 1, 2
    q, k, v = [jax.random.normal(kk, (B, H, S, hd))
               for kk in jax.random.split(key, 3)]
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=bq, block_k=bk, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    key = jax.random.PRNGKey(7)
    B, H, S, hd = 2, 2, 128, 64
    q, k, v = [jax.random.normal(kk, (B, H, S, hd)).astype(dtype)
               for kk in jax.random.split(key, 3)]
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True)
    ref = flash_attention_ref(q, k, v, causal=True)
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_flash_attention_model_layout_wrapper():
    key = jax.random.PRNGKey(3)
    B, S, H, hd = 2, 128, 4, 32
    q, k, v = [jax.random.normal(kk, (B, S, H, hd))
               for kk in jax.random.split(key, 3)]
    out = ops.flash_attention(q, k, v, causal=True)
    ref = jnp.swapaxes(flash_attention_ref(
        *(jnp.swapaxes(t, 1, 2) for t in (q, k, v)), causal=True), 1, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("B,D,M", [(64, 8, 32), (200, 23, 256), (300, 5, 128)])
@pytest.mark.parametrize("kind", ["mse", "mae"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_distill_sweep(B, D, M, kind, dtype):
    key = jax.random.PRNGKey(B + M)
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, D)).astype(dtype)
    xh = jax.random.normal(ks[1], (B, D)).astype(dtype)
    z = jax.random.normal(ks[2], (B, M)).astype(dtype)
    zt = jax.random.normal(ks[3], (B, M)).astype(dtype)
    mask = (jax.random.uniform(ks[4], (B,)) > 0.4).astype(jnp.float32)
    rows = fused_distill_rows(x, xh, z, zt, mask, lam=0.05, kind=kind,
                              interpret=True)
    got = jnp.mean(rows)
    ref = fused_distill_loss_ref(x, xh, z, zt, mask, lam=0.05, kind=kind)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    assert abs(float(got) - float(ref)) < tol


@pytest.mark.parametrize("kind", ["mse", "mae"])
def test_fused_distill_grads_match_reference(kind):
    """The closed-form custom VJP (Eq. 5 backward) must match autodiff
    through the pure-jnp oracle w.r.t. every differentiable input."""
    key = jax.random.PRNGKey(17)
    ks = jax.random.split(key, 5)
    B, D, M = 200, 23, 16
    x = jax.random.normal(ks[0], (B, D))
    xh = jax.random.normal(ks[1], (B, D))
    z = jax.random.normal(ks[2], (B, M))
    zt = jax.random.normal(ks[3], (B, M))
    mask = (jax.random.uniform(ks[4], (B,)) > 0.4).astype(jnp.float32)

    def fused(x, xh, z, zt, m):
        return jnp.mean(fused_distill_rows(x, xh, z, zt, m, lam=0.05,
                                           kind=kind, interpret=True))

    def ref(x, xh, z, zt, m):
        return fused_distill_loss_ref(x, xh, z, zt, m, lam=0.05, kind=kind)

    got = jax.grad(fused, argnums=(0, 1, 2, 3, 4))(x, xh, z, zt, mask)
    want = jax.grad(ref, argnums=(0, 1, 2, 3, 4))(x, xh, z, zt, mask)
    for g, w, name in zip(got, want, ("x", "x_hat", "z", "z_t", "mask")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-6,
                                   rtol=1e-5, err_msg=name)


def test_distill_kernel_trains_under_value_and_grad():
    """ROADMAP bug: the kernel path used to raise under autodiff.  One
    value_and_grad step of the full make_loss(use_kernel=True) closure
    must now run and agree with the reference closure's gradients."""
    from repro.core import autoencoder as ae
    from repro.core import distill
    key = jax.random.PRNGKey(3)
    params = ae.init_autoencoder(key, [12, 16, 8])
    batch = {"x": jax.random.normal(key, (64, 12)),
             "z_teacher": jax.random.normal(key, (64, 8)),
             "aligned": (jax.random.uniform(key, (64,)) > 0.5).astype(
                 jnp.float32)}
    vk, gk = jax.value_and_grad(distill.make_loss(use_kernel=True))(
        params, batch)
    vr, gr = jax.value_and_grad(distill.make_loss(use_kernel=False))(
        params, batch)
    assert abs(float(vk) - float(vr)) < 1e-6
    for a, b in zip(jax.tree.leaves(gk), jax.tree.leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5,
                                   rtol=1e-4)


def test_fused_distill_unaligned_rows_ignore_teacher():
    """Rows with mask=0 must be pure reconstruction loss (Eq. 5 case 2)."""
    key = jax.random.PRNGKey(9)
    ks = jax.random.split(key, 4)
    B, D, M = 96, 10, 16
    x = jax.random.normal(ks[0], (B, D))
    xh = jax.random.normal(ks[1], (B, D))
    z = jax.random.normal(ks[2], (B, M))
    mask = jnp.zeros((B,))
    a = ops.fused_distill_loss(x, xh, z, jnp.zeros_like(z), mask)
    b = ops.fused_distill_loss(x, xh, z, 1e6 * jnp.ones_like(z), mask)
    assert abs(float(a) - float(b)) < 1e-6


def test_ssd_chunked_vs_sequential_ref():
    """The chunked (matmul-form) SSD must equal the sequential recurrence."""
    from repro.configs import get_smoke
    from repro.models.mamba2 import ssd_chunked
    cfg = get_smoke("zamba2-2.7b")
    key = jax.random.PRNGKey(11)
    ks = jax.random.split(key, 4)
    B, S, H, P = 2, 64, cfg.ssm_heads, cfg.ssm_head_dim
    G, N = cfg.ssm_groups, cfg.ssm_state
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, G, N))
    Cm = jax.random.normal(ks[0], (B, S, G, N))
    y, _ = ssd_chunked(cfg, x, dt, A, Bm, Cm)   # multiplies x*dt internally
    ref = ssd_chunk_ref(x, dt, A, Bm, Cm)       # dt*B*x in the recurrence
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("Lc,N,P", [(32, 8, 16), (64, 16, 32), (128, 16, 64)])
def test_ssd_intra_chunk_kernel(Lc, N, P):
    """Pallas SSD intra-chunk kernel vs dense decay-matrix reference."""
    from repro.kernels.ssd_chunk import ssd_intra_chunk
    key = jax.random.PRNGKey(Lc + N)
    ks = jax.random.split(key, 4)
    G = 4
    a = -jax.nn.softplus(jax.random.normal(ks[0], (G, Lc)))
    B = jax.random.normal(ks[1], (G, Lc, N))
    C = jax.random.normal(ks[2], (G, Lc, N))
    x = jax.random.normal(ks[3], (G, Lc, P))
    y, st = ssd_intra_chunk(a, B, C, x, interpret=True)
    cs = jnp.cumsum(a, axis=1)
    Lmat = jnp.where(np.tril(np.ones((Lc, Lc), bool)),
                     jnp.exp(cs[:, :, None] - cs[:, None, :]), 0.0)
    scores = jnp.einsum("gln,gsn->gls", C, B)
    y_ref = jnp.einsum("gls,gsp->glp", scores * Lmat, x)
    st_ref = jnp.einsum("gsn,gs,gsp->gnp", B,
                        jnp.exp(cs[:, -1:] - cs), x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref),
                               atol=2e-4, rtol=2e-4)


def test_ssd_kernel_composes_full_scan():
    """Kernel intra-chunk + host inter-chunk recurrence == sequential SSD."""
    from repro.kernels.ssd_chunk import ssd_intra_chunk
    key = jax.random.PRNGKey(5)
    ks = jax.random.split(key, 5)
    B_, S, H, P, N, Lc = 2, 64, 3, 16, 8, 16
    Nc = S // Lc
    x = jax.random.normal(ks[0], (B_, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B_, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B_, S, 1, N))
    Cm = jax.random.normal(ks[4], (B_, S, 1, N))

    ref = ssd_chunk_ref(x, dt, A, Bm, Cm)

    # assemble via kernel: flatten (B, Nc, H) -> grid
    ch = lambda t: t.reshape((B_, Nc, Lc) + t.shape[2:])
    a = ch(dt * A)                                    # (B,Nc,Lc,H)
    xdt = ch(x * dt[..., None])                       # (B,Nc,Lc,H,P)
    Bh = jnp.repeat(ch(Bm), H, axis=3)
    Ch = jnp.repeat(ch(Cm), H, axis=3)
    g = lambda t: jnp.moveaxis(t, 3, 2).reshape((B_ * Nc * H,) + t.shape[2:3] + t.shape[4:]) \
        if t.ndim == 5 else jnp.moveaxis(t, 3, 2).reshape(B_ * Nc * H, Lc)
    y_i, st = ssd_intra_chunk(g(a), g(Bh), g(Ch), g(xdt), interpret=True)
    y_i = jnp.moveaxis(y_i.reshape(B_, Nc, H, Lc, P), 2, 3)
    st = st.reshape(B_, Nc, H, N, P)

    cs = jnp.cumsum(a, axis=2)
    chunk_decay = jnp.exp(cs[:, :, -1, :])            # (B,Nc,H)

    def body(h, inp):
        s, dec = inp
        h_out = h
        return h * dec[:, :, None, None] + s, h_out

    _, h_prev = jax.lax.scan(body, jnp.zeros((B_, H, N, P)),
                             (jnp.moveaxis(st, 1, 0),
                              jnp.moveaxis(chunk_decay, 1, 0)))
    h_prev = jnp.moveaxis(h_prev, 0, 1)
    y_x = jnp.einsum("bclhn,bchnp,bclh->bclhp", Ch, h_prev, jnp.exp(cs))
    y = (y_i + y_x).reshape(B_, S, H, P)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               atol=1e-3, rtol=1e-3)


# ---------------------------------------------------------------------------
# lane-blocked fused 2-layer MLP (kernels.lane_mlp)
# ---------------------------------------------------------------------------

def _mlp2_inputs(key, B, din, dh, dout):
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, din))
    w0 = jax.random.normal(ks[1], (din, dh)) / np.sqrt(din)
    b0 = jax.random.normal(ks[2], (dh,)) * 0.1
    w1 = jax.random.normal(ks[3], (dh, dout)) / np.sqrt(dh)
    b1 = jax.random.normal(ks[4], (dout,)) * 0.1
    return x, w0, b0, w1, b1


@pytest.mark.parametrize("B,din,dh,dout,bb", [
    (128, 6, 8, 4, 64),       # rows divide the block
    (200, 30, 64, 128, 128),  # padding path (200 -> 256)
    (96, 5, 64, 128, 128),    # B < block_b (single padded tile)
])
@pytest.mark.parametrize("final_act", [False, True])
def test_fused_mlp2_sweep(B, din, dh, dout, bb, final_act):
    args = _mlp2_inputs(jax.random.PRNGKey(B + din), B, din, dh, dout)
    out = fused_mlp2(*args, final_act=final_act, block_b=bb, interpret=True)
    ref = mlp2_ref(*args, final_act=final_act)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("final_act", [False, True])
def test_fused_mlp2_grads_match_autodiff(final_act):
    """The closed-form VJP (module docstring chain rule) must match
    autodiff through the jnp oracle w.r.t. every input — this is the
    exactness the lane engine's value_and_grad training relies on."""
    args = _mlp2_inputs(jax.random.PRNGKey(21), 200, 10, 16, 8)

    def fused(*a):
        return jnp.mean(jnp.square(fused_mlp2(*a, final_act=final_act,
                                              block_b=64, interpret=True)))

    def oracle(*a):
        return jnp.mean(jnp.square(mlp2_ref(*a, final_act=final_act)))

    got = jax.grad(fused, argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(oracle, argnums=(0, 1, 2, 3, 4))(*args)
    for g, w, name in zip(got, want, ("x", "w0", "b0", "w1", "b1")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-6,
                                   rtol=1e-5, err_msg=name)


def test_fused_lane_mlp2_dead_lanes_exact_zero():
    """Stacked-lane form: the vmap-prepended lane grid must reproduce each
    live lane's per-lane result and render dead (live=0) lanes as exact
    zeros — the invariant the lane-padded engine depends on."""
    key = jax.random.PRNGKey(4)
    L, B, din, dh, dout = 4, 96, 6, 8, 4
    per_lane = [_mlp2_inputs(k, B, din, dh, dout)
                for k in jax.random.split(key, L)]
    xs, w0s, b0s, w1s, b1s = (jnp.stack(t) for t in zip(*per_lane))
    live = jnp.asarray([1.0, 1.0, 0.0, 1.0])
    out = fused_lane_mlp2(xs, w0s, b0s, w1s, b1s, live, block_b=64,
                          interpret=True)
    assert np.all(np.asarray(out[2]) == 0.0)
    for i in (0, 1, 3):
        np.testing.assert_allclose(np.asarray(out[i]),
                                   np.asarray(mlp2_ref(*per_lane[i])),
                                   atol=2e-5, rtol=2e-5)


def test_lane_mlp_kernel_recon_loss_trains_under_value_and_grad():
    """One value_and_grad step of the lane-engine loss with the fused
    reconstruction path must agree with the jnp closure's gradients."""
    from repro.core import autoencoder as ae
    key = jax.random.PRNGKey(6)
    params = ae.init_autoencoder(key, [12, 16, 8])
    batch = {"x": jax.random.normal(key, (64, 12)),
             "mask": jnp.ones((12,)),
             "row_w": (jax.random.uniform(key, (64,)) > 0.3).astype(
                 jnp.float32)}
    vk, gk = jax.value_and_grad(ae.make_masked_recon_loss(True))(
        params, batch)
    vr, gr = jax.value_and_grad(ae.make_masked_recon_loss(False))(
        params, batch)
    assert abs(float(vk) - float(vr)) < 1e-6
    for a, b in zip(jax.tree.leaves(gk), jax.tree.leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5,
                                   rtol=1e-4)


# ---------------------------------------------------------------------------
# fused probe step (kernels.probe)
# ---------------------------------------------------------------------------

def _probe_inputs(key, n, d, c):
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (n, d))
    w = jax.random.normal(ks[1], (d, c)) * 0.1
    b = jax.random.normal(ks[2], (c,)) * 0.1
    y = jax.random.randint(ks[3], (n,), 0, c)
    rw = (jax.random.uniform(ks[4], (n,)) > 0.3).astype(jnp.float32)
    return w, b, x, y, rw


@pytest.mark.parametrize("n,d,c,bb", [
    (128, 16, 2, 64),    # rows divide the block
    (300, 33, 4, 128),   # padding path (300 -> 384)
    (96, 8, 3, 128),     # n < block_b
])
def test_probe_grad_step_sweep(n, d, c, bb):
    args = _probe_inputs(jax.random.PRNGKey(n + d), n, d, c)
    got = probe_grad_step(*args, block_b=bb, interpret=True)
    want = probe_grad_ref(*args)
    for a, b, name in zip(got, want, ("loss", "dW", "db")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   rtol=2e-5, err_msg=name)


def test_probe_grad_step_vmapped_fold_lanes():
    """k folds as vmap lanes (in_axes=(0, 0, None, None, 0), the
    classifier's fold-blocked layout): each lane must equal its solo
    reference — shared x/y, per-fold weights and row masks."""
    key = jax.random.PRNGKey(12)
    k, n, d, c = 5, 200, 16, 3
    _, _, x, y, _ = _probe_inputs(key, n, d, c)
    ks = jax.random.split(jax.random.PRNGKey(13), k)
    ws = jnp.stack([jax.random.normal(kk, (d, c)) * 0.1 for kk in ks])
    bs = jnp.stack([jax.random.normal(kk, (c,)) * 0.1 for kk in ks])
    rws = jnp.stack([(jax.random.uniform(kk, (n,)) > 0.4).astype(
        jnp.float32) for kk in ks])
    got = jax.vmap(
        lambda w, b, rw: probe_grad_step(w, b, x, y, rw, block_b=64,
                                         interpret=True),
        in_axes=(0, 0, 0))(ws, bs, rws)
    for i in range(k):
        want = probe_grad_ref(ws[i], bs[i], x, y, rws[i])
        for a, b, name in zip((got[0][i], got[1][i], got[2][i]), want,
                              ("loss", "dW", "db")):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5, rtol=2e-5,
                                       err_msg=f"fold{i}/{name}")


def test_probe_zero_weight_rows_exactly_inert():
    """rw=0 rows (a fold's test rows / padding) must not influence the
    step at all — corrupting their features changes nothing."""
    key = jax.random.PRNGKey(9)
    w, b, x, y, rw = _probe_inputs(key, 160, 12, 4)
    dead = np.asarray(rw) == 0.0
    x_bad = np.asarray(x).copy()
    x_bad[dead] = 1e6
    a = probe_grad_step(w, b, x, y, rw, interpret=True)
    bb = probe_grad_step(w, b, jnp.asarray(x_bad), y, rw, interpret=True)
    for u, v in zip(a, bb):
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v))


def test_kfold_cv_kernel_path_matches_reference():
    """classifier.kfold_cv(use_kernel=True) routes every fold's 300 Adam
    steps through the fused probe kernel; the CV metrics must land within
    float-accumulation distance of the jnp path."""
    from repro.core import classifier as clf
    rng = np.random.RandomState(0)
    n, d, c = 120, 8, 2
    x = rng.randn(n, d).astype(np.float32)
    y = (x[:, 0] + 0.5 * rng.randn(n) > 0).astype(np.int64)
    ref = clf.kfold_cv(x, y, c, k=5, seed=0, use_kernel=False)
    ker = clf.kfold_cv(x, y, c, k=5, seed=0, use_kernel=True)
    for key_ in ref:
        assert abs(ref[key_] - ker[key_]) < 0.02, (key_, ref, ker)


@pytest.mark.parametrize("W,hd,bw,window", [
    (64, 32, 16, 0), (128, 64, 64, 0), (128, 64, 32, 48), (256, 128, 128, 0),
])
def test_decode_attention_kernel(W, hd, bw, window):
    """One-token cache attention kernel vs masked softmax reference."""
    from repro.kernels.decode_attention import decode_attention
    key = jax.random.PRNGKey(W + hd)
    ks = jax.random.split(key, 3)
    BH = 4
    q = jax.random.normal(ks[0], (BH, hd))
    k = jax.random.normal(ks[1], (BH, W, hd))
    v = jax.random.normal(ks[2], (BH, W, hd))
    pos = jnp.int32(W * 3 // 4)
    slot_pos = jnp.where(jnp.arange(W) <= int(pos), jnp.arange(W),
                         -1).astype(jnp.int32)
    out = decode_attention(q, k, v, slot_pos, pos, window=window,
                           block_w=bw, interpret=True)
    s = jnp.einsum("bd,bwd->bw", q, k) / np.sqrt(hd)
    ok = (slot_pos >= 0) & (slot_pos <= pos)
    if window:
        ok &= slot_pos > pos - window
    s = jnp.where(ok, s, -1e30)
    ref = jnp.einsum("bw,bwd->bd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_decode_attention_matches_model_decode_path():
    """ops.decode_attention == models.attention.decode_attention softmax."""
    from repro.kernels import ops as kops
    from repro.models.attention import _gqa_expand
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 3)
    B, H, K, W, hd = 2, 4, 2, 64, 32
    q = jax.random.normal(ks[0], (B, H, hd))
    kc = jax.random.normal(ks[1], (B, W, K, hd))
    vc = jax.random.normal(ks[2], (B, W, K, hd))
    pos = jnp.int32(50)
    slot_pos = jnp.where(jnp.arange(W) <= 50, jnp.arange(W), -1).astype(jnp.int32)
    ke = _gqa_expand(kc, H, K)
    ve = _gqa_expand(vc, H, K)
    out = kops.decode_attention(q, ke, ve, slot_pos, pos)
    s = jnp.einsum("bhd,bwhd->bhw", q, ke) / np.sqrt(hd)
    s = jnp.where((slot_pos >= 0) & (slot_pos <= pos), s, -1e30)
    ref = jnp.einsum("bhw,bwhd->bhd", jax.nn.softmax(s, -1), ve)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
