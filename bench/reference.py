"""Plain reference of what the benchmark's cells compute.

Written from the paper (arXiv:2410.17648, Sec. 4 and App. B) in straight
``jax.numpy``: symmetric SELU autoencoders with a linear latent layer,
LeCun-normal weights and zero biases, Adam with Kingma & Ba's defaults,
early stopping on a 10 % validation split, the Eq. 5 distillation loss, and
a 10-fold logistic-regression probe trained by full-batch Adam.  It imports
nothing of the program under test and takes nothing the program made: it
rebuilds every initial weight from the seed by the published recipe.

Every matmul runs at ``precision`` (``highest`` for the reference).  The
control of ``correct`` is the same code with ``dtype=jnp.bfloat16``: the
parameters, the optimizer state and every activation are then held in
bfloat16, the nearest precision below the float32 the cells state.
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

B1, B2, EPS = 0.9, 0.999, 1e-8

# Table 3 encoder widths after the input width, by role
TABLE3 = {"g1_active": (64, 128), "g1_passive": (128, 256),
          "g2": (256, 256), "g3": (256, 256)}


def widths(role: str, n_in: int) -> list:
    return [n_in, *TABLE3[role]]


def init_mlp(key, ws, dtype=jnp.float32) -> dict:
    keys = jax.random.split(key, len(ws) - 1)
    p = {}
    for i, (a, b) in enumerate(zip(ws[:-1], ws[1:])):
        p[f"w{i}"] = (jax.random.normal(keys[i], (a, b)) / np.sqrt(a)
                      ).astype(dtype)
        p[f"b{i}"] = jnp.zeros((b,), dtype)
    return p


def init_autoencoder(key, enc_widths, dtype=jnp.float32) -> dict:
    k1, k2 = jax.random.split(key)
    return {"enc": init_mlp(k1, list(enc_widths), dtype),
            "dec": init_mlp(k2, list(enc_widths)[::-1], dtype)}


def mlp(p: dict, x, precision):
    n = len(p) // 2
    for i in range(n):
        x = jnp.matmul(x, p[f"w{i}"], precision=precision) + p[f"b{i}"]
        if i < n - 1:
            x = jax.nn.selu(x)
    return x


def encode(p, x, precision):
    return mlp(p["enc"], x, precision)


def recon_loss(p, batch, precision):
    x = batch["x"]
    x_hat = mlp(p["dec"], encode(p, x, precision), precision)
    return jnp.mean(jnp.square(x - x_hat))


def distill_loss(p, batch, precision, *, lam: float):
    """Eq. 5: reconstruction plus lam x the latent MSE on aligned rows."""
    x = batch["x"]
    z = encode(p, x, precision)
    x_hat = mlp(p["dec"], z, precision)
    rec = jnp.mean(jnp.square(x - x_hat), axis=-1)
    dis = jnp.mean(jnp.square(z - batch["z_teacher"]), axis=-1)
    return jnp.mean(rec + lam * dis * batch["aligned"].astype(rec.dtype))


def adam_step(p, m, v, g, t, lr):
    """One Adam step; ``t`` is the 1-based step count."""
    dt = jax.tree.leaves(p)[0].dtype
    tf = t.astype(jnp.float32)
    bc1, bc2 = 1.0 - B1 ** tf, 1.0 - B2 ** tf
    m = jax.tree.map(lambda m_, g_: (B1 * m_ + (1 - B1) * g_).astype(dt), m, g)
    v = jax.tree.map(lambda v_, g_: (B2 * v_ + (1 - B2) * g_ * g_).astype(dt),
                     v, g)
    p = jax.tree.map(
        lambda p_, m_, v_: (p_ - lr * (m_ / bc1) / (jnp.sqrt(v_ / bc2) + EPS)
                            ).astype(dt), p, m, v)
    return p, m, v


def split_rows(n: int, seed: int, val_frac: float = 0.1):
    """The paper's validation split: a seeded permutation, 10 % held out."""
    order = np.random.RandomState(seed).permutation(n)
    n_val = max(int(n * val_frac), 1)
    return order[n_val:], order[:n_val]


@partial(jax.jit, static_argnames=("loss", "batch_size", "epochs",
                                   "patience", "lr"))
def _fit(p0, key, tr, val, *, loss, batch_size, epochs, patience, lr):
    n_tr = tr["x"].shape[0]
    nb = n_tr // batch_size
    zeros = jax.tree.map(jnp.zeros_like, p0)

    def epoch(carry, e):
        p, m, v, t, best_p, best_v, since, live = carry
        perm = jax.random.permutation(jax.random.fold_in(key, e), n_tr)
        idx = perm[:nb * batch_size].reshape(nb, batch_size)

        def step(c, bidx):
            p_, m_, v_, t_ = c
            batch = {k: a[bidx] for k, a in tr.items()}
            value, g = jax.value_and_grad(loss)(p_, batch)
            p_, m_, v_ = adam_step(p_, m_, v_, g, t_ + 1, lr)
            return (p_, m_, v_, t_ + 1), value

        (p2, m2, v2, t2), losses = jax.lax.scan(step, (p, m, v, t), idx)
        keep = lambda a, b: jnp.where(live, a, b)
        p2, m2, v2 = (jax.tree.map(keep, p2, p), jax.tree.map(keep, m2, m),
                      jax.tree.map(keep, v2, v))
        t2 = jnp.where(live, t2, t)
        tl = jnp.mean(losses.astype(jnp.float32))
        vl = loss(p2, val).astype(jnp.float32)
        improved = live & (vl < best_v - 1e-6)
        best_p = jax.tree.map(lambda b, q: jnp.where(improved, q, b),
                              best_p, p2)
        best_v = jnp.where(improved, vl, best_v)
        since = jnp.where(improved, 0, since + 1)
        out = (jnp.where(live, tl, 0.0), jnp.where(live, vl, 0.0))
        live = live & (since < patience)
        return (p2, m2, v2, t2, best_p, best_v, since, live), out

    init = (p0, zeros, zeros, jnp.zeros((), jnp.int32), p0,
            jnp.asarray(jnp.inf, jnp.float32), jnp.zeros((), jnp.int32),
            jnp.asarray(True))
    carry, (tls, vls) = jax.lax.scan(epoch, init,
                                     jnp.arange(epochs, dtype=jnp.int32))
    return carry[4], tls, vls


def fit(p0, data: dict, seed: int, loss, *, batch_size: int, epochs: int,
        patience: int, lr: float) -> dict:
    """One autoencoder fit, as the paper describes it: mini-batches drawn
    by ``jax.random.permutation(fold_in(PRNGKey(seed), epoch))`` over the
    training rows, the remainder dropped, best-validation weights kept.
    Returns the best weights and the per-epoch train and validation
    losses."""
    n = len(next(iter(data.values())))
    ti, vi = split_rows(n, seed)
    dev = {k: jnp.asarray(a) for k, a in data.items()}
    tr = {k: a[ti] for k, a in dev.items()}
    val = {k: a[vi] for k, a in dev.items()}
    bs = max(min(batch_size, len(ti)), 1)
    best, tls, vls = _fit(p0, jax.random.PRNGKey(seed), tr, val, loss=loss,
                          batch_size=bs, epochs=epochs, patience=patience,
                          lr=lr)
    tls, vls = jax.device_get((tls, vls))
    return {"params": best, "train_loss": np.asarray(tls, np.float64),
            "val_loss": np.asarray(vls, np.float64)}


# ---------------------------------------------------------------------------
# the probe
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("n_classes", "steps", "lr", "precision"))
def _probe_fold(x, y, w_rows, x_test, *, n_classes, steps, lr, precision):
    dt = x.dtype
    p = {"w": jnp.zeros((x.shape[1], n_classes), dt),
         "b": jnp.zeros((n_classes,), dt)}

    def loss(q):
        logits = jnp.matmul(x, q["w"], precision=precision) + q["b"]
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        ce = jnp.sum((lse - gold) * w_rows) / jnp.sum(w_rows)
        return ce + 1e-4 * jnp.sum(jnp.square(q["w"]))

    def step(c, _):
        q, m, v, t = c
        q, m, v = adam_step(q, m, v, jax.grad(loss)(q), t + 1, lr)
        return (q, m, v, t + 1), None

    zeros = jax.tree.map(jnp.zeros_like, p)
    (p, _, _, _), _ = jax.lax.scan(
        step, (p, zeros, zeros, jnp.zeros((), jnp.int32)), None,
        length=steps)
    logits = jnp.matmul(x_test, p["w"], precision=precision) + p["b"]
    return jnp.argmax(logits, axis=-1)


def kfold_probe(z, y, n_classes: int, *, seed: int, k: int = 10,
                steps: int = 300, lr: float = 0.1,
                precision="highest") -> dict:
    """The paper's k-fold CV of a logistic probe on ``z``: mean accuracy
    over the folds of a seeded permutation split by ``array_split``, and
    each row's prediction by the probe of the fold that holds it out."""
    z = jnp.asarray(z)
    y = np.asarray(y)
    n = len(y)
    folds = np.array_split(np.random.RandomState(seed).permutation(n), k)
    accs = []
    preds = np.zeros(n, np.int64)
    for i, te in enumerate(folds):
        tr = np.concatenate([f for j, f in enumerate(folds) if j != i])
        pred = _probe_fold(z[tr], jnp.asarray(y[tr]),
                           jnp.ones((len(tr),), z.dtype), z[te],
                           n_classes=n_classes, steps=steps, lr=lr,
                           precision=precision)
        preds[te] = jax.device_get(pred)
        accs.append(np.mean(preds[te] == y[te]))
    return {"accuracy": float(np.mean(accs)), "pred": preds}


def rows_of_folds(fold_preds, n: int, *, seed: int, k: int = 10):
    """Per-row predictions from ``(k, max_te)`` predictions laid out as
    the folds of ``kfold_probe``: fold ``i``'s first ``len(fold)`` entries
    are its rows in order."""
    fold_preds = np.asarray(fold_preds)
    folds = np.array_split(np.random.RandomState(seed).permutation(n), k)
    preds = np.zeros(n, np.int64)
    for i, te in enumerate(folds):
        preds[te] = fold_preds[i, :len(te)]
    return preds


# ---------------------------------------------------------------------------
# the protocol (paper Fig. 3) for one seed
# ---------------------------------------------------------------------------

def psi(ids_a, ids_p):
    """Sorted common ids and their row positions on each side."""
    common, ia, ip = np.intersect1d(np.asarray(ids_a), np.asarray(ids_p),
                                    assume_unique=True, return_indices=True)
    return common, ia, ip


@lru_cache(maxsize=None)
def stage_loss(kind: str, precision, lam: float = 0.0):
    """One loss object per (kind, precision, lam), so that every seed's fit
    reuses one compiled program."""
    if kind == "recon":
        return partial(recon_loss, precision=precision)
    return partial(distill_loss, precision=precision, lam=lam)


def protocol(sc: dict, seed: int, hp: dict, *, probe: dict,
             dtype=jnp.float32, precision="highest") -> dict:
    """Steps 1-4 of the protocol for one seed on one scenario (a dict with
    ``xa``, ``xp``, ``ya``, ``ids_a``, ``ids_p``, ``n_classes``).  Returns
    every stage's initial and best weights and loss histories, the
    exchanged latents, the probe's metrics and its per-row predictions."""
    fit_kw = dict(batch_size=hp["batch_size"], epochs=hp["max_epochs"],
                  patience=hp["patience"], lr=hp["lr"])
    rl = stage_loss("recon", precision)
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed), 4)
    xa = jnp.asarray(sc["xa"], dtype)
    xp = jnp.asarray(sc["xp"], dtype)
    _, ia, ip = psi(sc["ids_a"], sc["ids_p"])
    out = {}

    def stage(name, key, role, data, lane_seed, loss):
        p0 = init_autoencoder(key, widths(role, data["x"].shape[1]), dtype)
        r = fit(p0, data, lane_seed, loss, **fit_kw)
        out[name] = {"init": p0, **r}
        return r["params"]

    g1a = stage("g1_active", k1, "g1_active", {"x": xa}, seed, rl)
    g1p = stage("g1_passive", k2, "g1_passive", {"x": xp}, seed + 1, rl)
    za = encode(g1a, xa[ia], precision)
    zp = encode(g1p, xp[ip], precision)
    out["exchange"] = zp
    zj = jnp.concatenate([za, zp], axis=1)
    g2 = stage("g2", k3, "g2", {"x": zj}, seed + 2, rl)
    zt = encode(g2, zj, precision)
    z_teacher = jnp.zeros((xa.shape[0], zt.shape[1]), dtype).at[ia].set(zt)
    aligned = jnp.zeros((xa.shape[0],), dtype).at[ia].set(1)
    g3 = stage("g3", k4, "g3",
               {"x": xa, "z_teacher": z_teacher, "aligned": aligned},
               seed + 3, stage_loss("distill", precision, float(hp["lam"])))
    z_all = encode(g3, xa, precision)
    m = kfold_probe(z_all, sc["ya"], sc["n_classes"], seed=seed,
                    k=probe["folds"], steps=probe["steps"], lr=probe["lr"],
                    precision=precision)
    out["probe_pred"] = m.pop("pred")
    out["metrics"] = m
    return out
