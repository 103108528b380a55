"""Logistic-regression probe (paper Sec. 5 "Models": linear probing) +
k-fold cross-validation and F1/accuracy metrics (micro/macro/weighted).

``kfold_cv`` treats the k folds as replica lanes: every fold's train split
is padded to a common row count with zero-weight rows, and all k fits plus
their test-fold predictions run as ONE jitted ``lax.scan``
(``_fit_predict_folds``).  Uneven ``array_split`` shapes used to force one
recompile per distinct fold size; now there is exactly one compile per
(n, d, k, n_classes) and one host sync for all predictions.  Zero-weight
padding is exact, not approximate: the weighted mean over real rows equals
the unweighted mean the per-fold path took, so gradients (and hence the
fitted probes) match to float tolerance — ``tests/test_replicas.py`` pins
parity against a per-fold reference.

The fit itself is FOLD-BLOCKED (``_probe_grads_blocked``): instead of
gathering k private per-fold copies of ``x`` and vmapping k independent
scans, every fold carries a full-row 0/1 weight vector (zero on its own
test rows) and all k probes advance through one closed-form gradient whose
fold axis is a column block of a single GEMM pair.  The probe step is
memory-bound on re-reading ``x``; reading it once for all folds instead of
once per fold is the dominant CV speedup on CPU.  ``use_kernel=True``
routes the same full-row-weight step through the fused Pallas probe kernel
(``kernels.probe``) with every fold a lane of the kernel grid.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import padding
from repro.optim.adam import paper_adam


def init_logreg(key, n_features: int, n_classes: int) -> dict:
    return {"w": jnp.zeros((n_features, n_classes)),
            "b": jnp.zeros((n_classes,))}


def logreg_logits(params: dict, x: jax.Array) -> jax.Array:
    return x @ params["w"] + params["b"]


def logreg_loss(params: dict, batch: dict) -> jax.Array:
    logits = logreg_logits(params, batch["x"])
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["y"][:, None], axis=-1)[:, 0]
    l2 = 1e-4 * jnp.sum(jnp.square(params["w"]))
    return jnp.mean(lse - gold) + l2


def _weighted_logreg_loss(params, x, y, w) -> jax.Array:
    """``logreg_loss`` with per-row weights: with 0/1 weights the weighted
    mean over real rows equals the plain mean over those rows exactly, so
    zero-weight padding rows are invisible to the gradients."""
    logits = logreg_logits(params, x)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    l2 = 1e-4 * jnp.sum(jnp.square(params["w"]))
    return jnp.sum((lse - gold) * w) / jnp.maximum(jnp.sum(w), 1.0) + l2


@partial(jax.jit, static_argnames=("n_classes", "steps", "lr",
                                   "use_kernel"))
def fit_logreg(x, y, n_classes: int, steps: int = 300, lr: float = 0.1,
               use_kernel: bool = False):
    """Full-batch Adam logistic regression (fast jit'd probe), on the same
    optimizer the training engine uses (repro.optim.adam).
    ``use_kernel=True`` computes each step's gradient through the fused
    Pallas probe kernel (``kernels.probe``, exact same math: all-ones row
    weights make the weighted CE the plain mean)."""
    params = {"w": jnp.zeros((x.shape[1], n_classes), jnp.float32),
              "b": jnp.zeros((n_classes,), jnp.float32)}
    opt = paper_adam(lr)
    if use_kernel:
        from repro.kernels import ops as kops
        ones = jnp.ones((x.shape[0],), jnp.float32)

        def grads(p):
            _, dw, db = kops.probe_grad_step(p["w"], p["b"], x, y, ones)
            return {"w": dw, "b": db}
    else:
        def grads(p):
            return jax.grad(logreg_loss)(p, {"x": x, "y": y})

    def step(carry, _):
        params, state = carry
        params, state, _ = opt.update(grads(params), state, params)
        return (params, state), None

    (params, _), _ = jax.lax.scan(step, (params, opt.init(params)), None,
                                  length=steps)
    return params


def _probe_grads_blocked(w, b, x, onehot, rw, *, l2: float = 1e-4):
    """Closed-form weighted softmax-CE gradient for ALL k fold probes in
    one pass over the SHARED ``x`` — the fold axis becomes a column block
    of a single GEMM pair instead of k gathered per-fold copies.

    ``w``: (k, d, C) stacked probes, ``b``: (k, C), ``x``: (n, d),
    ``onehot``: (n, C), ``rw``: (n, k) per-fold normalized row weights
    (0 for the fold's own test rows and padding).  The probe step is
    memory-bound on re-reading ``x``; this reads it exactly twice per
    step (logits + grad) for every fold at once, where the gathered
    per-fold layout read k private copies.  Matches the autodiff gradient
    of ``_weighted_logreg_loss`` exactly (``kernels.ref.probe_grad_ref``
    pins the algebra)."""
    k, d, c = w.shape
    w2 = w.transpose(1, 0, 2).reshape(d, k * c)
    logits = (x @ w2).reshape(-1, k, c) + b[None]
    g = (jax.nn.softmax(logits, axis=-1) - onehot[:, None, :]) * rw[:, :, None]
    dw = (x.T @ g.reshape(-1, k * c)).reshape(d, k, c).transpose(1, 0, 2)
    return dw + 2.0 * l2 * w, jnp.sum(g, axis=0)


def _fit_predict_folds_blocked(x, y, tr_idx, tr_w, te_idx, *, n_classes,
                               steps, lr, use_kernel=False):
    """Fold-blocked probe fits + test-fold predictions for one seed: all
    k probes advance together through ``steps`` Adam steps of
    ``_probe_grads_blocked``.  Zero-weight rows make the padding exact
    (module docstring).  ``use_kernel=True`` takes the same full-row-
    weight step through the fused Pallas probe kernel instead — every
    fold a lane of the kernel grid (``jax.vmap`` over stacked probes,
    shared ``x``/``y``)."""
    n = x.shape[0]
    k = tr_idx.shape[0]
    rw_full = jax.vmap(
        lambda tri, trw: jnp.zeros((n,), jnp.float32).at[tri].add(trw)
    )(tr_idx, tr_w)                                         # (k, n)
    denom = jnp.maximum(jnp.sum(tr_w, axis=1), 1.0)         # (k,)
    rw = (rw_full / denom[:, None]).T                       # (n, k)
    onehot = jax.nn.one_hot(y, n_classes, dtype=jnp.float32)
    params = {"w": jnp.zeros((k, x.shape[1], n_classes), jnp.float32),
              "b": jnp.zeros((k, n_classes), jnp.float32)}
    opt = paper_adam(lr)
    if use_kernel:
        from repro.kernels import ops as kops

        def fold_grads(w, b):
            # kernel normalizes by sum(rw) internally == denom for 0/1 w
            return jax.vmap(
                lambda wk, bk, rwk: kops.probe_grad_step(wk, bk, x, y, rwk),
                in_axes=(0, 0, 0))(w, b, rw_full)[1:]
    else:
        def fold_grads(w, b):
            return _probe_grads_blocked(w, b, x, onehot, rw)

    def step(carry, _):
        p, s = carry
        dw, db = fold_grads(p["w"], p["b"])
        p, s, _ = opt.update({"w": dw, "b": db}, s, p)
        return (p, s), None

    (params, _), _ = jax.lax.scan(step, (params, opt.init(params)), None,
                                  length=steps)
    logits = jnp.einsum("ked,kdc->kec", x[te_idx], params["w"]) \
        + params["b"][:, None, :]
    return jnp.argmax(logits, axis=-1)


@partial(jax.jit, static_argnames=("n_classes", "steps", "lr",
                                   "use_kernel"))
def _fit_predict_folds(x, y, tr_idx, tr_w, te_idx, *, n_classes: int,
                       steps: int = 300, lr: float = 0.1,
                       use_kernel: bool = False):
    """All k probe fits + test-fold predictions as one fold-blocked jitted
    call.

    ``tr_idx``/``te_idx`` are (k, max_tr)/(k, max_te) row indices into
    ``x`` (padded entries point at row 0), ``tr_w`` the matching 0/1 row
    weights.  Returns (k, max_te) predicted labels; padded test slots are
    sliced off by the host caller."""
    return _fit_predict_folds_blocked(x, y, tr_idx, tr_w, te_idx,
                                      n_classes=n_classes, steps=steps,
                                      lr=lr, use_kernel=use_kernel)


@partial(jax.jit, static_argnames=("n_classes", "steps", "lr",
                                   "use_kernel"))
def _fit_predict_folds_many(x, y, tr_idx, tr_w, te_idx, *, n_classes: int,
                            steps: int = 300, lr: float = 0.1,
                            use_kernel: bool = False):
    """S seeds x k folds of probe fits as one vmapped fold-blocked call:
    ``x``/``y`` carry a leading seed axis, the index arrays a leading
    (S, k) pair.  Returns (S, k, max_te) predicted labels."""
    per_seed = partial(_fit_predict_folds_blocked, n_classes=n_classes,
                       steps=steps, lr=lr, use_kernel=use_kernel)
    return jax.vmap(per_seed)(x, y, tr_idx, tr_w, te_idx)


def predict(params: dict, x) -> np.ndarray:
    return np.asarray(jnp.argmax(logreg_logits(params, jnp.asarray(x)),
                                 axis=-1))


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def f1_scores(y_true: np.ndarray, y_pred: np.ndarray, n_classes: int) -> dict:
    """Returns micro/macro/weighted F1 and accuracy.

    One ``np.bincount`` confusion matrix instead of four full-array passes
    per class; ``tests/test_replicas.py`` pins parity against the loop."""
    y_true = np.asarray(y_true)
    y_pred = np.asarray(y_pred)
    cm = np.bincount(y_true * n_classes + y_pred,
                     minlength=n_classes * n_classes)
    cm = cm.reshape(n_classes, n_classes)        # rows: true, cols: pred
    tp = np.diag(cm).astype(np.float64)
    fp = cm.sum(axis=0) - tp
    fn = cm.sum(axis=1) - tp
    support = cm.sum(axis=1).astype(np.float64)
    denom = 2 * tp + fp + fn
    f1c = np.where(denom > 0, 2 * tp / np.maximum(denom, 1), 0.0)
    micro_d = 2 * tp.sum() + fp.sum() + fn.sum()
    return {
        "accuracy": float(tp.sum() / max(len(y_true), 1)),
        "f1_micro": float(2 * tp.sum() / micro_d) if micro_d else 0.0,
        "f1_macro": float(np.mean(f1c)),
        "f1_weighted": float(np.sum(f1c * support) / max(support.sum(), 1)),
        # binary convention (positive class = 1), used for UCI credit card
        "f1_binary": float(f1c[1]) if n_classes == 2 else float(np.mean(f1c)),
    }


def _fold_arrays(n: int, k: int, seed: int):
    """The paper's fold assignment (seeded permutation + ``array_split``)
    as padded index arrays (``core.padding.pad_index_rows`` — the same
    zero-weight-row trick the lane engine uses): (k, max_tr) train indices
    + 0/1 weights (padded slots gather row 0 at zero weight — inert) and
    (k, max_te) test indices, plus the raw folds for host-side metric
    slicing."""
    perm = np.random.RandomState(seed).permutation(n)
    folds = np.array_split(perm, k)
    te_lens = [len(f) for f in folds]
    trs = [np.concatenate([folds[j] for j in range(k) if j != i])
           for i in range(k)]
    tr_idx, tr_w = padding.pad_index_rows(trs)
    te_idx, _ = padding.pad_index_rows(folds)
    return tr_idx, tr_w, te_idx, folds, te_lens


def kfold_cv(x: np.ndarray, y: np.ndarray, n_classes: int, *, k: int = 10,
             seed: int = 0, use_kernel: bool = False) -> dict:
    """Paper evaluation: 10-fold CV of the logistic probe; mean metrics.

    Fold assignment is the same ``array_split`` as always; the k fits run
    as one fold-blocked jitted call over zero-weight-padded folds (module
    docstring), with a single host sync for all predictions.
    ``use_kernel=True`` routes every fold's gradient step through the
    fused Pallas probe kernel (``kernels.probe``)."""
    x = np.asarray(x)
    y = np.asarray(y)
    tr_idx, tr_w, te_idx, folds, te_lens = _fold_arrays(len(x), k, seed)
    preds = np.asarray(_fit_predict_folds(
        jnp.asarray(x), jnp.asarray(y), jnp.asarray(tr_idx),
        jnp.asarray(tr_w), jnp.asarray(te_idx), n_classes=n_classes,
        use_kernel=use_kernel))
    accs = [f1_scores(y[folds[i]], preds[i, :te_lens[i]], n_classes)
            for i in range(k)]
    return {k_: float(np.mean([a[k_] for a in accs])) for k_ in accs[0]}


def kfold_cv_many(xs, ys, n_classes: int, *, k: int = 10, seeds,
                  use_kernel: bool = False) -> list:
    """S independent k-fold CVs (one per seed, equal shapes) as ONE jitted
    call: every (seed, fold) pair is a lane of the vmapped fold-blocked
    fit — the replica-lane treatment of the evaluation stage, and the
    step-4 dispatch ``pipeline.run_apcvfl_replicated`` runs (one compile
    + one host sync for all S x k probes).  Returns one metrics dict per
    seed, each matching ``kfold_cv(xs[i], ys[i], ..., seed=seeds[i])``
    within lane-engine tolerance."""
    ys = [np.asarray(y) for y in ys]
    # one seed rides with a copy of itself: XLA compiles a stack of one
    # unlike a stack of many, and rounds differently, so a seed's
    # predictions would depend on how many seeds share the call
    reps = 2 if len(ys) == 1 else 1
    x_s = jnp.stack([jnp.asarray(x) for x in xs] * reps)      # (S, n, d)
    y_s = jnp.stack([jnp.asarray(y) for y in ys] * reps)
    per_seed = [_fold_arrays(x_s.shape[1], k, s)
                for s in list(seeds) * reps]
    preds = np.asarray(_fit_predict_folds_many(
        x_s, y_s,
        jnp.asarray(np.stack([p[0] for p in per_seed])),
        jnp.asarray(np.stack([p[1] for p in per_seed])),
        jnp.asarray(np.stack([p[2] for p in per_seed])),
        n_classes=n_classes, use_kernel=use_kernel))   # (S, k, max_te)
    out = []
    for si, (y, (_, _, _, folds, te_lens)) in enumerate(zip(ys, per_seed)):
        accs = [f1_scores(y[folds[i]], preds[si, i, :te_lens[i]], n_classes)
                for i in range(k)]
        out.append({k_: float(np.mean([a[k_] for a in accs]))
                    for k_ in accs[0]})
    return out
