"""Device-resident scan-of-scans training engine for the tabular APC-VFL
stack.

Optimization is the paper's Adam (Kingma & Ba defaults, Appendix B) via
:mod:`repro.optim.adam`, <=200 epochs, early stopping on a 10% validation
split with patience 10.

Data-layout contract (the fused fit engine)
-------------------------------------------
``train`` takes ``data`` as a dict of equal-length, row-aligned host arrays.
The engine:

1. splits rows into train/val ONCE on the host (``np.random.RandomState(seed)``,
   identical split to the legacy loop) and uploads both sides to device ONCE;
2. draws each epoch's row permutation on device with ``jax.random``
   (``fold_in(PRNGKey(seed), epoch)``);
3. runs the WHOLE FIT as one jitted scan-of-scans: an outer ``lax.scan``
   over epochs whose carry holds the early-stop state (best-val params,
   best val loss, epochs-since-best, a ``live`` flag, epochs run) as
   traced values, and an inner ``lax.scan`` over ``(n_batches,
   batch_size)`` index slices for the epoch itself;
4. wraps the epoch body in ``lax.cond(live, ...)`` so once early stopping
   fires, the remaining outer iterations are cheap passthroughs — and the
   host syncs exactly ONCE per fit (epoch count + loss histories), not
   once per epoch.

Batching semantics: ``batch_size`` is clamped to the train-split size and the
epoch DROPS the remainder rows of the permutation (``n_batches = n_tr // bs``)
so every scan step sees a static batch shape.  Correctness is pinned by a
stored-trace oracle (``tests/data/train_trace.json``): a committed loss
trajectory recorded from this engine, which any semantic change to the
split, permutation, loss, or optimizer math will break.

The pre-fusion per-epoch loop survives as ``train_epochwise`` /
``train_lanes_epochwise``: it is the live parity oracle for the fused
engine (``tests/test_training_engine.py`` pins exact epoch counts and
best-val params on the stored-trace workloads) and the only path that can
run ``epoch_callback(epoch, params, train_loss, val_loss)`` — callbacks
need params on the host every epoch, which is precisely the sync the fused
engine removes, so ``train`` transparently routes callback users there.

Compilation caching: one jitted fit function exists per
``(loss identity, lr)`` — closures built by ``distill.make_loss`` carry a
semantic ``cache_key`` attribute so repeated stages reuse the same compiled
engine instead of re-tracing (see ``get_engine`` / ``get_fit_engine``).

Replica-lane training (``train_lanes``)
---------------------------------------
A *lane* is any independent training instance — a federated party's g1
stage, a seed replicate of the same stage, a CV fold.  ``train_lanes``
runs L lanes as ONE vmapped scan-of-scans: one upload, one compile, one
host sync per fit for ALL lanes.  K-party batching (PR 2's ``train_many``)
is the K-lane special case; seed replication stacks S replicates of every
stage into S x K lanes through the very same engine (``core.pipeline``'s
``run_apcvfl_replicated`` does exactly this).  The padded-stack layout
(:mod:`repro.core.padding`):

* every param leaf is zero-padded per-axis to the max shape across lanes
  and stacked along a leading lane axis (zero rows/cols feed on zero
  inputs and receive zero gradients, so each lane's real sub-block evolves
  exactly as it would unpadded);
* the lanes of one stack share their train row count (``_prep_lanes``
  raises otherwise; ``_lane_groups`` stacks only lanes of one data
  shape), so each epoch's batches are sliced straight from the lane's
  permutation of those rows; only the trailing feature width is
  zero-padded, and the stack stays on device throughout (jax-array
  inputs — e.g. encoder outputs of an earlier protocol stage — are padded
  and stacked without a host round-trip); when widths differ the loss
  must consume the ``mask`` (real-feature columns) entry the engine adds
  to each batch, beside an all-ones ``row_w`` — see
  ``autoencoder.masked_recon_loss``.  Equal-shape lanes (the seed-replica
  case) need no masking: losses that ignore the extra keys see exactly
  the batches ``train`` would feed them;
* each lane keeps its own host-side train/val split, PRNG stream, Adam
  state and step budget (``n_batches = n_tr // bs``; zero for a dead
  mesh-padding lane), and a per-lane step mask freezes params past a
  lane's own budget;
* early stopping is a per-lane ``live`` mask (mirroring the masked-loss
  trick in ``distill.make_loss``): converged lanes keep stepping on
  frozen params so the batch shape stays static, and the outer scan's
  ``lax.cond(any(live), ...)`` skips whole epochs once every lane has
  stopped.

The shared batch size is clamped to the SMALLEST lane's train split so
every lane runs at least one step per epoch.  Every lane draws the
IDENTICAL device permutation as ``train`` (same fold_in key); when
additionally ``batch_size <= min_i n_tr_i`` (no cross-lane clamping), a
lane's results match the sequential path to float tolerance — the parity
tests in ``tests/test_train_many.py`` and ``tests/test_replicas.py`` pin
this.

Mesh sharding (``train_lanes(..., mesh=...)``)
----------------------------------------------
Lanes are embarrassingly parallel, so the lane axis shards across devices
by *computation following data*: pass a mesh from
``repro.launch.mesh.make_lane_mesh`` (axes ``("lane", "data")``) and every
stacked input is ``device_put`` with a ``NamedSharding`` resolved through
the logical-axis policy (``repro.sharding.policy`` — lane axis ->
``"lane"``, rows -> ``"dp"`` when ``shard_rows=True``).  The SAME jitted
engine then runs device-parallel — jit specializes on the input shardings,
the computation is bitwise the computation the unsharded path runs, so
parity is exact.  The lane count is padded up to a multiple of the mesh's
lane-axis size with dead lanes (``live=False``, zero step budget) that are
stripped from the results; row sharding silently drops to replicated on
dims the mesh does not divide (``policy._divisible``), because padding
rows would change the device permutation and break parity.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import padding
from repro.core.spans import span
from repro.optim.adam import paper_adam


@dataclass
class TrainResult:
    params: dict
    epochs_run: int
    steps_run: int
    train_loss: list
    val_loss: list


@dataclass
class LaneSpec:
    """One lane's training problem for ``train_lanes``: unpadded init
    params, unpadded row-aligned data dict, and the lane's PRNG seed
    (drives both the host train/val split and the device epoch perms,
    exactly as the same seed would in ``train``).  A lane is any
    independent instance — a party, a seed replicate, a fold."""
    params: dict
    data: dict
    seed: int = 0


PartySpec = LaneSpec     # the K-party special case, kept by its PR-2 name

# the pre-dedup names, kept so downstream code reads either way
_pad_to = padding.pad_to
_pad_stack = padding.pad_stack


# ---------------------------------------------------------------------------
# engine cache
# ---------------------------------------------------------------------------

_ENGINE_CACHE: dict = {}
_ENGINE_CACHE_MAX = 64   # FIFO-evict beyond this: entries strong-reference
                         # the loss fn and its compiled executables


def loss_cache_key(loss_fn):
    """Semantic identity of a loss: closures tagged with ``cache_key``
    (e.g. ``distill.make_loss``) share one compiled engine across instances;
    plain module-level functions key on their own identity.  Untagged
    per-call closures each get their own engine (a full re-trace per
    ``train`` call) — tag them if they are built in a loop."""
    return getattr(loss_fn, "cache_key", loss_fn)


def _cached_engine(tag: str, loss_fn: Callable, lr: float, builder):
    key = (tag, loss_cache_key(loss_fn), float(lr))
    engine = _ENGINE_CACHE.get(key)
    if engine is None:
        while len(_ENGINE_CACHE) >= _ENGINE_CACHE_MAX:
            _ENGINE_CACHE.pop(next(iter(_ENGINE_CACHE)))
        engine = builder(loss_fn, float(lr))
        _ENGINE_CACHE[key] = engine
    return engine


# ---------------------------------------------------------------------------
# per-epoch engines (the epochwise parity oracle + callback path)
# ---------------------------------------------------------------------------

def _build_engine(loss_fn: Callable, lr: float):
    opt = paper_adam(lr)

    @partial(jax.jit, static_argnames=("n_batches", "batch_size"),
             donate_argnums=(0, 1))
    def run_epoch(params, opt_state, key, tr, val, *, n_batches, batch_size):
        n_tr = jax.tree.leaves(tr)[0].shape[0]
        perm = jax.random.permutation(key, n_tr)
        idx = perm[: n_batches * batch_size].reshape(n_batches, batch_size)

        def step(carry, bidx):
            p, s = carry
            batch = {k: v[bidx] for k, v in tr.items()}
            loss, grads = jax.value_and_grad(loss_fn)(p, batch)
            p, s, _ = opt.update(grads, s, p)
            return (p, s), loss

        (params, opt_state), losses = jax.lax.scan(step, (params, opt_state),
                                                   idx)
        return params, opt_state, jnp.mean(losses), loss_fn(params, val)

    return run_epoch


def get_engine(loss_fn: Callable, *, lr: float = 1e-3):
    """Jitted epoch runner for ``loss_fn``, cached on (loss identity, lr)."""
    return _cached_engine("train", loss_fn, lr, _build_engine)


def get_lanes_engine(loss_fn: Callable, *, lr: float = 1e-3):
    """Jitted vmapped replica-lane epoch runner, cached like
    ``get_engine``."""
    return _cached_engine("train_many", loss_fn, lr, _build_many_engine)


get_many_engine = get_lanes_engine   # pre-lane-engine name


# ---------------------------------------------------------------------------
# fused whole-fit engines (outer epoch scan, one host sync per fit)
# ---------------------------------------------------------------------------

def _build_fit_engine(loss_fn: Callable, lr: float):
    opt = paper_adam(lr)

    @partial(jax.jit, static_argnames=("n_batches", "batch_size",
                                       "max_epochs", "patience"))
    def run_fit(params, opt_state, base_key, tr, val, *, n_batches,
                batch_size, max_epochs, patience):
        n_tr = jax.tree.leaves(tr)[0].shape[0]

        def epoch_body(carry, epoch):
            p, s, best_p, best_v, since, live, epochs = carry
            key = jax.random.fold_in(base_key, epoch)
            perm = jax.random.permutation(key, n_tr)
            idx = perm[: n_batches * batch_size].reshape(n_batches,
                                                         batch_size)

            def step(c, bidx):
                p_, s_ = c
                batch = {k: v[bidx] for k, v in tr.items()}
                loss, grads = jax.value_and_grad(loss_fn)(p_, batch)
                p_, s_, _ = opt.update(grads, s_, p_)
                return (p_, s_), loss

            (p, s), losses = jax.lax.scan(step, (p, s), idx)
            tl = jnp.mean(losses)
            vl = loss_fn(p, val)
            # the epochwise loop's host bookkeeping, as traced values
            improved = vl < best_v - 1e-6
            best_p = jax.tree.map(lambda b, q: jnp.where(improved, q, b),
                                  best_p, p)
            best_v = jnp.where(improved, vl, best_v)
            since = jnp.where(improved, 0, since + 1)
            live = improved | (since < patience)
            return (p, s, best_p, best_v, since, live, epochs + 1), (tl, vl)

        def epoch_step(carry, epoch):
            dead = lambda c: (c, (jnp.zeros((), jnp.float32),
                                  jnp.zeros((), jnp.float32)))
            return jax.lax.cond(carry[5],
                                lambda c: epoch_body(c, epoch), dead, carry)

        init = (params, opt_state, params,
                jnp.asarray(jnp.inf, jnp.float32),
                jnp.asarray(0, jnp.int32), jnp.asarray(True, jnp.bool_),
                jnp.asarray(0, jnp.int32))
        (_, _, best_p, _, _, _, epochs), (tls, vls) = jax.lax.scan(
            epoch_step, init, jnp.arange(max_epochs, dtype=jnp.int32))
        return best_p, epochs, tls, vls

    return run_fit


def get_fit_engine(loss_fn: Callable, *, lr: float = 1e-3):
    """Jitted whole-fit runner (scan-of-scans), cached like
    ``get_engine``."""
    return _cached_engine("fit", loss_fn, lr, _build_fit_engine)


def _build_lanes_fit_engine(loss_fn: Callable, lr: float):
    opt = paper_adam(lr)

    @partial(jax.jit, static_argnames=("n_batches", "batch_size",
                                       "max_epochs", "patience", "uniform"))
    def run_fit_k(params, opt_state, base_keys, tr, val, nb, live0, *,
                  n_batches, batch_size, max_epochs, patience,
                  uniform=False):
        L = base_keys.shape[0]

        def lane_epoch(p, s, key, live_p, tr_p, val_p, nb_p):
            # every lane of the stack has these rows (``_prep_lanes``), so
            # this is the solo engine's permutation and its mini-batches
            perm = jax.random.permutation(key, tr_p["x"].shape[0])
            idx = perm[: n_batches * batch_size].reshape(n_batches,
                                                         batch_size)

            def step(carry, xs):
                p_, s_ = carry
                i, bidx = xs
                batch = {k: v[bidx] for k, v in tr_p.items() if k != "mask"}
                batch["mask"] = tr_p["mask"]
                batch["row_w"] = jnp.ones((batch_size,), jnp.float32)
                loss, grads = jax.value_and_grad(loss_fn)(p_, batch)
                p2, s2, _ = opt.update(grads, s_, p_)
                if uniform:
                    # every live lane runs every step (nb_p == n_batches for
                    # all lanes — caller-checked), so the freeze collapses
                    # to ONE live-select per epoch below instead of a
                    # params+opt tree select per step
                    return (p2, s2), loss
                # freeze past this lane's own step budget or after its
                # early stop — the masked-select twin of distill.make_loss
                on = live_p & (i < nb_p)
                sel = lambda a, b: jnp.where(on, a, b)
                return ((jax.tree.map(sel, p2, p_),
                         jax.tree.map(sel, s2, s_)),
                        jnp.where(on, loss, 0.0))

            (p2, s2), losses = jax.lax.scan(step, (p, s),
                                            (jnp.arange(n_batches, dtype=jnp.int32), idx))
            if uniform:
                sel = lambda a, b: jnp.where(live_p, a, b)
                p = jax.tree.map(sel, p2, p)
                s = jax.tree.map(sel, s2, s)
                tl = jnp.where(live_p,
                               jnp.sum(losses) / jnp.maximum(nb_p, 1), 0.0)
            else:
                p, s = p2, s2
                tl = jnp.sum(losses) / jnp.maximum(nb_p, 1)
            return p, s, tl, loss_fn(p, val_p)

        def live_epoch(carry, epoch):
            p, s, best_p, best_v, since, live, epochs = carry
            keys = jax.vmap(jax.random.fold_in, (0, None))(base_keys, epoch)
            p, s, tl, vl = jax.vmap(lane_epoch)(p, s, keys, live, tr, val,
                                                nb)
            epochs = epochs + live.astype(jnp.int32)
            # the epochwise lanes loop's host bookkeeping, as traced values
            improved = live & (vl < best_v - 1e-6)
            best_p = jax.tree.map(
                lambda b, q: jnp.where(
                    improved.reshape((L,) + (1,) * (q.ndim - 1)), q, b),
                best_p, p)
            best_v = jnp.where(improved, vl, best_v)
            since = jnp.where(improved, 0, since + 1)
            live = live & (since < patience)
            return (p, s, best_p, best_v, since, live, epochs), (tl, vl)

        def epoch_step(carry, epoch):
            # the cond sits OUTSIDE the per-lane vmap: once every lane has
            # stopped, remaining epochs cost one predicate each
            dead = lambda c: (c, (jnp.zeros((L,), jnp.float32),
                                  jnp.zeros((L,), jnp.float32)))
            return jax.lax.cond(jnp.any(carry[5]),
                                lambda c: live_epoch(c, epoch), dead, carry)

        init = (params, opt_state, params,
                jnp.full((L,), jnp.inf, jnp.float32),
                jnp.zeros((L,), jnp.int32), live0,
                jnp.zeros((L,), jnp.int32))
        (_, _, best_p, _, _, _, epochs), (tls, vls) = jax.lax.scan(
            epoch_step, init, jnp.arange(max_epochs, dtype=jnp.int32))
        return best_p, epochs, tls, vls

    return run_fit_k


def get_lanes_fit_engine(loss_fn: Callable, *, lr: float = 1e-3):
    """Jitted vmapped whole-fit lane runner, cached like ``get_engine``."""
    return _cached_engine("lanes_fit", loss_fn, lr, _build_lanes_fit_engine)


# ---------------------------------------------------------------------------
# single-instance training
# ---------------------------------------------------------------------------

def _prep_single(data: dict, *, seed: int, val_frac: float, batch_size: int):
    """Host-side train/val split + device upload shared by the fused and
    epochwise paths (identical RandomState split either way)."""
    n = len(next(iter(data.values())))
    split = np.random.RandomState(seed).permutation(n)
    n_val = max(int(n * val_frac), 1)
    val_idx, tr_idx = split[:n_val], split[n_val:]
    # jnp.asarray is a no-op for arrays already on device (an earlier
    # stage's encoder output), one upload for host arrays; the split
    # itself is a device gather either way
    dev = {k: jnp.asarray(v) for k, v in data.items()}
    val = {k: v[val_idx] for k, v in dev.items()}
    tr = {k: v[tr_idx] for k, v in dev.items()}
    n_tr = len(tr_idx)
    bs = max(min(batch_size, n_tr), 1)
    return tr, val, bs, n_tr // bs


def train(params, data: dict, loss_fn: Callable, *, batch_size: int = 128,
          max_epochs: int = 200, patience: int = 10, lr: float = 1e-3,
          val_frac: float = 0.1, seed: int = 0,
          epoch_callback: Optional[Callable] = None) -> TrainResult:
    """data: dict of equal-length arrays (row-aligned). loss_fn(params, batch).

    Runs the whole fit as one jitted scan-of-scans (module docstring) with
    a single host sync.  ``epoch_callback`` callers are routed to
    ``train_epochwise`` — per-epoch host params are exactly the sync the
    fused engine removes."""
    if epoch_callback is not None:
        return train_epochwise(params, data, loss_fn, batch_size=batch_size,
                               max_epochs=max_epochs, patience=patience,
                               lr=lr, val_frac=val_frac, seed=seed,
                               epoch_callback=epoch_callback)
    tr, val, bs, n_batches = _prep_single(data, seed=seed, val_frac=val_frac,
                                          batch_size=batch_size)
    engine = get_fit_engine(loss_fn, lr=lr)
    best_p, epochs, tls, vls = engine(
        params, paper_adam(lr).init(params), jax.random.PRNGKey(seed), tr,
        val, n_batches=n_batches, batch_size=bs, max_epochs=max_epochs,
        patience=patience)
    # the single host sync of the fit
    epochs, tls, vls = jax.device_get((epochs, tls, vls))
    epochs = int(epochs)
    return TrainResult(best_p, epochs, epochs * n_batches,
                       [float(t) for t in tls[:epochs]],
                       [float(v) for v in vls[:epochs]])


def train_epochwise(params, data: dict, loss_fn: Callable, *,
                    batch_size: int = 128, max_epochs: int = 200,
                    patience: int = 10, lr: float = 1e-3,
                    val_frac: float = 0.1, seed: int = 0,
                    epoch_callback: Optional[Callable] = None) -> TrainResult:
    """The pre-fusion per-epoch loop: one jitted epoch per dispatch, one
    host sync per epoch.  Kept as the fused engine's parity oracle and as
    the ``epoch_callback`` path (callbacks get a defensive copy of the
    params each epoch — the engine donates its own buffers onward)."""
    tr, val, bs, n_batches = _prep_single(data, seed=seed, val_frac=val_frac,
                                          batch_size=batch_size)
    # fresh buffers: the engine donates its params/opt args, so the loop must
    # own them (never the caller's arrays, never the best-so-far snapshot)
    params = jax.tree.map(jnp.array, params)
    best_params = jax.tree.map(jnp.copy, params)
    engine = get_engine(loss_fn, lr=lr)
    opt_state = paper_adam(lr).init(params)
    base_key = jax.random.PRNGKey(seed)

    best_val, since_best = np.inf, 0
    tl_hist, vl_hist, steps, epochs = [], [], 0, 0
    for epoch in range(max_epochs):
        epochs = epoch + 1
        params, opt_state, tl, vl = engine(
            params, opt_state, jax.random.fold_in(base_key, epoch), tr, val,
            n_batches=n_batches, batch_size=bs)
        tl, vl = float(tl), float(vl)   # the single host sync of the epoch
        steps += n_batches
        tl_hist.append(tl)
        vl_hist.append(vl)
        if epoch_callback is not None:
            # defensive copy: the engine donates ``params`` into the next
            # epoch, so a stashed reference would be use-after-donate
            epoch_callback(epoch, jax.tree.map(jnp.copy, params), tl, vl)
        if vl < best_val - 1e-6:
            best_val, since_best = vl, 0
            best_params = jax.tree.map(jnp.copy, params)
        else:
            since_best += 1
            if since_best >= patience:
                break
    return TrainResult(best_params, epochs, steps, tl_hist, vl_hist)


# ---------------------------------------------------------------------------
# replica-lane training: all lanes' fits as ONE vmapped scan-of-scans
# ---------------------------------------------------------------------------

# all lanes' epoch keys in one dispatch; module-scoped so the trivial
# trace compiles once per process, not once per train_lanes call
_FOLD_KEYS = jax.jit(jax.vmap(jax.random.fold_in, (0, None)))


def _build_many_engine(loss_fn: Callable, lr: float):
    opt = paper_adam(lr)

    @partial(jax.jit, static_argnames=("n_batches", "batch_size"),
             donate_argnums=(0, 1))
    def run_epoch_k(params, opt_state, keys, tr, val, nb, live, *,
                    n_batches, batch_size):
        def one(p, s, key, tr_p, val_p, nb_p, live_p):
            # every lane of the stack has these rows (``_prep_lanes``), so
            # this is the solo engine's permutation and its mini-batches
            perm = jax.random.permutation(key, tr_p["x"].shape[0])
            idx = perm[: n_batches * batch_size].reshape(n_batches,
                                                         batch_size)

            def step(carry, xs):
                p, s = carry
                i, bidx = xs
                batch = {k: v[bidx] for k, v in tr_p.items() if k != "mask"}
                batch["mask"] = tr_p["mask"]
                batch["row_w"] = jnp.ones((batch_size,), jnp.float32)
                loss, grads = jax.value_and_grad(loss_fn)(p, batch)
                p2, s2, _ = opt.update(grads, s, p)
                # freeze past this lane's own step budget or after its
                # early stop — the masked-select twin of distill.make_loss
                on = live_p & (i < nb_p)
                sel = lambda a, b: jnp.where(on, a, b)
                return ((jax.tree.map(sel, p2, p), jax.tree.map(sel, s2, s)),
                        jnp.where(on, loss, 0.0))

            (p, s), losses = jax.lax.scan(step, (p, s),
                                          (jnp.arange(n_batches, dtype=jnp.int32), idx))
            tl = jnp.sum(losses) / jnp.maximum(nb_p, 1)
            return p, s, tl, loss_fn(p, val_p)

        return jax.vmap(one)(params, opt_state, keys, tr, val, nb, live)

    return run_epoch_k


def _prep_lanes(specs: Sequence[LaneSpec], *, batch_size: int,
                val_frac: float, lr: float):
    """Per-lane host split + padded-stack upload shared by the fused and
    epochwise lane paths.  The lanes of one stack must share their train
    row count: the engines draw each epoch's batches from a permutation of
    the stack's rows, which would reach padded rows of a shorter lane."""
    K = len(specs)
    assert K >= 1
    for sp in specs:
        if "x" not in sp.data:
            raise ValueError("train_lanes: every LaneSpec.data needs an "
                             "'x' feature array (sizes the rows and the "
                             "real-feature mask)")
    rows = [len(next(iter(sp.data.values()))) for sp in specs]
    n_tr = {n - max(int(n * val_frac), 1) for n in rows}
    if len(n_tr) > 1:
        raise ValueError(f"train_lanes: one stack's lanes have unequal "
                         f"train rows {sorted(n_tr)}; stack only lanes of "
                         f"one _lane_groups group")
    n_tr = n_tr.pop()

    # --- per-lane split: host-side indices, device-side gather ------------
    tr_list, val_list = [], []
    for sp, n in zip(specs, rows):
        split = np.random.RandomState(sp.seed).permutation(n)
        n_val = n - n_tr
        vi, ti = split[:n_val], split[n_val:]
        dev = {k: jnp.asarray(v) for k, v in sp.data.items()}
        val_list.append({k: v[vi] for k, v in dev.items()})
        tr_list.append({k: v[ti] for k, v in dev.items()})
    bs = max(min(batch_size, n_tr), 1)
    nb = np.full((K,), n_tr // bs)        # per-lane step budget per epoch

    for t, v in zip(tr_list, val_list):
        t["mask"] = jnp.ones((t["x"].shape[1],), jnp.float32)
        v["mask"] = t["mask"]
        v["row_w"] = jnp.ones((v["x"].shape[0],), jnp.float32)

    # --- padded-stack, built on device (no host round-trip) ---------------
    tr = padding.pad_stack(tr_list)
    val = padding.pad_stack(val_list)
    params = padding.pad_stack([sp.params for sp in specs])
    opt_state = paper_adam(lr).init(params)
    opt_state = opt_state._replace(step=jnp.zeros((K,), jnp.int32))
    base_keys = jnp.stack([jax.random.PRNGKey(sp.seed) for sp in specs])
    return params, opt_state, base_keys, tr, val, nb, bs


def _shard_lanes(mesh, params, opt_state, base_keys, tr, val, *lane_vecs,
                 shard_rows: bool):
    """Pad the lane axis to a mesh multiple with dead lanes and
    ``device_put`` every stacked input, and each per-lane vector in
    ``lane_vecs`` (step budgets, live flags), with policy-resolved
    shardings.  Returns the inputs device-parallel in the order given; the
    engine itself is unchanged (computation follows data)."""
    from jax.sharding import NamedSharding

    from repro.sharding import policy

    if "lane" not in mesh.axis_names:
        raise ValueError(
            f"train_lanes: mesh axes {tuple(mesh.axis_names)} lack the "
            "'lane' axis — build the mesh with "
            "repro.launch.mesh.make_lane_mesh")
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    K = int(base_keys.shape[0])
    Lp = -(-K // sizes["lane"]) * sizes["lane"]

    def grow(a):
        # dead lanes: zero params/data, live=False, zero step budget
        return padding.pad_to(a, (Lp,) + a.shape[1:])

    (params, opt_state, base_keys, tr, val, lane_vecs) = jax.tree.map(
        grow, (params, opt_state, base_keys, tr, val, lane_vecs))

    def put(a, *, rows=False):
        axes = ("lane",)
        if rows and a.ndim > 1 and "data" in mesh.axis_names:
            axes = ("lane", "dp")
        axes = axes + (None,) * (a.ndim - len(axes))
        spec = policy._divisible(a.shape,
                                 policy.resolve(axes, mesh.axis_names), mesh)
        return jax.device_put(a, NamedSharding(mesh, spec))

    params = jax.tree.map(put, params)
    opt_state = jax.tree.map(put, opt_state)
    base_keys = put(base_keys)
    lane_vecs = tuple(put(a) for a in lane_vecs)
    # "mask" is per-feature, not per-row; everything else shards rows
    tr = {k: put(v, rows=shard_rows and k != "mask") for k, v in tr.items()}
    val = {k: put(v, rows=shard_rows and k != "mask") for k, v in val.items()}
    return (params, opt_state, base_keys, tr, val) + lane_vecs


# every live lane's leaves out of a shape group's stack in ONE dispatch,
# not two eager programs a leaf a lane; module-scoped like _FOLD_KEYS, so
# it traces once per leaf shapes, lane count and sharding.  Dead
# mesh-padding lanes (``i >= k``) are never emitted.  On a mesh the
# outputs come back replicated over it, as eager ``l[i]`` does: callers
# concatenate lanes' encodings, which arrays on different chips would break.
@partial(jax.jit, static_argnums=1)
def _unstack_lanes(leaves, k):
    """Lanes ``0 .. k-1`` of every stacked leaf, lane by lane."""
    return tuple(tuple(l[i] for l in leaves) for i in range(k))


def _unstack_lane_params(specs, best_params):
    """One params tree per spec out of the group's stacked best-val params.
    A group's lanes share every shape (``_lane_groups``), so the stack
    holds no padding to strip."""
    treedef = jax.tree.structure(specs[0].params)
    lanes = _unstack_lanes(tuple(jax.tree.leaves(best_params)),
                           len(specs))
    return [jax.tree.unflatten(treedef, pl) for pl in lanes]


def _lane_groups(specs: Sequence[LaneSpec]):
    """Partition lane indices by (data shapes, param shapes) signature.
    Lanes in one group pad-stack with ZERO padding waste — mixed-shape
    fleets (e.g. one active + K passive parties) otherwise pay the max
    shape for every lane (the Table-3 active g1 is ~7x smaller than the
    passive g1 it was padded to)."""
    groups: dict = {}
    order = []
    for i, sp in enumerate(specs):
        dsig = tuple(sorted((k, tuple(np.shape(v)))
                            for k, v in sp.data.items()))
        psig = (jax.tree.structure(sp.params),
                tuple(tuple(np.shape(l))
                      for l in jax.tree.leaves(sp.params)))
        key = (dsig, psig)
        if key not in groups:
            groups[key] = []
            order.append(key)
        groups[key].append(i)
    return [groups[k] for k in order]


def train_lanes(specs: Sequence[LaneSpec], loss_fn: Callable, *,
                batch_size: int = 128, max_epochs: int = 200,
                patience: int = 10, lr: float = 1e-3,
                val_frac: float = 0.1, mesh=None,
                shard_rows: bool = False) -> List[TrainResult]:
    """Train L independent lanes as one vmapped scan-of-scans — one upload,
    one compile per shape group, ONE host sync per fit for all lanes
    (module docstring: padded-stack layout, per-lane early-stop mask, mesh
    sharding).

    Lanes are partitioned into shape groups (``_lane_groups``) so
    mixed-shape fleets never pad small lanes up to the largest party;
    the global batch-size clamp (min over ALL lanes' train rows) is
    computed before grouping, so every lane draws the same mini-batches
    as the ungrouped engine — parity is exact, only padding FLOPs are
    removed.  Groups whose lanes all share one step budget additionally
    run the ``uniform`` engine fast path (epoch-level live select instead
    of a per-step params+opt tree select).

    Every lane's ``data`` must carry its feature array under the ``"x"``
    key — the engine sizes rows and the real-feature ``mask`` from it; any
    other row-aligned keys are padded too but only ``"x"`` is masked.
    When lane shapes differ within a group (padding present) ``loss_fn``
    must consume the ``mask`` (real-feature columns) and ``row_w``
    (real-row weights) entries the engine adds to every batch — use
    ``autoencoder.masked_recon_loss`` for reconstruction workloads; lanes
    of identical shape (seed replicas) may use any plain loss, the extra
    keys are inert.

    ``mesh`` (from ``repro.launch.mesh.make_lane_mesh``, axes
    ``("lane", "data")``) shards the lane axis across devices;
    ``shard_rows=True`` additionally shards each lane's rows across the
    ``data`` axis (the large-row regime).  Sharded or not, the same jitted
    engine runs the same computation — parity is exact.

    Returns one ``TrainResult`` per lane: its best-val params, unstacked
    from the group in one dispatch, and histories truncated at that lane's
    stop epoch."""
    K = len(specs)
    # global batch-size clamp (the ungrouped engine's bs): computed over
    # ALL lanes so per-group _prep_lanes clamps to exactly this value
    # (global min <= every group min)
    n_tr_all = []
    for sp in specs:
        n = len(next(iter(sp.data.values())))
        n_tr_all.append(n - max(int(n * val_frac), 1))
    global_bs = max(min(batch_size, min(n_tr_all)), 1)

    engine = get_lanes_fit_engine(loss_fn, lr=lr)
    launched = []                 # (idxs, gspecs, best_params, nb)
    host_parts = []               # (epochs, tls, vls) per group, in-flight
    for idxs in _lane_groups(specs):
        gspecs = [specs[i] for i in idxs]
        with span("lanes.prep"):
            (params, opt_state, base_keys, tr, val, nb,
             bs) = _prep_lanes(gspecs, batch_size=global_bs,
                               val_frac=val_frac, lr=lr)
            n_batches = int(nb.max())
            uniform = bool((nb == nb[0]).all())
            nb_dev = jnp.asarray(nb, jnp.int32)
            live0 = jnp.ones((len(idxs),), bool)
        if mesh is not None:
            with span("lanes.shard"):
                (params, opt_state, base_keys, tr, val, nb_dev,
                 live0) = _shard_lanes(mesh, params, opt_state, base_keys,
                                       tr, val, nb_dev, live0,
                                       shard_rows=shard_rows)
        with span("lanes.launch"):
            best_params, epochs, tls, vls = engine(
                params, opt_state, base_keys, tr, val, nb_dev,
                live0, n_batches=n_batches, batch_size=bs,
                max_epochs=max_epochs, patience=patience, uniform=uniform)
        launched.append((idxs, gspecs, best_params, nb))
        host_parts.append((epochs, tls, vls))
    # the single host sync of the fit, coalesced over every shape group
    # (dead padding lanes sliced away)
    with span("lanes.sync"):
        host_parts = jax.device_get(host_parts)

    results: List[TrainResult] = [None] * K  # type: ignore[list-item]
    with span("lanes.unstack"):
        for (idxs, gspecs, best_params, nb), parts in zip(launched,
                                                          host_parts):
            epochs, tls, vls = parts
            lane_params = _unstack_lane_params(gspecs, best_params)
            for j, i in enumerate(idxs):
                e = int(epochs[j])
                results[i] = TrainResult(lane_params[j], e, e * int(nb[j]),
                                         [float(t) for t in tls[:e, j]],
                                         [float(v) for v in vls[:e, j]])
    return results


def train_lanes_epochwise(specs: Sequence[LaneSpec], loss_fn: Callable, *,
                          batch_size: int = 128, max_epochs: int = 200,
                          patience: int = 10, lr: float = 1e-3,
                          val_frac: float = 0.1) -> List[TrainResult]:
    """The pre-fusion lane loop: one vmapped epoch per dispatch, one host
    sync per epoch for the early-stop bookkeeping.  Kept as the fused lane
    engine's live parity oracle (``tests/test_training_engine.py``) —
    it shape-groups lanes exactly like ``train_lanes`` (same global
    batch-size clamp, same per-group padding) so the two paths draw
    identical device permutations."""
    n_tr_all = []
    for sp in specs:
        n = len(next(iter(sp.data.values())))
        n_tr_all.append(n - max(int(n * val_frac), 1))
    global_bs = max(min(batch_size, min(n_tr_all)), 1)

    results: List[TrainResult] = [None] * len(specs)  # type: ignore
    for idxs in _lane_groups(specs):
        gspecs = [specs[i] for i in idxs]
        for i, r in zip(idxs, _train_lanes_epochwise_group(
                gspecs, loss_fn, batch_size=global_bs,
                max_epochs=max_epochs, patience=patience, lr=lr,
                val_frac=val_frac)):
            results[i] = r
    return results


def _train_lanes_epochwise_group(specs, loss_fn, *, batch_size, max_epochs,
                                 patience, lr, val_frac):
    K = len(specs)
    (params, opt_state, base_keys, tr, val, nb,
     bs) = _prep_lanes(specs, batch_size=batch_size, val_frac=val_frac,
                       lr=lr)
    n_batches = int(nb.max())
    best_params = jax.tree.map(jnp.copy, params)
    engine = get_lanes_engine(loss_fn, lr=lr)
    nb_dev = jnp.asarray(nb, jnp.int32)

    best_val = np.full((K,), np.inf)
    since = np.zeros((K,), np.int64)
    live = np.ones((K,), bool)
    epochs_run = np.zeros((K,), np.int64)
    tl_hist = [[] for _ in range(K)]
    vl_hist = [[] for _ in range(K)]

    for epoch in range(max_epochs):
        keys = _FOLD_KEYS(base_keys, epoch)  # all lanes' keys, one dispatch
        params, opt_state, tl, vl = engine(
            params, opt_state, keys, tr, val, nb_dev,
            jnp.asarray(live), n_batches=n_batches, batch_size=bs)
        tl = np.asarray(tl)
        vl = np.asarray(vl)               # the single host sync of the epoch
        epochs_run[live] += 1
        for i in range(K):
            if live[i]:
                tl_hist[i].append(float(tl[i]))
                vl_hist[i].append(float(vl[i]))
        improved = live & (vl < best_val - 1e-6)
        if improved.any():
            sel = jnp.asarray(improved)
            best_params = jax.tree.map(
                lambda b, p: jnp.where(
                    sel.reshape((K,) + (1,) * (p.ndim - 1)), p, b),
                best_params, params)
            best_val = np.where(improved, vl, best_val)
        since = np.where(improved, 0, since + 1)
        live = live & (since < patience)
        if not live.any():
            break

    lane_params = _unstack_lane_params(specs, best_params)
    return [TrainResult(lane_params[i], int(epochs_run[i]),
                        int(epochs_run[i] * nb[i]), tl_hist[i], vl_hist[i])
            for i in range(K)]


train_many = train_lanes     # the K-party special case, by its PR-2 name
