"""The lane engine: every fit of the tabular APC-VFL stack runs as a lane
of one vmapped, device-resident scan.

Optimization is the paper's Adam (Kingma & Ba defaults, Appendix B) via
:mod:`repro.optim.adam`, <=200 epochs, early stopping on a 10% validation
split with patience 10.

Lanes
-----
A *lane* is one independent fit (``LaneSpec``: init params, a dict of
equal-length row-aligned arrays, a seed): a federated party's g1 stage, a
seed replicate of a stage, a CV fold.  ``train_lanes`` fits L lanes
together; ``train`` is a lane of one.  Each lane

1. splits its rows into train/val ONCE on the host
   (``np.random.RandomState(seed)``) and gathers both sides on device;
2. draws each epoch's row permutation on device with ``jax.random``
   (``fold_in(PRNGKey(seed), epoch)``) and slices ``n_batches = n_tr //
   bs`` static-shape batches straight from it, dropping the remainder;
3. keeps its own Adam state and early-stop state (best-val params, best
   val loss, epochs since the best, a ``live`` flag, epochs run).

``_lane_groups`` partitions lanes by exact data and param shapes, and
each group is stacked on a leading lane axis (``core.padding``; a group
holds no padding).  The batch size is clamped once, to the smallest train
split over ALL lanes, so a lane draws the same batches in any group.
Where a lane's data holds an ``"x"`` array, every batch also carries an
all-ones ``mask`` (feature columns) and ``row_w`` (rows), which
``autoencoder.masked_recon_loss`` and ``distill.make_lanes_loss``
consume; other losses ignore them.

One epoch, two programs
-----------------------
``_build_lane_engine`` writes the epoch once: the vmapped pass of every
lane over its batches (a lane that has stopped keeps its params; its
steps still run, so shapes stay static), then the early-stop bookkeeping
on the carry as traced values.  It compiles two programs from it, cached
together per (loss identity, lr) in ``_ENGINE_CACHE``:

* ``run_fit_k`` (``get_lanes_fit_engine``) scans the epoch over
  ``max_epochs`` under ``lax.cond(any(live))``, so epochs after the last
  lane stops cost one predicate each.  ``train_lanes`` launches it once a
  shape group and syncs ONCE for all groups.
* ``run_epoch_k`` (``get_lanes_engine``) runs one epoch with its carry
  donated.  The host loop (``train_lanes_epochwise``, and ``train`` with
  an ``epoch_callback``) drives it, syncs ``(tl, vl, live)`` once an
  epoch, and stops when no lane is live.  Callbacks need params on the
  host every epoch, which is the sync the scan removes.

Both run the same epoch, so they agree exactly.  The stored-trace oracle
(``tests/data/train_trace.json``) pins the split, permutation, loss and
optimizer math.  Loss closures built by ``distill.make_loss`` and friends
carry a semantic ``cache_key``, so repeated stages reuse one engine.

Mesh sharding
-------------
``train_lanes(..., mesh=...)`` shards the lane axis across devices by
*computation following data*: with a mesh from
``repro.launch.mesh.make_lane_mesh`` (axes ``("lane", "data")``) every
stacked input is ``device_put`` with a ``NamedSharding`` resolved through
the logical-axis policy (``repro.sharding.policy``: lane axis ->
``"lane"``, rows -> ``"dp"`` when ``shard_rows=True``), and the same
program runs device-parallel, bit for bit the unsharded computation.  The
lane count is padded to a multiple of the mesh's lane axis with dead
lanes (``live=False``) that are stripped from the results; row sharding
drops to replicated on dims the mesh does not divide
(``policy._divisible``), because padding rows would change the
permutation.
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
    (drives both the host train/val split and the device epoch perms).
    A lane is any independent instance — a party, a seed replicate, a
    fold."""
    params: dict
    data: dict
    seed: int = 0


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


def _lane_engine(loss_fn: Callable, lr: float):
    """``(run_fit_k, run_epoch_k)`` for ``loss_fn``, built once per
    (loss identity, lr)."""
    key = (loss_cache_key(loss_fn), float(lr))
    engine = _ENGINE_CACHE.get(key)
    if engine is None:
        while len(_ENGINE_CACHE) >= _ENGINE_CACHE_MAX:
            _ENGINE_CACHE.pop(next(iter(_ENGINE_CACHE)))
        engine = _build_lane_engine(loss_fn, float(lr))
        _ENGINE_CACHE[key] = engine
    return engine


def get_lanes_fit_engine(loss_fn: Callable, *, lr: float = 1e-3):
    """The whole-fit program ``run_fit_k`` for ``loss_fn``."""
    return _lane_engine(loss_fn, lr)[0]


def get_lanes_engine(loss_fn: Callable, *, lr: float = 1e-3):
    """The one-epoch program ``run_epoch_k`` for ``loss_fn``; it donates
    its carry (argument 0)."""
    return _lane_engine(loss_fn, lr)[1]


# ---------------------------------------------------------------------------
# the epoch, and the two programs built from it
# ---------------------------------------------------------------------------

def _carry0(params, opt_state, best_p, live0):
    """The early-stop carry before the first epoch."""
    L = live0.shape[0]
    return (params, opt_state, best_p, jnp.full((L,), jnp.inf, jnp.float32),
            jnp.zeros((L,), jnp.int32), live0, jnp.zeros((L,), jnp.int32))


def _build_lane_engine(loss_fn: Callable, lr: float):
    opt = paper_adam(lr)

    def lane_pass(p, s, key, live, tr, val, *, n_batches, batch_size):
        # every lane of the stack has these rows (``_prep_lanes``), so its
        # batches are slices of its own permutation
        n_tr = next(v for k, v in tr.items() if k != "mask").shape[0]
        perm = jax.random.permutation(key, n_tr)
        idx = perm[: n_batches * batch_size].reshape(n_batches, batch_size)

        def step(carry, bidx):
            p_, s_ = carry
            batch = {k: v[bidx] for k, v in tr.items() if k != "mask"}
            if "mask" in tr:
                batch["mask"] = tr["mask"]
                batch["row_w"] = jnp.ones((batch_size,), jnp.float32)
            loss, grads = jax.value_and_grad(loss_fn)(p_, batch)
            p_, s_, _ = opt.update(grads, s_, p_)
            return (p_, s_), loss

        (p2, s2), losses = jax.lax.scan(step, (p, s), idx)
        # a stopped lane keeps its params and Adam state
        keep = lambda a, b: jnp.where(live, a, b)
        p = jax.tree.map(keep, p2, p)
        s = jax.tree.map(keep, s2, s)
        return p, s, jnp.where(live, jnp.mean(losses), 0.0), loss_fn(p, val)

    def epoch(carry, e, base_keys, tr, val, *, n_batches, batch_size,
              patience):
        p, s, best_p, best_v, since, live, epochs = carry
        keys = jax.vmap(jax.random.fold_in, (0, None))(base_keys, e)
        p, s, tl, vl = jax.vmap(partial(lane_pass, n_batches=n_batches,
                                        batch_size=batch_size))(
            p, s, keys, live, tr, val)
        epochs = epochs + live.astype(jnp.int32)
        improved = live & (vl < best_v - 1e-6)
        best_p = jax.tree.map(
            lambda b, q: jnp.where(
                improved.reshape(improved.shape + (1,) * (q.ndim - 1)),
                q, b),
            best_p, p)
        best_v = jnp.where(improved, vl, best_v)
        since = jnp.where(improved, 0, since + 1)
        live = live & (since < patience)
        return (p, s, best_p, best_v, since, live, epochs), (tl, vl)

    @partial(jax.jit, static_argnames=("n_batches", "batch_size",
                                       "max_epochs", "patience"))
    def run_fit_k(params, opt_state, base_keys, tr, val, live0, *,
                  n_batches, batch_size, max_epochs, patience):
        L = live0.shape[0]
        body = partial(epoch, base_keys=base_keys, tr=tr, val=val,
                       n_batches=n_batches, batch_size=batch_size,
                       patience=patience)

        def epoch_step(carry, e):
            # the cond sits OUTSIDE the per-lane vmap: once every lane has
            # stopped, remaining epochs cost one predicate each
            dead = lambda c: (c, (jnp.zeros((L,), jnp.float32),
                                  jnp.zeros((L,), jnp.float32)))
            return jax.lax.cond(jnp.any(carry[5]), lambda c: body(c, e),
                                dead, carry)

        carry, (tls, vls) = jax.lax.scan(
            epoch_step, _carry0(params, opt_state, params, live0),
            jnp.arange(max_epochs, dtype=jnp.int32))
        return carry[2], carry[6], tls, vls

    @partial(jax.jit, static_argnames=("n_batches", "batch_size",
                                       "patience"), donate_argnums=0)
    def run_epoch_k(carry, e, base_keys, tr, val, *, n_batches, batch_size,
                    patience):
        return epoch(carry, e, base_keys, tr, val, n_batches=n_batches,
                     batch_size=batch_size, patience=patience)

    return run_fit_k, run_epoch_k


def _epoch_loop(loss_fn, lr, params, opt_state, base_keys, tr, val, live0,
                *, max_epochs, on_epoch, **kw):
    """Drive ``run_epoch_k`` from the host, one sync an epoch, until no
    lane is live.  Returns what ``run_fit_k`` does, histories cut at the
    last epoch run."""
    run_epoch = get_lanes_engine(loss_fn, lr=lr)
    # best_p is a copy: the carry is donated and must hold no buffer twice
    carry = _carry0(params, opt_state, jax.tree.map(jnp.copy, params),
                    live0)
    tls, vls = [], []
    for e in range(max_epochs):
        carry, (tl, vl) = run_epoch(carry, e, base_keys, tr, val, **kw)
        tl, vl, live = jax.device_get((tl, vl, carry[5]))
        tls.append(tl)
        vls.append(vl)
        if on_epoch is not None:
            on_epoch(e, carry[0], tl, vl)
        if not live.any():
            break
    return carry[2], carry[6], np.stack(tls), np.stack(vls)


# ---------------------------------------------------------------------------
# lane stacks: split, stack, shard, unstack
# ---------------------------------------------------------------------------

def _prep_lanes(specs: Sequence[LaneSpec], *, batch_size: int,
                val_frac: float, lr: float):
    """One shape group's per-lane host split and stacked device upload.
    The lanes of one stack must share their train row count: each epoch's
    batches are slices of a permutation of the stack's rows, which would
    reach padded rows of a shorter lane.  Returns ``(params, opt_state,
    base_keys, tr, val, n_batches, bs)``."""
    K = len(specs)
    assert K >= 1
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

    for t, v in zip(tr_list, val_list):
        if "x" in t:
            t["mask"] = jnp.ones((t["x"].shape[1],), jnp.float32)
            v["mask"] = t["mask"]
            v["row_w"] = jnp.ones((v["x"].shape[0],), jnp.float32)

    # --- stacked on device (no host round-trip) ---------------------------
    tr = padding.pad_stack(tr_list)
    val = padding.pad_stack(val_list)
    params = padding.pad_stack([sp.params for sp in specs])
    opt_state = paper_adam(lr).init(params)
    opt_state = opt_state._replace(step=jnp.zeros((K,), jnp.int32))
    base_keys = jnp.stack([jax.random.PRNGKey(sp.seed) for sp in specs])
    return params, opt_state, base_keys, tr, val, n_tr // bs, bs


def _grow_lanes(stacked, n: int):
    """Pad the lane axis of every stacked array to ``n`` with dead lanes:
    zero params and data, ``live=False``."""
    return jax.tree.map(lambda a: padding.pad_to(a, (n,) + a.shape[1:]),
                        stacked)


def _shard_lanes(mesh, params, opt_state, base_keys, tr, val, *lane_vecs,
                 shard_rows: bool):
    """Pad the lane axis to a mesh multiple with dead lanes and
    ``device_put`` every stacked input, and each per-lane vector in
    ``lane_vecs`` (the live flags), with policy-resolved shardings.
    Returns the inputs device-parallel in the order given; the engine
    itself is unchanged (computation follows data)."""
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
    (params, opt_state, base_keys, tr, val, lane_vecs) = _grow_lanes(
        (params, opt_state, base_keys, tr, val, lane_vecs), Lp)

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
# not two eager programs a leaf a lane; module-scoped, so it traces once
# per leaf shapes, lane count and sharding.  Dead mesh-padding lanes
# (``i >= k``) are never emitted.  On a mesh the outputs come back
# replicated over it, as eager ``l[i]`` does: callers concatenate lanes'
# encodings, which arrays on different chips would break.
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


# ---------------------------------------------------------------------------
# the drivers
# ---------------------------------------------------------------------------

def _fit_lanes(specs: Sequence[LaneSpec], loss_fn: Callable, *,
               batch_size: int, max_epochs: int, patience: int, lr: float,
               val_frac: float, mesh=None, shard_rows: bool = False,
               epochwise: bool = False,
               on_epoch: Optional[Callable] = None) -> List[TrainResult]:
    """Every shape group of ``specs`` through ``run_fit_k`` (launched
    group after group, one host sync for all), or through the host loop
    over ``run_epoch_k`` when ``epochwise``."""
    # the batch-size clamp over ALL lanes, so each group's _prep_lanes
    # clamps to exactly this value
    n_tr_all = [n - max(int(n * val_frac), 1)
                for n in (len(next(iter(sp.data.values()))) for sp in specs)]
    global_bs = max(min(batch_size, min(n_tr_all)), 1)

    launched = []                 # (idxs, gspecs, best_params, n_batches)
    host_parts = []               # (epochs, tls, vls) per group, in-flight
    for idxs in _lane_groups(specs):
        gspecs = [specs[i] for i in idxs]
        with span("lanes.prep"):
            (params, opt_state, base_keys, tr, val, n_batches,
             bs) = _prep_lanes(gspecs, batch_size=global_bs,
                               val_frac=val_frac, lr=lr)
            live0 = jnp.ones((len(idxs),), bool)
            if len(idxs) == 1:
                # XLA compiles a stack of one unlike a stack of many, and
                # rounds differently; with a dead lane beside it, a lane's
                # result does not depend on the stack it rides in
                (params, opt_state, base_keys, tr, val,
                 live0) = _grow_lanes((params, opt_state, base_keys, tr,
                                       val, live0), 2)
        if mesh is not None:
            with span("lanes.shard"):
                (params, opt_state, base_keys, tr, val,
                 live0) = _shard_lanes(mesh, params, opt_state, base_keys,
                                       tr, val, live0, shard_rows=shard_rows)
        kw = dict(n_batches=n_batches, batch_size=bs, max_epochs=max_epochs,
                  patience=patience)
        with span("lanes.launch"):
            if epochwise:
                best_params, *parts = _epoch_loop(
                    loss_fn, lr, params, opt_state, base_keys, tr, val,
                    live0, on_epoch=on_epoch, **kw)
            else:
                best_params, *parts = get_lanes_fit_engine(loss_fn, lr=lr)(
                    params, opt_state, base_keys, tr, val, live0, **kw)
        launched.append((idxs, gspecs, best_params, n_batches))
        host_parts.append(parts)
    # the single host sync of the fit, coalesced over every shape group
    with span("lanes.sync"):
        host_parts = jax.device_get(host_parts)

    results: List[TrainResult] = [None] * len(specs)  # type: ignore
    with span("lanes.unstack"):
        for (idxs, gspecs, best_params, n_batches), (epochs, tls, vls) in \
                zip(launched, host_parts):
            lane_params = _unstack_lane_params(gspecs, best_params)
            for j, i in enumerate(idxs):
                e = int(epochs[j])
                results[i] = TrainResult(lane_params[j], e, e * n_batches,
                                         [float(t) for t in tls[:e, j]],
                                         [float(v) for v in vls[:e, j]])
    return results


def train_lanes(specs: Sequence[LaneSpec], loss_fn: Callable, *,
                batch_size: int = 128, max_epochs: int = 200,
                patience: int = 10, lr: float = 1e-3,
                val_frac: float = 0.1, mesh=None,
                shard_rows: bool = False) -> List[TrainResult]:
    """Fit L independent lanes through ``run_fit_k``: one upload and one
    compile per shape group, ONE host sync per call for all lanes (module
    docstring: layout, per-lane early stop, mesh sharding).

    ``loss_fn(params, batch)`` sees a dict of the lane's row arrays; when
    the data holds ``"x"`` the batch also carries the all-ones ``mask``
    and ``row_w`` entries (``autoencoder.masked_recon_loss``).

    ``mesh`` (from ``repro.launch.mesh.make_lane_mesh``, axes
    ``("lane", "data")``) shards the lane axis across devices;
    ``shard_rows=True`` additionally shards each lane's rows across the
    ``data`` axis (the large-row regime).  Sharded or not, the same
    program runs the same computation.

    Returns one ``TrainResult`` per lane: its best-val params, unstacked
    from the group in one dispatch, and histories cut at that lane's stop
    epoch."""
    return _fit_lanes(specs, loss_fn, batch_size=batch_size,
                      max_epochs=max_epochs, patience=patience, lr=lr,
                      val_frac=val_frac, mesh=mesh, shard_rows=shard_rows)


def train_lanes_epochwise(specs: Sequence[LaneSpec], loss_fn: Callable, *,
                          batch_size: int = 128, max_epochs: int = 200,
                          patience: int = 10, lr: float = 1e-3,
                          val_frac: float = 0.1) -> List[TrainResult]:
    """``train_lanes`` driven epoch by epoch from the host through
    ``run_epoch_k``, one sync an epoch: the tests' oracle for the scan."""
    return _fit_lanes(specs, loss_fn, batch_size=batch_size,
                      max_epochs=max_epochs, patience=patience, lr=lr,
                      val_frac=val_frac, epochwise=True)


def train(params, data: dict, loss_fn: Callable, *, batch_size: int = 128,
          max_epochs: int = 200, patience: int = 10, lr: float = 1e-3,
          val_frac: float = 0.1, seed: int = 0,
          epoch_callback: Optional[Callable] = None) -> TrainResult:
    """One fit, as a lane of one.  data: dict of equal-length arrays
    (row-aligned); loss_fn(params, batch).

    ``epoch_callback(epoch, params, train_loss, val_loss)`` runs the host
    loop instead of the scan; it gets a copy of the lane's params each
    epoch, which stays readable after the carry is donated onward."""
    specs = [LaneSpec(params, data, seed)]
    kw = dict(batch_size=batch_size, max_epochs=max_epochs,
              patience=patience, lr=lr, val_frac=val_frac)
    if epoch_callback is None:
        return train_lanes(specs, loss_fn, **kw)[0]

    def on_epoch(epoch, stack, tl, vl):
        epoch_callback(epoch, _unstack_lane_params(specs, stack)[0],
                       float(tl[0]), float(vl[0]))
    return _fit_lanes(specs, loss_fn, epochwise=True, on_epoch=on_epoch,
                      **kw)[0]
