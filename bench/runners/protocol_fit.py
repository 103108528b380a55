"""Whole protocol fits, back to back, through the entry point users call:
``repro.core.pipeline.run_apcvfl_replicated`` with one seed lane per
scenario (g1 lanes of both roles, the exchange, g2, g3, the k-fold probe).

Set-up builds a pool of scenarios from the seed (the dataset recipe and
vertical split in ``bench/gen.py``) and warms the program up with one fit
of the window's shapes.  The window fits the pool again and again, each
fit with fresh seeds drawn from the run's seed, until ``seconds`` have
passed.  The check takes one fit of the window, drawn from the seed, and
some of its seed lanes, and runs the plain reference
(``bench/reference.py``) from the same seeds.  What it compares is what the
timed calls returned: the weights and exchanged latents in each
``RunResult``, the per-epoch losses of every lane the lane engine fitted,
and the k-fold probe's predictions.  The protocol drops the last two on
its way out, so set-up wraps the two program functions that return them,
``training.train_lanes`` and ``classifier._fit_predict_folds_many``, with
recorders that pass every call through unchanged.
"""
from __future__ import annotations

import time

import numpy as np

import checks as chk
import gen
import reference as ref


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        self.cfg, self.traffic, self.seed = config, traffic, int(seed)
        self.lanes = int(traffic["seed_lanes_per_fit"])
        self.hp = dict(config["train"], patience=config["patience"])
        self.fits = []            # (scenario order, seeds, results, lanes)

    # --- the program's inputs ----------------------------------------------

    def _scenario(self, k: int) -> dict:
        cfg = self.cfg
        ds = gen.make_dataset(cfg["dataset"]["name"],
                              gen.derive(self.seed, 1, k))
        return gen.make_scenario(ds, n_active_features=cfg[
            "n_active_features"], n_aligned=cfg["n_aligned"],
            seed=gen.derive(self.seed, 2, k))

    def _fit_seeds(self, j: int) -> list:
        # 30-bit seeds: the protocol also uses seed + 1 .. seed + 3
        return [gen.derive(self.seed, 3, j + 1, i) >> 1
                for i in range(self.lanes)]

    def setup(self) -> None:
        from repro.core import classifier, pipeline, training
        from repro.data.vertical import ParticipantData, VFLScenario

        self._pipeline = pipeline
        self.pool = [self._scenario(k) for k in range(self.lanes)]
        self.program_pool = [
            VFLScenario(self.cfg["dataset"]["name"],
                        ParticipantData(x=s["xa"], ids=s["ids_a"], y=s["ya"]),
                        ParticipantData(x=s["xp"], ids=s["ids_p"]),
                        s["n_aligned"], s["n_classes"], s["a_cols"],
                        s["p_cols"]) for s in self.pool]
        # every lane fit the engine runs, keyed by its lane seed, and the
        # probe's (seeds, folds, rows) predictions
        self._record = self._probe = None
        orig = training.train_lanes
        orig_probe = classifier._fit_predict_folds_many

        def recording(specs, loss_fn, **kw):
            out = orig(specs, loss_fn, **kw)
            if self._record is not None:
                for sp, r in zip(specs, out):
                    self._record.append((int(sp.seed), r))
            return out

        def recording_probe(*a, **kw):
            out = orig_probe(*a, **kw)
            if self._probe is not None:
                self._probe.append(out)
            return out

        training.train_lanes = recording
        classifier._fit_predict_folds_many = recording_probe
        self._restore = ((training, "train_lanes", orig),
                         (classifier, "_fit_predict_folds_many", orig_probe))
        self._fit(-1)              # compiles every program of the window

    def _fit(self, j: int) -> dict:
        order = [(j + i) % self.lanes for i in range(self.lanes)]
        seeds = self._fit_seeds(j)
        self._record, self._probe = [], []
        results = self._pipeline.run_apcvfl_replicated(
            [self.program_pool[k] for k in order], seeds=seeds,
            lam=self.hp["lam"], batch_size=self.hp["batch_size"],
            max_epochs=self.hp["max_epochs"], patience=self.hp["patience"],
            lr=self.hp["lr"])
        lanes, probe = self._record, self._probe
        self._record = self._probe = None
        return {"order": order, "seeds": seeds, "results": results,
                "lanes": lanes, "probe": probe}

    # --- the window --------------------------------------------------------

    def window(self, seconds: float, span) -> dict:
        self.fits = []
        t0 = time.perf_counter()
        while True:
            with span("protocol_fit"):
                self.fits.append(self._fit(len(self.fits)))
            wall = time.perf_counter() - t0
            if wall >= seconds:
                break
        n = len(self.fits)
        return {"fits": n, "wall_s": wall, "seed_lanes": n * self.lanes,
                "attempted": n,
                "failed": sum(any(not np.isfinite(r.metrics["accuracy"])
                                  for r in f["results"]) for f in self.fits)}

    def end_to_end(self, stats: dict) -> dict:
        return {"fit_s": stats["wall_s"] / stats["fits"]}

    def release(self) -> None:
        import jax
        for mod, attr, orig in self._restore:
            setattr(mod, attr, orig)
        rng = np.random.RandomState(gen.derive(self.seed, 4))
        j = int(rng.randint(len(self.fits)))
        lanes = rng.choice(self.lanes, int(self.traffic["check_lanes"]),
                           replace=False)
        f = self.fits[j]
        by_seed = dict(f["lanes"])
        # one probe call a fit, one row of predictions a seed lane
        probe = (np.asarray(jax.device_get(f["probe"][0]))
                 if len(f["probe"]) == 1 else None)
        keep = []
        for i in sorted(int(i) for i in lanes):
            s = f["seeds"][i]
            r = f["results"][i]
            stages = {st: to_host(by_seed.get(s + k)) for st, k in
                      (("g1_active", 0), ("g1_passive", 1), ("g2", 2),
                       ("g3", 3))}
            # the weights the protocol returns, where it returns them
            for st in ("g1_active", "g2", "g3"):
                if stages[st] is not None:
                    stages[st]["params"] = jax.device_get(r.params[st])
            sc = self.pool[f["order"][i]]
            keep.append({
                "scenario": sc, "seed": s, "stages": stages,
                "probe_pred": (None if probe is None else ref.rows_of_folds(
                    probe[i], len(sc["ya"]), seed=s,
                    k=self.cfg["probe"]["folds"])),
                "exchange": np.asarray(r.artifacts["z_passive_aligned"]),
                "accuracy": r.metrics["accuracy"]})
        self.checked = keep
        self.fits = self.program_pool = self._pipeline = None

    # --- the check ---------------------------------------------------------

    def control(self) -> list:
        """The reference in bfloat16 in the program's place."""
        import jax.numpy as jnp
        out = []
        for c in self.checked:
            low = ref.protocol(c["scenario"], c["seed"], self.hp,
                               probe=self.cfg["probe"], dtype=jnp.bfloat16,
                               precision=None)
            out.append(chk.protocol_numbers(as_program(low), ref.protocol(
                c["scenario"], c["seed"], self.hp, probe=self.cfg["probe"])))
        return out

    def check(self) -> list:
        return [chk.protocol_numbers(c, ref.protocol(
            c["scenario"], c["seed"], self.hp, probe=self.cfg["probe"]))
            for c in self.checked]


def as_program(out: dict) -> dict:
    """A reference run laid out as ``release`` lays out the program's."""
    stages = {st: {"params": out[st]["params"],
                   "train_loss": out[st]["train_loss"],
                   "val_loss": out[st]["val_loss"]} for st in chk.STAGES}
    return {"stages": stages, "exchange": np.asarray(
        out["exchange"], np.float32), "accuracy": out["metrics"]["accuracy"],
        "probe_pred": out["probe_pred"]}


def to_host(r):
    """A lane's ``TrainResult`` as host arrays (None if the engine never
    ran the lane)."""
    if r is None:
        return None
    import jax
    return {"params": jax.device_get(r.params),
            "train_loss": np.asarray(r.train_loss, np.float64),
            "val_loss": np.asarray(r.val_loss, np.float64)}
