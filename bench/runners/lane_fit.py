"""Lane-sharded fits, back to back, through the lane engine's entry
point: ``repro.core.training.train_lanes(..., mesh=make_lane_mesh(lane=n))``.

Set-up makes every lane's rows on the device from the seed (the scale
grid's party block, ``bench/gen.py``) and every lane's initial weights in
one jitted call, then warms the program with one fit.  The window fits the
same lanes again and again, each fit with fresh lane seeds (a new
validation split and batch order per lane), until ``seconds`` have passed.
The check takes one fit of the window and some of its lanes, drawn from
the seed, and fits them again with the plain reference.
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
        self.devices = devices
        self.n_lanes = config["parties"] * config["seed_replicas"]
        self.widths = [config["features"], *config["encoder"]]

    def setup(self) -> None:
        import jax

        from repro.core import autoencoder as ae
        from repro.core import training
        from repro.launch.mesh import make_lane_mesh

        cfg = self.cfg
        self._training, self._loss = training, ae.masked_recon_loss
        self.mesh = make_lane_mesh(lane=cfg["lane_mesh"])
        self.x = [gen.make_party(cfg["rows"], n_features=cfg["features"],
                                 n_latent=cfg["latent"], party=p,
                                 seed=gen.derive(self.seed, 1, r),
                                 noise=cfg["noise"], device=self.devices[0])
                  for r in range(cfg["seed_replicas"])
                  for p in range(cfg["parties"])]
        widths = self.widths

        @jax.jit
        def init(key):
            return [ref.init_autoencoder(k, widths)
                    for k in jax.random.split(key, self.n_lanes)]

        self.init = init(jax.random.PRNGKey(gen.derive(self.seed, 2)))
        self._fit(-1)

    def _lane_seeds(self, j: int) -> list:
        return [gen.derive(self.seed, 3, j + 1, i) for i in range(self.n_lanes)]

    def _fit(self, j: int):
        cfg, training = self.cfg, self._training
        seeds = self._lane_seeds(j)
        lanes = [training.LaneSpec(p, {"x": x}, s)
                 for p, x, s in zip(self.init, self.x, seeds)]
        out = training.train_lanes(
            lanes, self._loss, batch_size=cfg["batch_size"],
            max_epochs=cfg["max_epochs"], patience=cfg["patience"],
            lr=cfg["lr"], mesh=self.mesh)
        return seeds, out

    def window(self, seconds: float, span) -> dict:
        rng = np.random.RandomState(gen.derive(self.seed, 4))
        self.kept = None
        fits, lane_rows = 0, 0
        t0 = time.perf_counter()
        while True:
            with span("lane_fit"):
                seeds, out = self._fit(fits)
            lane_rows += sum(r.steps_run for r in out) * self.cfg["batch_size"]
            if rng.randint(fits + 1) == 0:      # one fit, drawn uniformly
                self.kept = (seeds, out)
            fits += 1
            wall = time.perf_counter() - t0
            if wall >= seconds:
                break
        return {"fits": fits, "wall_s": wall, "lane_rows": lane_rows,
                "attempted": fits * self.n_lanes,
                "failed": sum(not np.isfinite(r.train_loss[-1])
                              for r in self.kept[1])}

    def end_to_end(self, stats: dict) -> dict:
        return {"train_rows_per_s": stats["lane_rows"] / stats["wall_s"]}

    def release(self) -> None:
        import jax

        # lanes shard over the chips in contiguous blocks: check lanes of
        # every chip's block, drawn from the seed
        rng = np.random.RandomState(gen.derive(self.seed, 5))
        per_chip = int(self.traffic["check_lanes_per_chip"])
        idx = sorted(int(i) for block in np.array_split(
            np.arange(self.n_lanes), len(self.devices))
            for i in rng.choice(block, min(per_chip, len(block)),
                                replace=False))
        seeds, out = self.kept
        self.checked = [{
            "lane": i, "seed": seeds[i], "x": self.x[i],
            "init": jax.device_get(self.init[i]),
            "prog": {"params": jax.device_get(out[i].params),
                     "train_loss": np.asarray(out[i].train_loss),
                     "val_loss": np.asarray(out[i].val_loss)}}
            for i in idx]
        self.x = self.init = self.kept = self.mesh = None

    def _ref_fit(self, c, dtype=None, precision="highest"):
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        dtype = dtype or jnp.float32
        p0 = jax.tree.map(lambda a: jnp.asarray(a, dtype), c["init"])
        return ref.fit(p0, {"x": jnp.asarray(c["x"], dtype)}, c["seed"],
                       ref.stage_loss("recon", precision),
                       batch_size=cfg["batch_size"], epochs=cfg["max_epochs"],
                       patience=cfg["patience"], lr=cfg["lr"])

    def control(self) -> list:
        """The reference in bfloat16 in the program's place."""
        import jax.numpy as jnp
        return [chk.lane_numbers(self._ref_fit(c, jnp.bfloat16, None),
                                 self._ref_fit(c), c["init"])
                for c in self.checked]

    def check(self) -> list:
        return [chk.lane_numbers(c["prog"], self._ref_fit(c), c["init"])
                for c in self.checked]
