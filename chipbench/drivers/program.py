"""Driver for programming mixes: back-to-back whole deploys.

The window runs `deploy_arrays` over the configuration's projection
leaves again and again, each deploy with its own key from the seed,
until `--seconds` have passed; the deploy that is running then is run
to its end.  `program_cols_per_s` is every column the window's deploys
brought to target over the time of those whole deploys.

Traffic keys: `method` (write-and-verify scheme), `n_cells` (cells per
verify column), `check_columns_per_deploy` (how many columns of each
deploy the check compares), `limits` (of the check's numbers).

The check: from each deploy of the window, columns drawn from the seed
(the same number from each leaf in proportion to its size) are kept as
the program left them, and after the window a plain reference
(`chipbench.reference.programming`) programs the same columns from the
same weights and key.  The program's per-column random streams make a
column's conductances a function of the key, its id and its targets
alone, so the two must agree bit for bit.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import inputs
from chipbench.harness import Check, enable_kernels
from chipbench.reference import programming as ref

from repro.core import WVConfig, WVMethod
from repro.core.programmer import deploy_arrays

WARMUP = 1 << 30  # the set-up deploy's key index; the window's count from 0


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        cfg, tr = ctx.config, ctx.traffic
        analog = cfg["analog"]
        self.wv = enable_kernels(WVConfig(
            method=WVMethod(tr["method"]), n_cells=int(tr["n_cells"]),
            weight_bits=int(analog["weight_bits"]),
        ))
        if self.wv.device.bc != int(analog["cell_bits"]):
            raise ValueError(
                f"the program's cells hold {self.wv.device.bc} bits, the "
                f"configuration {analog['cell_bits']}"
            )
        self.ref_wv = ref.WV(method=tr["method"], n_cells=self.wv.n_cells,
                             weight_bits=self.wv.weight_bits)
        self.dev = ref.Device(bc=self.wv.device.bc)
        self.n_check = int(tr["check_columns_per_deploy"])
        self.limits = tr["limits"]
        self.records: dict = {"deploys": 0, "columns": 0, "column_iterations": 0.0,
                              "n_cells": self.wv.n_cells,
                              "magnitude_pulses": not self.ref_wv.ternary}
        self.attempted = self.failed = 0
        self.kept: list[tuple[np.ndarray, jax.Array]] = []

    def _key(self, i: int) -> jax.Array:
        return jax.random.fold_in(inputs.key_from_seed(self.ctx.seed, 2), i)

    def _deploy(self, i: int):
        with self.ctx.annotate("deploy"):
            deployed, report = deploy_arrays(self._key(i), self.weights, self.wv)
            jax.block_until_ready([a.g for a in deployed.arrays.values()])
        return deployed, report

    def _keep(self, i: int, deployed) -> None:
        rows = sample_rows(self.ctx.seed, i, [c for _, _, c in self.leaves], self.n_check)
        uids = np.concatenate([base + r for (_, base, _), r in zip(self.leaves, rows)])
        g = jnp.concatenate([
            deployed.arrays[name].g[jnp.asarray(r, jnp.int32)]
            for (name, _, _), r in zip(self.leaves, rows)
        ])
        self.kept.append((uids, g))

    def setup(self) -> None:
        self.weights = inputs.projection_weights(self.ctx.config, self.ctx.seed)
        jax.block_until_ready(self.weights)
        deployed, _ = self._deploy(WARMUP)  # compiles what the window runs
        self.leaves = [(name, int(a.uids[0]), int(a.g.shape[0]))
                       for name, a in deployed.arrays.items()]
        self.leaves.sort(key=lambda leaf: leaf[1])
        self._keep(WARMUP, deployed)  # the gathers the window dispatches
        jax.block_until_ready(self.kept[-1][1])
        self.kept.clear()
        del deployed

    def window(self, seconds: float) -> dict[str, float]:
        t0 = time.perf_counter()
        i = 0
        while time.perf_counter() - t0 < seconds:
            self.attempted += 1
            t_deploy = time.perf_counter()
            deployed, report = self._deploy(i)
            self.ctx.log(f"deploy {i}: {time.perf_counter() - t_deploy!r} s")
            self._keep(i, deployed)
            del deployed
            self.records["columns"] += report.num_columns
            self.records["column_iterations"] += report.mean_iterations * report.num_columns
            i += 1
        elapsed = time.perf_counter() - t0
        self.records["deploys"] = i
        return {"program_cols_per_s": self.records["columns"] / elapsed}

    def release(self) -> None:
        del self.weights

    def check(self) -> list[Check]:
        """The kept columns against the reference, deploy by deploy."""
        share = compare(self.ctx, [(self._key(i), uids, g)
                                   for i, (uids, g) in enumerate(self.kept)],
                        self.ref_wv, self.dev)
        return [Check("differing_columns_share", share,
                      float(self.limits["differing_columns_share"]))]


def sample_rows(seed: int, deploy: int, counts: list[int], n_check: int) -> list[np.ndarray]:
    """Rows of each leaf that the check compares in deploy `deploy`: as
    many from each leaf as its share of the columns, drawn from the seed,
    or all of them where the check asks for as many."""
    total = sum(counts)
    g = np.random.default_rng([seed, deploy])
    out = []
    for count in counts:
        k = max(1, round(n_check * count / total))
        out.append(np.arange(count) if k >= count else np.sort(g.integers(0, count, k)))
    return out


def compare(ctx, programmed, wv, dev) -> float:
    """Share of the columns `programmed` ((key, uids, conductances) per
    deploy) whose conductances differ from the reference's."""
    weights = inputs.projection_weights(ctx.config, ctx.seed)
    every = np.concatenate([uids for _, uids, _ in programmed])
    targets_all = ref.targets_for(weights, every, wv, dev)
    del weights
    differing = compared = 0
    worst = 0.0
    for key, uids, g in programmed:
        targets = jnp.asarray(targets_all[compared : compared + len(uids)])
        ids = jnp.asarray(uids, jnp.int32)
        d2d = ref.d2d_for(key, ids, wv.n_cells, dev)
        want = ref.program_jit(key, targets, ids, d2d, wv, dev)
        diff = np.asarray(jnp.abs(g - want))
        differing += int(np.sum(np.any(diff != 0, axis=-1)))
        compared += len(uids)
        worst = max(worst, float(diff.max()))
    ctx.log(f"check: {compared} columns of {len(programmed)} deploys compared, "
            f"{differing} differ, widest gap {worst!r} LSB")
    return differing / compared if compared else 1.0


def control(ctx, deploys: int = 3) -> float:
    """The lower-precision control: the reference in place of the
    program, computed in bfloat16, on the columns a run would check in
    its first `deploys` deploys, compared as the check compares."""
    driver = Driver(ctx)
    weights = inputs.projection_weights(ctx.config, ctx.seed)
    counts = [ref.leaf_columns(w.shape, driver.ref_wv.n_cells,
                               driver.ref_wv.weight_bits // driver.dev.bc)
              for w in jax.tree_util.tree_leaves(weights)]
    bases = np.cumsum([0] + counts[:-1])
    every = []
    for i in range(deploys):
        rows = sample_rows(ctx.seed, i, counts, driver.n_check)
        every.append(np.concatenate([b + r for b, r in zip(bases, rows)]))
    targets = ref.targets_for(weights, np.concatenate(every), driver.ref_wv, driver.dev)
    del weights
    programmed, off = [], 0
    for i, uids in enumerate(every):
        key, ids = driver._key(i), jnp.asarray(uids, jnp.int32)
        t = jnp.asarray(targets[off : off + len(uids)])
        off += len(uids)
        d2d = ref.d2d_for(key, ids, driver.ref_wv.n_cells, driver.dev, jnp.bfloat16)
        g = ref.program_jit(key, t, ids, d2d, driver.ref_wv, driver.dev, jnp.bfloat16)
        programmed.append((key, uids, g.astype(jnp.float32)))
    return compare(ctx, programmed, driver.ref_wv, driver.dev)
