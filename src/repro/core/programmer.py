"""Model-level RRAM deployment: quantize -> slice -> program -> read back.

This is the integration point between the paper's WV technique and the
training/serving framework: `deploy_params` takes any pytree of model
parameters, pushes every matmul weight through the
quantize -> bit-slice -> pack-to-columns -> write-and-verify pipeline,
and returns the *programmed* parameters (with real programming error)
plus aggregate WV statistics (latency / energy / iterations), so a
trained checkpoint can be "burned" onto simulated RRAM with CW-SC, MRA,
HD-PV, or HARP and then served to measure end-task robustness.

Two deployment paths share one programming core:

* `deploy_params` / `deploy_matrix` — the original "collapse to dense"
  path: program, read back, return an ordinary parameter pytree.  The
  array state is discarded; conductances are frozen forever.
* `deploy_arrays` — the persistent path (DESIGN.md Sec. 9): returns a
  `DeployedModel` that keeps per-leaf `ArrayState` (programmed
  conductances `g`, integer `targets`, static `d2d` efficiencies, quant
  `scale`, pack `layout`) alive, plus `materialize()` to rebuild dense
  params on demand.  This is what `repro.lifetime` ages, verifies, and
  refreshes: conductances are *state*, not a one-shot output.

By default both deploy the whole model through the bucketed programming
pipeline (`core.pipeline`, DESIGN.md Sec. 10): all leaves' packed
columns are concatenated into a few power-of-two column buckets, each
programmed by ONE jitted, donated `program_columns` dispatch (column
axis shardable over a device mesh), with `DeployReport` accumulated
device-side and a single host sync per deploy.  `batched=False` keeps
the per-leaf baseline path; per-column RNG sub-streams make the two
bit-identical.

Deployment policy (documented in DESIGN.md Sec. 3):
* >=2D weight leaves go to RRAM (flattened to (K, M) on the last axis);
* 1D leaves (norm scales, biases) stay digital — they are tiny and in
  real ACiM macros live in SRAM next to the shift-and-add periphery;
* embedding tables are RRAM-deployable but excluded by default
  (`deploy_embeddings=False`): token embedding lookups are row reads,
  not VMM columns.

Columns are independent; under jit the caller may shard the column axis
over the full mesh (launch/program.py does this for the dry-run mesh).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.quant import (
    QuantConfig,
    dequantize_weight,
    pack_columns,
    quantize_weight,
    unpack_columns,
)
from repro import obs
from repro.quant.pack import PackedLayout

from . import device as dev_mod
from . import pipeline
from . import remap as remap_mod
from .cost import CircuitCost
from .types import FaultConfig, WVConfig
from .wv import WVStats

__all__ = [
    "ArrayState",
    "DeployReport",
    "DeployedModel",
    "deploy_arrays",
    "deploy_params",
    "deploy_matrix",
]


@dataclasses.dataclass
class DeployReport:
    """Aggregate WV statistics for one deployment.

    The give-up/remap fields ride the SAME single host sync as the rest
    of the report (DESIGN.md Secs. 10/15): `total_gave_up_cells` counts
    cells the bounded-retry budget declared unprogrammable,
    `total_retry_pulses` the fine pulses burned on them before giving
    up, and `remapped_columns` the primary columns repaired onto spares.
    All three are zero on a fault-free / budget-less deploy.
    """

    num_columns: int = 0
    num_cells: int = 0
    mean_iterations: float = 0.0
    total_latency_ns: float = 0.0     # sum over arrays (columns in parallel)
    critical_latency_ns: float = 0.0  # max over columns = array wall-time
    total_energy_pj: float = 0.0
    rms_cell_error_lsb: float = 0.0
    total_reads: float = 0.0          # verify ADC conversions/comparisons
    total_write_pulses: float = 0.0
    total_gave_up_cells: float = 0.0  # cells declared unprogrammable
    total_retry_pulses: float = 0.0   # pulses burned on gave-up cells
    remapped_columns: int = 0         # primaries repaired onto spares
    # The WV loops' occupancy (`pipeline.loop_work`): iterations of the
    # columns while still being programmed, against trips x columns
    # carried by every ladder stage of every loop (bucket filler
    # included), and the compacted stages that ran.
    active_column_iterations: int = 0
    loop_column_iterations: int = 0
    loop_compactions: int = 0
    leaves: dict[str, dict[str, float]] = dataclasses.field(default_factory=dict)

    @staticmethod
    def reductions(
        leaf_stats: "dict[str, WVStats]",
        remapped: "dict[str, jax.Array] | None" = None,
        loop_works: "Sequence[jax.Array]" = (),
        extra: Any | None = None,
    ) -> tuple:
        """The report's device-side reductions, for ONE host sync.

        All reductions (per-leaf and aggregate) are jnp ops over the
        still-on-device `WVStats` arrays; building them never blocks.
        The caller transfers the returned tree with a single
        `pipeline.host_fetch` (device_get) and builds the report with
        `from_fetched`.  This is the batched-deployment stats contract
        (DESIGN.md Sec. 10): nothing in the deploy loop blocks on the
        device.  `loop_works` are the programming passes' per-bucket
        loop work (`pipeline.program_packed_columns`); `extra` is an
        arbitrary device tree (per-tile health reductions, deploy
        digests — DESIGN.md Sec. 16) riding the SAME fetch, returned last
        so the caller can fold its host copy.
        """
        stats = list(leaf_stats.values())
        its = jnp.concatenate([s.iterations for s in stats])
        lat = jnp.concatenate([s.latency_ns for s in stats])
        en = jnp.concatenate([s.energy_pj for s in stats])
        rms2 = jnp.concatenate([s.rms_error_lsb**2 for s in stats])
        agg = dict(
            mean_iterations=jnp.mean(its),
            total_latency_ns=jnp.sum(lat),
            critical_latency_ns=jnp.max(lat),
            total_energy_pj=jnp.sum(en),
            rms_cell_error_lsb=jnp.sqrt(jnp.mean(rms2)),
            # Telemetry sums (DESIGN.md Sec. 14) ride the same single
            # fetch: device-side reductions, zero extra syncs.
            total_reads=jnp.sum(
                jnp.concatenate([s.reads for s in stats])
            ),
            total_write_pulses=jnp.sum(
                jnp.concatenate([s.write_pulses for s in stats])
            ),
            # Give-up accounting (DESIGN.md Sec. 15) rides the same sync.
            total_gave_up_cells=jnp.sum(
                jnp.concatenate([s.gave_up for s in stats])
            ),
            total_retry_pulses=jnp.sum(
                jnp.concatenate([s.retry_pulses for s in stats])
            ),
        )
        per = {
            name: dict(
                mean_iterations=jnp.mean(s.iterations),
                critical_latency_ns=jnp.max(s.latency_ns),
                energy_pj=jnp.sum(s.energy_pj),
                rms_cell_error_lsb=jnp.sqrt(jnp.mean(s.rms_error_lsb**2)),
                gave_up_cells=jnp.sum(s.gave_up),
            )
            for name, s in leaf_stats.items()
        }
        return agg, per, remapped or {}, list(loop_works), extra

    @classmethod
    def from_fetched(
        cls, leaf_stats: "dict[str, WVStats]", n_cells: int, fetched: tuple
    ) -> "DeployReport":
        """The report from the host copy of `reductions`' tree."""
        agg_h, per_h, rem_h, loops_h, _ = fetched
        # Per-bucket int32 values, summed on the host without overflow.
        active, loop, compactions = sum(
            (np.asarray(w, np.int64).sum(axis=0) for w in loops_h),
            np.zeros(3, np.int64),
        )
        stats = list(leaf_stats.values())
        report = cls(
            num_columns=sum(int(s.iterations.shape[0]) for s in stats),
            num_cells=sum(int(s.iterations.shape[0]) * n_cells for s in stats),
            remapped_columns=int(sum(float(v) for v in rem_h.values())),
            active_column_iterations=int(active),
            loop_column_iterations=int(loop),
            loop_compactions=int(compactions),
            **{k: float(v) for k, v in agg_h.items()},
        )
        report.leaves = {
            name: dict(
                columns=int(leaf_stats[name].iterations.shape[0]),
                **{k: float(v) for k, v in d.items()},
            )
            for name, d in per_h.items()
        }
        for name, v in rem_h.items():
            report.leaves[name]["remapped_columns"] = float(v)
        return report

    def merge(self, name: str, stats: WVStats, wv_cfg: WVConfig) -> None:
        c, n_cells = int(stats.iterations.shape[0]), wv_cfg.n_cells
        lat = float(jnp.sum(stats.latency_ns))
        crit = float(jnp.max(stats.latency_ns))
        en = float(jnp.sum(stats.energy_pj))
        it = float(jnp.mean(stats.iterations))
        rms = float(jnp.sqrt(jnp.mean(stats.rms_error_lsb**2)))
        # One leaf is one dispatch with no filler, counted as a bucket is.
        active, loop, compactions = np.asarray(
            pipeline.loop_work(stats.iterations, c, 1, wv_cfg.max_fine_iters),
            np.int64,
        )
        self.active_column_iterations += int(active)
        self.loop_column_iterations += int(loop)
        self.loop_compactions += int(compactions)
        self.total_reads += float(jnp.sum(stats.reads))
        self.total_write_pulses += float(jnp.sum(stats.write_pulses))
        self.total_gave_up_cells += float(jnp.sum(stats.gave_up))
        self.total_retry_pulses += float(jnp.sum(stats.retry_pulses))
        self.leaves[name] = dict(
            columns=c, mean_iterations=it, critical_latency_ns=crit,
            energy_pj=en, rms_cell_error_lsb=rms,
        )
        tot_cells = self.num_cells + c * n_cells
        w_old = self.num_cells / max(tot_cells, 1)
        self.rms_cell_error_lsb = float(
            (self.rms_cell_error_lsb**2 * w_old + rms**2 * (1 - w_old)) ** 0.5
        )
        self.mean_iterations = (
            self.mean_iterations * self.num_columns + it * c
        ) / max(self.num_columns + c, 1)
        self.num_columns += c
        self.num_cells = tot_cells
        self.total_latency_ns += lat
        self.critical_latency_ns = max(self.critical_latency_ns, crit)
        self.total_energy_pj += en


@dataclasses.dataclass
class ArrayState:
    """Persistent programmed state of one weight leaf on RRAM.

    `g` is the *live* analog conductance of every cell (LSB units) — the
    lifetime subsystem mutates it (drift, refresh) by assigning a new
    array; everything else is fixed at deployment: `targets` are the
    intended integer levels (the refresh target), `d2d` the static
    per-cell step-efficiency (a device property, so re-programming the
    same physical array must reuse it), `scale`/`layout`/`shape`/`dtype`
    invert the quantize/pack transform.

    Faulty-silicon deploys (DESIGN.md Sec. 15) carry two extra pieces of
    physical state: `fault` — the sampled per-cell `FaultMap`, reused by
    every re-program of the same cells — and `remap` — the spare-column
    `RemapTable`.  With a remap the per-column arrays are PHYSICAL
    (C + S rows: C primaries then S spares) and the logical C-column
    view is ``x[remap.perm]``; `layout` always describes the logical
    geometry.
    """

    g: jax.Array              # (C[+S], N) programmed analog levels, LSB
    targets: jax.Array        # (C[+S], N) integer target levels, LSB
    d2d: jax.Array            # (C[+S], N) static per-cell step efficiency
    scale: jax.Array          # per-channel quantization scale
    layout: PackedLayout
    shape: tuple[int, ...]    # original leaf shape
    dtype: Any
    fault: dev_mod.FaultMap | None = None   # sampled silicon faults
    remap: remap_mod.RemapTable | None = None  # spare-column repair view
    # Physical column uids (host numpy, one per g row).  Pure address
    # metadata: uid // columns_per_tile is the tile a column lives on,
    # which is how scrub-time health maps (obs.health, DESIGN.md
    # Sec. 16) attribute drift to silicon without any device work.
    uids: np.ndarray | None = None

    def materialize(self, dtype: Any | None = None) -> jax.Array:
        """Programmed conductances -> effective dense weight leaf.

        `dtype` overrides the stored leaf dtype (deploy_matrix reads
        back in float32 regardless of the input dtype, so the analog
        error is not additionally rounded to a low-precision mantissa).
        """
        g = remap_mod.apply_remap(self.g, self.remap)
        q = unpack_columns(g, self.layout)
        w = dequantize_weight(q, self.scale).reshape(self.shape)
        return w.astype(self.dtype if dtype is None else dtype)


@dataclasses.dataclass
class DeployedModel:
    """A parameter pytree whose matmul leaves live on simulated RRAM.

    State-ownership contract (DESIGN.md Sec. 9): this object owns the
    analog array state.  Consumers (serving) never touch `g` directly —
    they call `materialize()` for a dense snapshot; producers (the
    lifetime simulator, refresh policies) advance `g` via
    `update_array`.  Digital leaves (norms, biases, embeddings) are kept
    verbatim and merged back at materialization.
    """

    treedef: Any
    leaves: list              # digital leaves verbatim; RRAM slots hold None
    slots: dict[str, int]     # leaf name -> index into `leaves`
    arrays: dict[str, ArrayState]
    wv_cfg: WVConfig
    cost: CircuitCost

    def materialize(self, dtype: Any | None = None) -> Any:
        """Rebuild the full dense parameter pytree from current `g`.

        `dtype` overrides the deployed leaves' stored dtype (see
        `ArrayState.materialize`); digital leaves are returned as kept.
        """
        leaves = list(self.leaves)
        for name, state in self.arrays.items():
            leaves[self.slots[name]] = state.materialize(dtype)
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    def update_array(self, name: str, g: jax.Array) -> None:
        """Swap in aged/refreshed conductances for one leaf."""
        self.arrays[name] = dataclasses.replace(self.arrays[name], g=g)

    @property
    def num_columns(self) -> int:
        return sum(int(a.g.shape[0]) for a in self.arrays.values())


@dataclasses.dataclass
class _LeafPlan:
    """One eligible leaf, quantized and packed, awaiting programming."""

    name: str
    leaf: jax.Array
    cols: jax.Array           # (C, N) packed target levels
    layout: PackedLayout
    scale: jax.Array
    uid_base: int             # first global column uid of this leaf

    def state(
        self,
        g: jax.Array,
        d2d: jax.Array,
        targets: jax.Array | None = None,
        fault: dev_mod.FaultMap | None = None,
        remap: remap_mod.RemapTable | None = None,
        uids: np.ndarray | None = None,
    ) -> ArrayState:
        if uids is None:
            uids = self.uid_base + np.arange(
                int(self.cols.shape[0]), dtype=np.int64
            )
        return ArrayState(
            g=g, targets=self.cols if targets is None else targets, d2d=d2d,
            scale=self.scale, layout=self.layout, shape=self.leaf.shape,
            dtype=self.leaf.dtype, fault=fault, remap=remap,
            uids=np.asarray(uids, np.int64),
        )


# Deploy-wide digest configurations (static, so every deploy folds into
# the same bucket geometry): per-column verify write pulses and WV
# iterations.  Out-of-range columns clamp into the edge buckets.
_PULSE_DIGEST = ("deploy.write_pulses_per_column", 0.0, 4096.0, 64)
_ITER_DIGEST = ("deploy.iterations_per_column", 0.0, 128.0, 64)


def _deploy_health_tree(
    stats_map: "dict[str, WVStats]",
    uids_map: "dict[str, np.ndarray]",
    fault_cfg: FaultConfig | None,
    extra_columns: "dict[str, dict[str, jax.Array]] | None" = None,
) -> dict[str, Any]:
    """Device tree of per-tile health reductions + deploy digests.

    Everything here is a jnp reduction (or host uid bookkeeping) meant
    to ride the deploy's single `host_fetch` via `DeployReport.reductions
    (extra=...)` — building it never synchronizes (DESIGN.md Sec. 16).
    """
    cpt = (fault_cfg or FaultConfig()).columns_per_tile
    tiles = obs.health.tile_deploy_stats(
        stats_map, uids_map, cpt, extra_columns=extra_columns
    )
    stats = list(stats_map.values())
    pulses = jnp.concatenate([s.write_pulses for s in stats])
    iters = jnp.concatenate([s.iterations for s in stats])
    digs = {}
    for (name, lo, hi, nb), vals in (
        (_PULSE_DIGEST, pulses), (_ITER_DIGEST, iters),
    ):
        digs[name] = obs.StreamingDigest.zeros(lo, hi, nb).add(vals)
    return {"tiles": tiles, "digests": digs}


def _fold_deploy_health(extra_h: dict[str, Any]) -> dict[str, int]:
    """Fold the FETCHED health tree into the host registries.

    Also counts, under `deploy.`, `health_tile_range` (the tiles of the
    dense range the device reduced) and `health_tiles` (those that held
    a column), and returns both."""
    tiles_h = extra_h["tiles"]
    tile_ids, tiles = obs.health.present_tiles(tiles_h)
    for metric, vals in tiles.items():
        obs.health_registry.fold_tiles(f"deploy.{metric}", tile_ids, vals)
    for name, dig in extra_h["digests"].items():
        obs.digests.fold(name, dig)
    n_range = int(np.size(tiles_h["sums"]["columns"])) if tiles_h else 0
    counts = {"health_tile_range": n_range, "health_tiles": len(tile_ids)}
    obs.registry.fold(counts, prefix="deploy.")
    return counts


def _fold_deploy(report: "DeployReport", wv_cfg: WVConfig, cost: CircuitCost) -> None:
    """Registry counters and ledger charges of one deploy (DESIGN.md
    Sec. 14): every value was already fetched by the report's host
    sync(s) — pure host floats."""
    obs.registry.fold(
        {
            "columns": report.num_columns,
            "verify_reads": report.total_reads,
            "write_pulses": report.total_write_pulses,
            # Contract-bearing give-up/remap counters (DESIGN.md Sec. 15).
            "gave_up_cells": report.total_gave_up_cells,
            "retry_pulses": report.total_retry_pulses,
            "remapped_columns": report.remapped_columns,
            # The WV loops' occupancy: active over loop column-iterations.
            "active_column_iterations": report.active_column_iterations,
            "loop_column_iterations": report.loop_column_iterations,
            "loop_compactions": report.loop_compactions,
        },
        prefix="deploy.",
    )
    obs.charge(
        "deploy",
        energy_pj=report.total_energy_pj,
        latency_ns=report.critical_latency_ns,
        reads=report.total_reads,
        method=wv_cfg.method.value,
        columns=report.num_columns,
    )
    if report.total_gave_up_cells or report.remapped_columns:
        # Ledger attribution of the bounded-retry waste: energy of the
        # pulses burned on cells that were ultimately given up on,
        # estimated at mid-scale conductance (the per-pulse energy model
        # of cost.write_phase_cost, g = G_max/2).
        e_pulse_pj = (
            cost.v_set**2
            * (wv_cfg.device.g_max_lsb / 2.0 * cost.g_lsb_us)
            * cost.t_write_pulse_ns * 1e-3
        )
        obs.charge(
            "deploy.give_up",
            energy_pj=report.total_retry_pulses * e_pulse_pj,
            gave_up_cells=report.total_gave_up_cells,
            retry_pulses=report.total_retry_pulses,
            remapped_columns=report.remapped_columns,
        )


# Integer-exact, so compiling it whole changes no value.  Run op by op,
# its narrow-minor-axis slicing and transposes compiled some 25 small
# programs per leaf shape: about 80 s per leaf on a TPU v5e.
@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _pack_cols(q, n_cells: int, bc: int, slices: int) -> jax.Array:
    return pack_columns(q, n_cells, bc, slices)[0]


def _plan_leaf(name, w, wv_cfg, q_cfg, uid_base) -> _LeafPlan:
    w2 = w.reshape((-1, w.shape[-1]))
    # Quantization stays op by op: compiled whole, XLA may rewrite its
    # float division and change which level a weight rounds to.
    q, scale = quantize_weight(w2, q_cfg)
    cols = _pack_cols(q, wv_cfg.n_cells, q_cfg.cell_bits, q_cfg.slices)
    layout = PackedLayout(
        *q.shape, wv_cfg.n_cells, q_cfg.slices, q_cfg.cell_bits
    )
    return _LeafPlan(name, w, cols, layout, scale, uid_base)


def _program_plan(
    key: jax.Array, plan: _LeafPlan, wv_cfg: WVConfig, cost: CircuitCost | None
) -> tuple[ArrayState, WVStats]:
    """Program one planned leaf on its own (the per-leaf baseline path).

    Columns draw from per-column sub-streams ``fold_in(key, uid)``
    (DESIGN.md Sec. 10), with d2d sampled from the same split the engine
    would use — so the result is bit-identical to programming the same
    uids inside a bucketed multi-leaf dispatch.
    """
    cols = plan.cols
    col_ids = plan.uid_base + jnp.arange(cols.shape[0], dtype=jnp.int32)
    d2d = pipeline.sample_d2d_for(key, col_ids, cols.shape, wv_cfg.device)
    # Dispatch through the shared jitted entry so the math is compiled
    # identically to the bucketed path (jit-vs-eager rounding differs at
    # the ulp level); the per-leaf cost profile — one trace per leaf
    # shape, per-leaf host syncs in the caller — is unchanged.  The
    # entry donates its targets/d2d buffers off-CPU, and both must
    # survive as ArrayState, so pass copies there.
    fn = pipeline.get_program_fn(wv_cfg, cost if cost is not None else CircuitCost())
    if pipeline.donates():
        g, stats = fn(key, jnp.copy(cols), jnp.copy(d2d), col_ids)
    else:
        g, stats = fn(key, cols, d2d, col_ids)
    return plan.state(g, d2d), stats


def _program_leaf(
    key: jax.Array,
    w: jax.Array,
    wv_cfg: WVConfig,
    q_cfg: QuantConfig,
    cost: CircuitCost | None,
) -> tuple[ArrayState, WVStats]:
    """Quantize, pack, and program one weight leaf; keep the array state."""
    return _program_plan(key, _plan_leaf("", w, wv_cfg, q_cfg, 0), wv_cfg, cost)


def deploy_matrix(
    key: jax.Array,
    w: jax.Array,
    wv_cfg: WVConfig,
    q_cfg: QuantConfig | None = None,
    cost: CircuitCost | None = None,
) -> tuple[jax.Array, WVStats]:
    """Program one weight matrix onto RRAM; returns (w_programmed, stats)."""
    if q_cfg is None:
        q_cfg = QuantConfig(
            weight_bits=wv_cfg.weight_bits, cell_bits=wv_cfg.device.bc
        )
    state, stats = _program_leaf(key, w, wv_cfg, q_cfg, cost)
    return state.materialize(dtype=jnp.float32), stats


def _eligible_leaves(
    params: Any,
    deploy_embeddings: bool,
    predicate: Callable[[str, jax.Array], bool] | None,
):
    """Flatten params and yield (index, name, leaf, eligible)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    records = []
    for i, (path, leaf) in enumerate(flat):
        name = jax.tree_util.keystr(path)
        eligible = hasattr(leaf, "ndim") and leaf.ndim >= 2
        if eligible and not deploy_embeddings and "embed" in name.lower():
            eligible = False
        if eligible and predicate is not None:
            eligible = predicate(name, leaf)
        records.append((i, name, leaf, eligible))
    return records, treedef


def deploy_arrays(
    key: jax.Array,
    params: Any,
    wv_cfg: WVConfig,
    q_cfg: QuantConfig | None = None,
    cost: CircuitCost | None = None,
    *,
    deploy_embeddings: bool = False,
    predicate: Callable[[str, jax.Array], bool] | None = None,
    batched: bool = True,
    mesh: Any | None = None,
    min_bucket: int = pipeline.DEFAULT_MIN_BUCKET,
    max_bucket: int = pipeline.DEFAULT_MAX_BUCKET,
    fault_cfg: FaultConfig | None = None,
    remap_cfg: remap_mod.RemapConfig | None = None,
    sensitivity: Callable[[str, jax.Array], float] | None = None,
) -> tuple[DeployedModel, DeployReport]:
    """Program every eligible weight leaf, keeping persistent array state.

    Returns (DeployedModel, DeployReport).  Same eligibility policy as
    `deploy_params`; `DeployedModel.materialize()` reproduces exactly
    what `deploy_params` would have returned for the same key.

    `batched=True` (default) routes ALL leaves' packed columns through
    the bucketed pipeline (`core.pipeline`): one jitted, donated
    `program_columns` dispatch per shape bucket, stats accumulated
    device-side with a single host sync, and the column axis optionally
    sharded over `mesh`.  `batched=False` is the per-leaf baseline path
    (one dispatch + per-leaf host syncs); both paths draw per-column RNG
    sub-streams, so their results are bit-identical.

    Faulty silicon (DESIGN.md Sec. 15, batched path only):
    `fault_cfg` samples a per-cell `FaultMap` (persisted in each
    `ArrayState`) and programs under it; `remap_cfg` provisions spare
    columns per leaf and — after the primary pass — repairs the worst
    columns (by `WVStats.gave_up`, so set `wv_cfg.give_up_pulses`) onto
    them, with optional fault-aware placement steering leaves ranked by
    `sensitivity(name, leaf)` onto the cleanest probed tiles.  All remap
    decisions are device-side; the deploy still performs exactly one
    host sync, with give-up/remap accounting riding it.

    Spans (DESIGN.md Sec. 14): `deploy` covers the whole call, and its
    args carry `columns`, `active_column_iterations`,
    `loop_column_iterations`, `loop_compactions`, `health_tile_range`
    and `health_tiles` (the tiles the health maps reduced over, and
    those that held a column; both also `deploy.*` counters).  Its
    children are `deploy.plan` (quantize and pack), `deploy.dispatch`
    (the d2d draw and the bucket dispatches), `deploy.report` (the uid
    bounds and upload, the health tree's and the report's reductions,
    dispatched), `deploy.sync` (the host fetch's wait for the device)
    and `deploy.fold` (everything after the sync).
    """
    if q_cfg is None:
        q_cfg = QuantConfig(
            weight_bits=wv_cfg.weight_bits, cell_bits=wv_cfg.device.bc
        )
    if cost is None:
        cost = CircuitCost()
    use_fault = fault_cfg is not None and fault_cfg.any_faults
    use_remap = remap_cfg is not None and remap_cfg.spare_frac > 0.0
    if (use_fault or use_remap) and not batched:
        raise ValueError(
            "fault_cfg/remap_cfg require the batched deployment path"
        )
    with obs.span(
        "deploy", cat="deploy", method=wv_cfg.method.value, batched=batched,
    ) as sp:
        records, treedef = _eligible_leaves(params, deploy_embeddings, predicate)
        leaves: list = []
        slots: dict[str, int] = {}
        plans: list[_LeafPlan] = []
        with obs.span("deploy.plan", cat="deploy"):
            uid = 0
            for i, name, leaf, eligible in records:
                if not eligible:
                    leaves.append(leaf)
                    continue
                plan = _plan_leaf(name, leaf, wv_cfg, q_cfg, uid)
                uid += int(plan.cols.shape[0])
                slots[name] = len(leaves)
                plans.append(plan)
                leaves.append(None)
        sp["leaves"] = len(plans)

        arrays: dict[str, ArrayState] = {}
        if batched and not use_remap:
            g_blocks, stats_blocks, d2d_blocks, fault_blocks, loop_work = (
                pipeline.program_packed_columns(
                    key, [p.cols for p in plans], wv_cfg, cost,
                    mesh=mesh, min_bucket=min_bucket, max_bucket=max_bucket,
                    fault_cfg=fault_cfg if use_fault else None,
                )
            )
            for plan, g, st, d2d, fb in zip(
                plans, g_blocks, stats_blocks, d2d_blocks, fault_blocks
            ):
                arrays[plan.name] = plan.state(g, d2d, fault=fb)
            stats_map = {p.name: s for p, s in zip(plans, stats_blocks)}
            remapped = extra_columns = None
            loop_works = [loop_work]
        elif batched:
            # Two-pass spare-column deploy (DESIGN.md Sec. 15).  Pass A
            # programs every leaf's primary columns; the worst columns
            # (by give-up count) pick spare candidates DEVICE-SIDE; pass
            # B programs the spares at their own physical uids; the
            # remap table is decided device-side from both passes'
            # stats.  One host sync total, in `deploy.sync` below.
            c_counts = [int(p.cols.shape[0]) for p in plans]
            s_counts = [remap_mod.n_spares(c, remap_cfg) for c in c_counts]
            phys_counts = [c + s for c, s in zip(c_counts, s_counts)]
            if remap_cfg.placement and use_fault:
                sens = [
                    sensitivity(p.name, p.leaf) if sensitivity is not None
                    else 1.0 / max(pc, 1)
                    for p, pc in zip(plans, phys_counts)
                ]
                uid_arrays = remap_mod.plan_placement(
                    key, phys_counts, fault_cfg, sens,
                    provision=remap_cfg.placement_provision,
                )
                uid_end = max(
                    (int(u.max()) + 1 for u in uid_arrays if u.size), default=0
                )
            else:
                uid_arrays, base = [], 0
                for pc in phys_counts:
                    uid_arrays.append(base + np.arange(pc, dtype=np.int32))
                    base += pc
                uid_end = base
            prim_uids = np.concatenate(
                [ua[:c] for ua, c in zip(uid_arrays, c_counts)]
            )
            spare_uids = np.concatenate(
                [ua[c:] for ua, c in zip(uid_arrays, c_counts)]
            )
            fc = fault_cfg if use_fault else None
            g_blocks, stats_blocks, d2d_blocks, fault_blocks, loop_a = (
                pipeline.program_packed_columns(
                    key, [p.cols for p in plans], wv_cfg, cost,
                    mesh=mesh, min_bucket=min_bucket, max_bucket=max_bucket,
                    uids=prim_uids, pad_uid_base=uid_end, fault_cfg=fc,
                )
            )
            cands = [
                remap_mod.spare_candidates(st.gave_up, s)
                for st, s in zip(stats_blocks, s_counts)
            ]
            sg_blocks, sstats_blocks, sd2d_blocks, sfault_blocks, loop_b = (
                pipeline.program_packed_columns(
                    key,
                    [p.cols[cand] for p, cand in zip(plans, cands)],
                    wv_cfg, cost,
                    mesh=mesh, min_bucket=min_bucket, max_bucket=max_bucket,
                    uids=spare_uids, pad_uid_base=uid_end, fault_cfg=fc,
                )
            )
            remapped: dict[str, jax.Array] = {}
            stats_map: dict[str, WVStats] = {}
            remap_flags: dict[str, jax.Array] = {}
            cat = lambda a, b: jnp.concatenate([a, b])  # noqa: E731
            for plan, ua, c, cand, g, st, d2d, fb, sg, sst, sd2d, sfb in zip(
                plans, uid_arrays, c_counts, cands, g_blocks, stats_blocks,
                d2d_blocks, fault_blocks, sg_blocks, sstats_blocks,
                sd2d_blocks, sfault_blocks,
            ):
                table = remap_mod.build_table(
                    st.gave_up, cand, sst.gave_up, remap_cfg.min_gave_up
                )
                arrays[plan.name] = plan.state(
                    cat(g, sg),
                    cat(d2d, sd2d),
                    targets=cat(plan.cols, plan.cols[cand]),
                    fault=(
                        jax.tree.map(cat, fb, sfb) if fb is not None else None
                    ),
                    remap=table,
                    uids=ua,
                )
                stats_map[plan.name] = jax.tree.map(cat, st, sst)
                not_active = (~table.active[:c]).astype(jnp.float32)
                remapped[plan.name] = jnp.sum(not_active)
                # Per-column remap flags (physical order: primaries then
                # spares) for the per-tile health map.
                remap_flags[plan.name] = jnp.concatenate(
                    [not_active, jnp.zeros((len(ua) - c,), jnp.float32)]
                )
            extra_columns = {"remapped_columns": remap_flags}
            loop_works = [loop_a, loop_b]
        if batched:
            with obs.span("deploy.report", cat="deploy"):
                uids_map = {p.name: arrays[p.name].uids for p in plans}
                pending = DeployReport.reductions(
                    stats_map, remapped=remapped, loop_works=loop_works,
                    extra=_deploy_health_tree(
                        stats_map, uids_map, fault_cfg,
                        extra_columns=extra_columns,
                    ),
                )
            with obs.span("deploy.sync", cat="deploy"):
                fetched = pipeline.host_fetch(pending)
            with obs.span("deploy.fold", cat="deploy"):
                report = DeployReport.from_fetched(
                    stats_map, wv_cfg.n_cells, fetched
                )
                # Health/digest fold (DESIGN.md Sec. 16): the per-tile
                # reductions and deploy digests were fetched BY the
                # report's single host sync; folding them is pure host
                # work.
                tile_counts = _fold_deploy_health(fetched[-1])
                _fold_deploy(report, wv_cfg, cost)
        else:
            tile_counts = {"health_tile_range": 0, "health_tiles": 0}
            report = DeployReport()
            for plan in plans:
                state, stats = _program_plan(key, plan, wv_cfg, cost)
                with obs.span("deploy.sync", cat="deploy"):
                    report.merge(plan.name, stats, wv_cfg)
                arrays[plan.name] = state
            with obs.span("deploy.fold", cat="deploy"):
                _fold_deploy(report, wv_cfg, cost)
        sp["columns"] = report.num_columns
        sp["rms_cell_error_lsb"] = report.rms_cell_error_lsb
        sp["active_column_iterations"] = report.active_column_iterations
        sp["loop_column_iterations"] = report.loop_column_iterations
        sp["loop_compactions"] = report.loop_compactions
        sp.update(tile_counts)
    return (
        DeployedModel(
            treedef=treedef, leaves=leaves, slots=slots, arrays=arrays,
            wv_cfg=wv_cfg, cost=cost,
        ),
        report,
    )


def deploy_params(
    key: jax.Array,
    params: Any,
    wv_cfg: WVConfig,
    q_cfg: QuantConfig | None = None,
    cost: CircuitCost | None = None,
    *,
    deploy_embeddings: bool = False,
    predicate: Callable[[str, jax.Array], bool] | None = None,
    batched: bool = True,
    mesh: Any | None = None,
) -> tuple[Any, DeployReport]:
    """Program every eligible weight leaf of a parameter pytree.

    Returns (programmed_params, DeployReport).  Eligibility: ndim >= 2,
    plus the optional `predicate(path, leaf)`; embedding-like leaves
    (path contains 'embed') follow `deploy_embeddings`.

    This is the dense one-shot path: array state is collapsed to weights
    immediately.  Use `deploy_arrays` when the conductances must stay
    live (lifetime simulation, refresh).  Programming itself is shared
    with `deploy_arrays` (bucketed pipeline by default).
    """
    deployed, report = deploy_arrays(
        key, params, wv_cfg, q_cfg, cost,
        deploy_embeddings=deploy_embeddings, predicate=predicate,
        batched=batched, mesh=mesh,
    )
    return deployed.materialize(), report
