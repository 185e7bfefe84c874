"""Column-wise write-and-verify engine (paper Secs. 3-4).

Implements all four WV schemes behind one vectorized loop:

  CW-SC  - column-wise single-cell baseline: one-hot verify reads with the
           compare-only ADC mode (ternary decision per cell, 1 fine
           pulse/iteration).  The paper's primary baseline.
  MRA-M  - multi-read averaging: M full-SAR one-hot reads per cell,
           averaged; magnitude estimate -> multi-pulse update.
  HD-PV  - Hadamard-encoded parallel verify: N Hadamard reads, full SAR,
           inverse-Hadamard (FWHT) decode; magnitude -> multi-pulse update.
  HARP   - Hadamard reads, compare-only vs the Hadamard-domain target
           (eq. 9), ternary aggregate s_w = H^T s_y (eq. 10), threshold
           tau_w (eq. 11); 1 fine pulse/iteration.

The verify READ itself — basis encode, noise sampling, converter
quantization, per-sweep cost — is owned by the shared readout subsystem
(`repro.readout`, DESIGN.md Sec. 12): each method is one point of the
basis x converter x averaging matrix (`readout.for_wv_method`), and this
module only owns the key schedule, the decision logic on the returned
measurements, and the write phase.

The engine runs a `lax.while_loop` over WV iterations for an arbitrary
batch of columns simultaneously, with per-cell freeze masks (streak
counter, Sec. 3.1) and per-column active masks — the idiomatic way to
batch heterogeneous convergence on SPMD hardware (no vmap-of-while).
With per-column streams the loop is a short static ladder of such loops
over halving column capacities (`compaction_ladder`): between stages
the unfinished columns are gathered into the next, smaller batch, so
the convergence tail stops paying for finished columns (DESIGN.md
Sec. 10).

Physical modelling notes:
* Verify reads always sense the WHOLE column (frozen cells keep
  contributing current); frozen cells merely ignore their decisions.
* mu_cm is redrawn per column per sweep and shared by every measurement
  in that sweep (incl. all M reads of MRA) — see readout.noise.
* Compare-mode targets are first quantized onto the ADC code grid (the
  comparator's DAC can only produce code levels) — readout owns that.
* Costs follow readout.cost / core.cost; per-column latency/energy
  accumulate only while the column is still active.
* An optional static per-column converter offset (`col_offset`,
  reference drift — readout.calibrate) biases every verify read.

Shapes: targets (C, N) float32 integer levels; returns g (C, N) and a
`WVStats` pytree of per-column diagnostics.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.readout import config as ro_config
from repro.readout import cost as ro_cost
from repro.readout import readout as ro

from . import device as dev_mod
from . import rng
from .cost import CircuitCost, write_phase_cost
from .types import WVConfig, WVMethod

__all__ = [
    "WVStats",
    "compaction_ladder",
    "ladder_loop_work",
    "program_columns",
    "verify_aggregate",
    "verify_sweep",
]

# Smallest batch the staged fine loop compacts into: below it a stage's
# gathers cost more than the trips they save.
COMPACT_FLOOR = 2048


class WVStats(NamedTuple):
    """Per-column WV diagnostics (all shape (C,)).

    The two give-up fields are appended LAST so positional consumers of
    the original seven fields keep working; both are identically zero
    unless `cfg.give_up_pulses` is set (DESIGN.md Sec. 15).
    """

    iterations: jax.Array      # fine WV sweeps executed while column active
    latency_ns: jax.Array      # verify + write critical-path latency
    energy_pj: jax.Array       # verify + write + decode energy
    reads: jax.Array           # ADC conversions / comparisons issued
    write_pulses: jax.Array    # total write pulses applied
    rms_error_lsb: jax.Array   # final per-column RMS |g - w*|
    frozen_frac: jax.Array     # fraction of cells frozen at termination
    gave_up: jax.Array         # cells declared unprogrammable (count)
    retry_pulses: jax.Array    # fine pulses burned on cells that gave up


def verify_aggregate(
    key: jax.Array,
    g: jax.Array,
    targets: jax.Array,
    cfg: WVConfig,
    col_offset: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, float]:
    """One verification sweep, stopping BEFORE the ternary threshold.

    The pre-threshold aggregate is what the fused Pallas cell-update
    kernel consumes (it applies the threshold in VMEM); `verify_sweep`
    applies it in jnp for the unfused path.  `key` may be a batch of
    per-column keys (batched-pipeline RNG policy).  The physical read is
    one `readout.read_columns` sweep under the method's readout config.

    Returns:
      agg:      (C, N) decision aggregate — the decoded deviation for
        magnitude methods, the comparator sign for CW-SC, the
        unnormalized s_w = H^T s_y for HARP.
      dev_mag:  (C, N) |deviation| estimate in LSB for magnitude methods
        (pulse sizing); 1.0 placeholder for ternary methods.
      n_compares: (C, N) comparator operations (compare modes) else zeros.
      threshold: static decision threshold such that
        decision = sign(agg) * (|agg| > threshold).
    """
    rcfg = ro_config.for_wv_method(cfg)
    thr = cfg.decision_threshold_lsb

    if cfg.method == WVMethod.CW_SC:
        res = ro.read_columns(key, g, rcfg, targets=targets, col_offset=col_offset)
        # The comparator already made the ternary call; 0.5 re-thresholds
        # its {-1, 0, +1} output to itself.
        return res.values, jnp.ones_like(g), res.n_compares, 0.5

    if cfg.method in (WVMethod.MRA, WVMethod.HD_PV):
        res = ro.read_columns(key, g, rcfg, col_offset=col_offset)
        w_hat = ro.decode_magnitude(res.values, rcfg)  # eq. 6 digital adders
        dev = w_hat - targets
        return dev, jnp.abs(dev), jnp.zeros_like(g), thr

    if cfg.method == WVMethod.HARP:
        res = ro.read_columns(key, g, rcfg, targets=targets, col_offset=col_offset)
        s_w = ro.decode_ternary(res.values, rcfg)  # unnormalized H^T s_y
        return s_w, jnp.ones_like(g), res.n_compares, cfg.tau_w

    raise ValueError(cfg.method)


def _threshold(agg: jax.Array, thr: float) -> jax.Array:
    return jnp.where(agg > thr, 1.0, jnp.where(agg < -thr, -1.0, 0.0))


def verify_sweep(
    key: jax.Array,
    g: jax.Array,
    targets: jax.Array,
    cfg: WVConfig,
    col_offset: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One verification sweep for a batch of columns.

    Returns:
      decision: (C, N) in {-1, 0, +1} = sign of estimated (g - w*) beyond
        the threshold; +1 means conductance too HIGH (needs RESET).
      dev_mag:  (C, N) |deviation| estimate in LSB for magnitude methods
        (pulse sizing); 1.0 placeholder for ternary methods.
      n_compares: (C, N) comparator operations (compare modes) else zeros.
    """
    agg, dev_mag, n_cmp, thr = verify_aggregate(key, g, targets, cfg, col_offset)
    return _threshold(agg, thr), dev_mag, n_cmp


def _characterized_coarse_pulses(
    targets: jax.Array, dev_cfg, max_pulses: int
) -> jax.Array:
    """Coarse pulse counts from the characterized (nominal) device response.

    Real WV controllers derive open-loop pulse counts from the device's
    programming look-up table (NeuroSim-style cumulative SET curve), not
    from target/step — otherwise the nonlinear taper near LRS leaves a
    large systematic undershoot at high levels.  The nominal curve starts
    from g = 0 for EVERY cell, so one scalar (P+1,) landing trajectory
    characterizes the whole batch; the per-cell argmin is a broadcast
    against the targets, not a (P, C, N) scan.
    """
    from .device import _effective_step

    def body(g_nom, _):
        g_next = jnp.clip(
            g_nom + _effective_step(g_nom, 1.0, dev_cfg, dev_cfg.coarse_step_lsb),
            0.0,
            dev_cfg.g_max_lsb,
        )
        return g_next, g_next

    g0 = jnp.zeros((), jnp.float32)
    _, traj = jax.lax.scan(body, g0, None, length=max_pulses)
    # landings[p] = nominal conductance after p pulses, shape (P+1,).
    landings = jnp.concatenate([g0[None], traj], axis=0)
    err = jnp.abs(landings.reshape((-1,) + (1,) * targets.ndim) - targets[None])
    return jnp.argmin(err, axis=0).astype(jnp.float32)


def compaction_ladder(c: int) -> tuple[int, ...]:
    """Column capacities of the staged fine loop for a C-column batch.

    ``C, C/2, C/4, ...`` while the next capacity stays at or above
    ``max(C/16, COMPACT_FLOOR)``; a one-entry ladder is the single loop
    (every batch under ``2 * COMPACT_FLOOR`` columns).  A pure function
    of the shape, so it is static under jit.
    """
    floor = max(c // 16, COMPACT_FLOOR)
    caps = [c]
    while caps[-1] // 2 >= floor:
        caps.append(caps[-1] // 2)
    return tuple(caps)


def ladder_loop_work(
    iterations: jax.Array, max_fine_iters: int
) -> tuple[jax.Array, jax.Array]:
    """Column-trips the staged fine loop carried, and its compactions.

    `iterations` is one loop's per-column `WVStats.iterations` (C,).
    Stage s runs while more than ``caps[s+1]`` columns are unfinished,
    so it ends at the first trip t with at most that many columns of
    more than t iterations; the last stage ends when none is left.
    Returns int32 scalars: Σ over stages of trips x capacity, and the
    number of compacted stages that ran at least one trip.  (Under a
    give-up budget a sweep in which a column's last unfrozen cells all
    exhaust is not counted in its iterations, so there the trips can be
    short by one per such sweep.)
    """
    caps = compaction_ladder(int(iterations.shape[0]))
    trips = jnp.arange(max_fine_iters + 1, dtype=jnp.int32)
    # left[t]: columns unfinished after t trips, non-increasing in t.
    left = jnp.sum(iterations.astype(jnp.int32)[None, :] > trips[:, None], axis=1)
    ends = [jnp.sum(left > k) for k in caps[1:] + (0,)]
    work = ends[0] * caps[0]
    shrinks = jnp.asarray(0, jnp.int32)
    for cap, start, end in zip(caps[1:], ends[:-1], ends[1:]):
        work = work + (end - start) * cap
        shrinks = shrinks + (end > start).astype(jnp.int32)
    return work, shrinks


class _ColInputs(NamedTuple):
    """Per-column inputs of the fine loop (rows gathered on compaction)."""

    targets: jax.Array
    d2d: jax.Array
    k_loop: jax.Array
    col_offset: jax.Array | None
    fault: dev_mod.FaultMap | None


class _LoopState(NamedTuple):
    g: jax.Array
    streak: jax.Array
    frozen: jax.Array
    it: jax.Array
    iters: jax.Array
    lat: jax.Array
    en: jax.Array
    reads: jax.Array
    pulses: jax.Array
    cell_pulses: jax.Array   # (C, N) fine pulses per cell (give-up budget)
    gave_up: jax.Array       # (C, N) cells frozen by budget exhaustion


def program_columns(
    key: jax.Array,
    targets: jax.Array,
    cfg: WVConfig,
    cost: CircuitCost | None = None,
    d2d: jax.Array | None = None,
    col_ids: jax.Array | None = None,
    col_offset: jax.Array | None = None,
    fault: dev_mod.FaultMap | None = None,
) -> tuple[jax.Array, WVStats]:
    """Program a batch of columns from HRS to integer target levels.

    Args:
      key: PRNG key.
      targets: (C, N) float32 target levels in [0, 2^Bc - 1].
      cfg: WV configuration (method, noise, ADC, device).
      cost: circuit cost constants (Table 1 defaults if None).
      d2d: optional pre-sampled (C, N) device-to-device efficiency.
      col_ids: optional (C,) int32 per-column stream ids.  When given,
        every column draws its noise from its own sub-stream
        ``fold_in(key, col_ids[c])`` (DESIGN.md Sec. 10), making the
        result per-column independent of batch composition/padding —
        the contract the bucketed deployment pipeline relies on.  When
        None, the legacy batch-shaped draws are used (same key schedule
        as pre-pipeline behaviour; the write-noise multiply was
        reassociated, so results match to the ulp, not bit-exactly).
      col_offset: optional (C,) static per-column converter reference
        offset biasing every verify read (readout.calibrate scenario).
      fault: optional static per-cell :class:`device.FaultMap` — weak
        cells see collapsed step efficiency, stuck cells never move.
        Sampled caller-side (like `d2d`) so refresh re-programs under
        the same silicon.  The verify key schedule is unconditional, so
        `fault=None` and an inert map are bit-identical.

    Compaction (DESIGN.md Sec. 10): with `col_ids` the fine loop runs
    as the static ladder of `compaction_ladder(C)`: stage s loops until
    at most the next stage's capacity of columns is unfinished, then the
    unfinished rows (state, targets, d2d, keys, offsets, fault rows) are
    gathered into that capacity and the stage's rows scattered back to
    full-size outputs.  The trip counter and `max_fine_iters` stay
    global, so every column sees the trips and draws it would see in
    one loop; the result is bit-identical to it.  Batches under
    ``2 * COMPACT_FLOOR`` columns, and the legacy path, run one loop.

    Give-up (DESIGN.md Sec. 15): with `cfg.give_up_pulses` set, a cell
    whose cumulative fine-pulse count reaches the budget at the start of
    a sweep is declared unprogrammable and folded into the frozen mask
    (same treatment the fused kernel already gives converged cells); the
    per-column count and the pulses burned on such cells are reported in
    `WVStats.gave_up` / `WVStats.retry_pulses`.  Magnitude methods may
    overshoot the budget by up to one burst (`max_pulses_per_iter - 1`)
    because the check runs at sweep granularity.  Cells still unfrozen
    at `max_fine_iters` also count as gave-up.  With the budget unset
    the decision logic is untouched and both stats are exactly zero.

    Returns (g_final, WVStats).
    """
    if cost is None:
        cost = CircuitCost()
    targets = targets.astype(jnp.float32)
    c, n = targets.shape
    assert n == cfg.n_cells, (n, cfg.n_cells)
    dev_cfg = cfg.device
    rcfg = ro_config.for_wv_method(cfg)

    if col_ids is None:
        k_d2d, k_coarse, k_loop = jax.random.split(key, 3)
    else:
        col_keys = rng.fold_col_keys(key, col_ids)
        k_d2d, k_coarse, k_loop = rng.split(col_keys, 3)
    if d2d is None:
        d2d = dev_mod.sample_d2d(k_d2d, targets.shape, dev_cfg)

    # ---- coarse OPEN-LOOP SET from HRS (Table 1: 4V, 5 steps/pulse, up to
    # max_coarse_iters pulses).  Fig. 8 shows coarse SET as a distinct
    # initialization before the WV loop: pulse counts come from the target
    # (no verify reads — coarse pays write cost only).  Per-pulse noise
    # accumulates as a random walk (device.map_noise_mode="pulse"), so the
    # residual entering the fine loop is ~ +-coarse_step/2 quantization plus
    # ~sigma_map of accumulated programming noise — the working point at
    # which HARP's tau_w=4 corresponds to the 0.5-LSB cell threshold.
    g = dev_mod.initial_state(targets.shape)
    n_coarse = _characterized_coarse_pulses(targets, dev_cfg, cfg.max_coarse_iters)
    direction0 = jnp.where(n_coarse > 0, 1.0, 0.0)
    g = dev_mod.apply_pulses(
        k_coarse, g, direction0, n_coarse, d2d, dev_cfg,
        step_lsb=dev_cfg.coarse_step_lsb, fault=fault,
    )
    lat0, en0 = write_phase_cost(g, n_coarse, direction0, dev_cfg, cost, coarse=True)
    pulses0 = jnp.sum(n_coarse, axis=-1)

    ternary = cfg.method in (WVMethod.CW_SC, WVMethod.HARP)
    reads_per_sweep = rcfg.reads_per_sweep
    # Freeze warmup (Sec. 3.1): streaks don't bite during the coarse-
    # residual transient; see types.WVConfig.freeze_warmup_iters.
    warmup = cfg.freeze_warmup_iters + (
        cfg.freeze_warmup_ternary_extra if ternary else 0
    )

    # Give-up budget: Python-level gate, so with the budget unset the
    # frozen mask fed to the decision logic is *literally* st.frozen and
    # the compiled decision stream is unchanged.
    budget = cfg.give_up_pulses

    def body(inp: _ColInputs, st: _LoopState) -> _LoopState:
        k_it = rng.fold_in(inp.k_loop, st.it)
        k_v, k_w = rng.split(k_it)

        if budget is not None:
            # Budget check at sweep start: unconverged cells that spent
            # their pulse budget are declared unprogrammable and treated
            # exactly like converged-frozen cells from here on.
            exhausted = (~st.frozen) & (st.cell_pulses >= float(budget))
            frozen_in = st.frozen | exhausted
            gave_up = st.gave_up | exhausted
        else:
            frozen_in = st.frozen
            gave_up = st.gave_up
        col_active = ~jnp.all(frozen_in, axis=-1)  # (C,)

        agg, dev_mag, n_cmp, thr = verify_aggregate(
            k_v, st.g, inp.targets, cfg, inp.col_offset
        )
        can_freeze = st.it >= warmup

        if cfg.use_pallas:
            # Fused verify-tail + write: threshold -> streak -> freeze ->
            # pulse-size -> device-step -> clip in ONE VMEM pass (the
            # kernel is deterministic: write noise is pre-sampled here
            # from the same key splits `apply_pulses` uses, so fused and
            # unfused paths are bit-identical).  `can_freeze` is static
            # inside the kernel; the warmup boundary picks between two
            # kernel instances via lax.cond.
            from repro.kernels.wv_step import ops as wv_ops
            from repro.kernels.wv_step.ref import WVCellParams

            c2c, nmap = dev_mod.sample_write_noise(k_w, st.g.shape, dev_cfg)
            # The kernel consumes a pre-multiplied efficiency field, so
            # weak/tile-degraded cells need no kernel change; stuck cells
            # are re-pinned after the update (same association as the
            # unfused apply_pulses path -> still bit-identical).
            d2d_eff = inp.d2d if inp.fault is None else inp.d2d * inp.fault.efficiency

            def upd(cf: bool):
                p = WVCellParams(
                    threshold=thr,
                    k_streak=cfg.k_streak,
                    can_freeze=cf,
                    ternary=ternary,
                    fine_step=dev_cfg.fine_step_lsb,
                    max_pulses=float(cfg.max_pulses_per_iter),
                    g_max=dev_cfg.g_max_lsb,
                    nonlinearity=dev_cfg.nonlinearity,
                    reset_asymmetry=dev_cfg.reset_asymmetry,
                    nmap_sqrt_pulses=dev_cfg.map_noise_mode == "pulse",
                )
                return wv_ops.wv_cell_update(
                    agg, dev_mag, st.g, st.streak, frozen_in, c2c, nmap,
                    d2d_eff, p
                )

            g, streak, frozen, n_p, direction = jax.lax.cond(
                can_freeze, lambda: upd(True), lambda: upd(False)
            )
            g = dev_mod.clamp_stuck(g, inp.fault)
        else:
            decision = _threshold(agg, thr)
            # Streak / freeze (Sec. 3.1): K consecutive in-threshold
            # verifies freeze a cell, gated behind the warmup.
            in_thr = decision == 0.0
            streak = jnp.where(in_thr, st.streak + 1, 0)
            frozen = frozen_in | (can_freeze & (streak >= cfg.k_streak))

            # Pulse sizing: ternary methods use single fine pulses;
            # magnitude methods apply round(|dev| / step) pulses (capped).
            if ternary:
                n_p = jnp.ones_like(st.g)
            else:
                n_p = jnp.clip(
                    jnp.round(dev_mag / dev_cfg.fine_step_lsb),
                    1.0,
                    float(cfg.max_pulses_per_iter),
                )
            act_cell = (~frozen_in) & (decision != 0.0) & col_active[:, None]
            n_p = jnp.where(act_cell, n_p, 0.0)
            direction = jnp.where(act_cell, -decision, 0.0)  # too high -> RESET

            g_new = dev_mod.apply_pulses(
                k_w, st.g, direction, n_p, inp.d2d, dev_cfg, fault=inp.fault
            )
            g = jnp.where(col_active[:, None], g_new, st.g)

        # Cost accounting (active columns only).
        lat_r, en_r = ro_cost.sweep_cost(
            rcfg, cost, n_compares=n_cmp if ternary else None
        )
        lat_w, en_w = write_phase_cost(st.g, n_p, direction, dev_cfg, cost)
        actf = col_active.astype(jnp.float32)
        return _LoopState(
            g=g,
            streak=streak,
            frozen=frozen,
            it=st.it + 1,
            iters=st.iters + actf,
            lat=st.lat + actf * (lat_r + lat_w),
            en=st.en + actf * (en_r + en_w),
            reads=st.reads + actf * reads_per_sweep,
            pulses=st.pulses + jnp.sum(n_p, axis=-1),
            cell_pulses=st.cell_pulses + n_p,
            gave_up=gave_up,
        )

    def cond(st: _LoopState) -> jax.Array:
        return (st.it < cfg.max_fine_iters) & jnp.any(~st.frozen)

    def stage_cond(nxt: int | None):
        """A ladder stage runs until at most `nxt` columns are unfinished;
        the last stage (`nxt` None) until none is."""
        if nxt is None:
            return cond
        return lambda st: (st.it < cfg.max_fine_iters) & (
            jnp.sum(~jnp.all(st.frozen, axis=-1)) > nxt
        )

    zero = jnp.zeros((c,), jnp.float32)
    init = _LoopState(
        g=g,
        streak=jnp.zeros(targets.shape, jnp.int32),
        frozen=jnp.zeros(targets.shape, bool),
        it=jnp.asarray(0, jnp.int32),
        iters=zero,
        lat=lat0,
        en=en0,
        reads=zero,
        pulses=pulses0,
        cell_pulses=jnp.zeros(targets.shape, jnp.float32),
        gave_up=jnp.zeros(targets.shape, bool),
    )
    inp = _ColInputs(targets, d2d, k_loop, col_offset, fault)
    # The legacy batch-shaped draws tie a column's noise to its batch, so
    # only per-column streams may compact.
    caps = (c,) if col_ids is None else compaction_ladder(c)
    nexts = caps[1:] + (None,)
    st = out = jax.lax.while_loop(
        stage_cond(nexts[0]), functools.partial(body, inp), init
    )
    pos = jnp.arange(c, dtype=jnp.int32) if len(caps) > 1 else None
    for cap, nxt in zip(caps[1:], nexts[1:]):
        # Compaction: gather the unfinished rows into `cap` slots.  A
        # finished column's state never changes again (its cells are all
        # frozen) and every draw comes from its own stream at the global
        # trip `it`, so dropping it is exact.  Filler slots are frozen
        # copies of row 0 that scatter nowhere (index c, dropped).
        unfinished = ~jnp.all(st.frozen, axis=-1)
        (rows,) = jnp.nonzero(unfinished, size=cap, fill_value=0)
        filler = jnp.arange(cap) >= jnp.sum(unfinished)
        take = lambda x: x[rows]  # noqa: E731
        inp = jax.tree.map(take, inp)
        rows_st = jax.tree.map(take, st._replace(it=None))
        st = rows_st._replace(it=st.it, frozen=rows_st.frozen | filler[:, None])
        pos = jnp.where(filler, c, pos[rows])
        st = jax.lax.while_loop(stage_cond(nxt), functools.partial(body, inp), st)
        # Every carried row's state as this stage left it; a later stage
        # overwrites the rows it carries on.
        out = jax.tree.map(
            lambda o, x: o.at[pos].set(x, mode="drop"),
            out._replace(it=None), st._replace(it=None),
        )._replace(it=st.it)
    st = out

    if budget is not None:
        # Cells still unfrozen at max_fine_iters never converged either.
        gave_up_cells = st.gave_up | ~st.frozen
        retry_pulses = jnp.sum(
            jnp.where(gave_up_cells, st.cell_pulses, 0.0), axis=-1
        )
        gave_up_count = jnp.sum(gave_up_cells.astype(jnp.float32), axis=-1)
    else:
        gave_up_count = zero
        retry_pulses = zero

    err = st.g - targets
    stats = WVStats(
        iterations=st.iters,
        latency_ns=st.lat,
        energy_pj=st.en,
        reads=st.reads,
        write_pulses=st.pulses,
        rms_error_lsb=jnp.sqrt(jnp.mean(err * err, axis=-1)),
        frozen_frac=jnp.mean(st.frozen.astype(jnp.float32), axis=-1),
        gave_up=gave_up_count,
        retry_pulses=retry_pulses,
    )
    return st.g, stats
