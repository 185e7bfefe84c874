"""Plain reference of RRAM programming: quantize, slice, pack and
write-and-verify, one column at a time in `jax.numpy`.

It imports nothing of the program under test.  Two verify schemes:
HARP (N Hadamard-pattern reads, each compared one-shot against the
target's read on the ADC grid; ternary aggregate s_w = H^T s_y,
threshold tau_w; one fine pulse per iteration) and MRA (each cell read
alone M times with a full SAR conversion, averaged; pulses sized by the
estimated deviation).  It follows the program's documented conventions,
which are part of its output's meaning and are restated here:

* weights are quantized per output channel (absmax over the rows of the
  leaf flattened to (rows, M), B = 6 bits) in the leaf's own dtype, and
  split into a positive and a negative magnitude, each in base-2^Bc
  slices (LSB first);
* a leaf (rows, M) packs into columns ((rows / N) * M * 2 * S, N): row
  block, output channel, polarity, slice; the leaves of a model number
  their columns one after another in the order of the flattened pytree;
* column u draws every random field from its own stream
  `fold_in(key, u)`, split three ways into the device-to-device draw,
  the coarse write and the fine loop; iteration i of the fine loop uses
  `fold_in(k_loop, i)`, split into the verify read and the write.

`program` runs in `dtype`: float32 is the configuration's precision,
and bfloat16 is the lower-precision control.  Nothing here is timed.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Device:
    """RRAM cell model, in cell-LSB units (G_max = 2^Bc - 1)."""

    bc: int = 3
    fine_step: float = 0.25
    coarse_step: float = 1.25
    sigma_map_frac: float = 0.10
    nonlinearity: float = 0.35
    reset_asymmetry: float = 0.85
    sigma_c2c: float = 0.15
    sigma_d2d: float = 0.10

    @property
    def g_max(self) -> float:
        return float((1 << self.bc) - 1)

    def pulse_sigma(self, step: float) -> float:
        """Mapping noise per pulse of size `step`, scaled so that a
        full-swing coarse write accumulates sigma_map in all."""
        sigma_map = self.sigma_map_frac * self.g_max
        n_swing = self.g_max / self.coarse_step
        return float(sigma_map / n_swing**0.5 * (step / self.coarse_step))


@dataclasses.dataclass(frozen=True)
class WV:
    """Write-and-verify settings."""

    method: str = "harp"           # "harp" or "mra"
    n_cells: int = 32
    weight_bits: int = 6
    adc_bits: int = 9
    sigma_read: float = 0.7        # per-read noise, cell-LSB
    threshold: float = 0.5         # MRA decision / HARP compare dead zone
    tau_w: float = 4.0             # HARP threshold on s_w
    mra_reads: int = 5
    max_pulses: int = 16           # MRA pulse burst cap
    k_streak: int = 2
    freeze_warmup: int = 7         # iterations before a streak may freeze
    ternary_warmup_extra: int = 4  # ... and more for one-pulse schemes
    max_fine_iters: int = 50
    max_coarse_pulses: int = 10

    def __post_init__(self):
        if self.method not in ("harp", "mra"):
            raise ValueError(f"no reference for write-and-verify {self.method!r}")

    @property
    def ternary(self) -> bool:
        return self.method == "harp"

    @property
    def freeze_after(self) -> int:
        return self.freeze_warmup + (self.ternary_warmup_extra if self.ternary else 0)


# ------------------------------------------------------------ targets
def _quantize(w2, q_max):
    amax = jnp.max(jnp.abs(w2), axis=0, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / q_max
    return jnp.clip(jnp.round(w2 / scale), -q_max, q_max).astype(jnp.int32)


# Every operation rounds to its dtype, as it does when run op by op.
_quantize_jit = jax.jit(
    _quantize, compiler_options={"xla_allow_excess_precision": False}
)


def quantize(w: jax.Array, weight_bits: int) -> jax.Array:
    """Signed integer levels of a leaf flattened to (rows, M), per output
    channel, each operation rounded to the leaf's dtype."""
    w2 = w.reshape((-1, w.shape[-1]))
    return _quantize_jit(w2, jnp.asarray((1 << weight_bits) - 1, w2.dtype))


def leaf_columns(shape: tuple[int, ...], n: int, slices: int) -> int:
    rows = math.prod(shape[:-1])
    return -(-rows // n) * shape[-1] * 2 * slices


def column_targets(q: np.ndarray, cols: np.ndarray, n: int, bc: int,
                   slices: int) -> np.ndarray:
    """Target levels (len(cols), n) of columns `cols` of one leaf's
    signed levels q (rows, M): column ((b * M + m) * 2 + p) * S + s holds
    slice s of polarity p (0 positive, 1 negative) of output channel m
    over rows b*n .. b*n + n - 1 (rows past the end read 0)."""
    rows, m_out = q.shape
    s = cols % slices
    pol = (cols // slices) % 2
    m = (cols // (2 * slices)) % m_out
    block = cols // (2 * slices * m_out)
    r = block[:, None] * n + np.arange(n)[None, :]
    vals = np.where(r < rows, q[np.minimum(r, rows - 1), m[:, None]], 0)
    mag = np.maximum(np.where(pol[:, None] == 0, vals, -vals), 0)
    base = 1 << bc
    return ((mag // base ** s[:, None]) % base).astype(np.float32)


def targets_for(weights: dict[str, Any], uids: np.ndarray, wv: WV,
                dev: Device) -> np.ndarray:
    """Target levels of the columns `uids` of the flattened leaves."""
    slices = wv.weight_bits // dev.bc
    out = np.zeros((len(uids), wv.n_cells), np.float32)
    base = 0
    for w in jax.tree_util.tree_leaves(weights):
        c = leaf_columns(w.shape, wv.n_cells, slices)
        sel = (uids >= base) & (uids < base + c)
        if sel.any():
            q = np.asarray(quantize(w, wv.weight_bits), np.int64)
            out[sel] = column_targets(q, uids[sel] - base, wv.n_cells, dev.bc, slices)
        base += c
    return out


# ------------------------------------------------------------ programming
def _split(keys, num):
    ks = jax.vmap(lambda k: jax.random.split(k, num))(keys)
    return tuple(ks[:, j] for j in range(num))


def _normal(keys, shape, dtype):
    return jax.vmap(lambda k: jax.random.normal(k, shape, jnp.float32))(keys).astype(dtype)


def hadamard(x: jax.Array) -> jax.Array:
    """Unnormalized Walsh-Hadamard transform along the last axis:
    log2 N butterfly stages, pairing elements 2^s apart as (a+b, a-b)."""
    shape, n = x.shape, x.shape[-1]
    for s in range(n.bit_length() - 1):
        h = 1 << s
        y = x.reshape(shape[:-1] + (n // (2 * h), 2, h))
        a, b = y[..., 0, :], y[..., 1, :]
        x = jnp.concatenate([a + b, a - b], axis=-1).reshape(shape)
    return x


def _taper(g, up, dev: Device, dtype):
    """Per-pulse step efficiency at conductance g: SET weakens towards
    G_max, RESET towards 0 and by the asymmetry factor."""
    frac = jnp.clip(g / dtype(dev.g_max), 0.0, 1.0)
    set_eff = (1.0 - frac) ** dtype(dev.nonlinearity)
    reset_eff = frac ** dtype(dev.nonlinearity) * dtype(dev.reset_asymmetry)
    return jnp.where(up, set_eff, reset_eff)


def _sar(y, lo, wv: WV, dev: Device, dtype):
    """A read converted by the verify ADC, whose range is the column's
    full scale N (2^Bc - 1) from `lo`."""
    fs = float(wv.n_cells * dev.g_max)
    width = fs / float(1 << wv.adc_bits)
    lo = dtype(lo)
    code = jnp.clip(jnp.round((jnp.clip(y, lo, lo + dtype(fs)) - lo) / dtype(width)),
                    0, (1 << wv.adc_bits) - 1)
    return lo + code * dtype(width)


def _adc_grid(y, wv: WV, dev: Device, dtype):
    """Hadamard reads on the ADC grid: the first (all-ones) row over
    [0, FS], the balanced rows over [-FS/2, FS/2]."""
    row = jnp.arange(wv.n_cells)
    fs = float(wv.n_cells * dev.g_max)
    return jnp.where(row > 0, _sar(y, -fs / 2.0, wv, dev, dtype),
                     _sar(y, 0.0, wv, dev, dtype))


def d2d_for(key: jax.Array, uids: jax.Array, n: int, dev: Device,
            dtype=jnp.float32) -> jax.Array:
    """The static device-to-device step efficiency of each cell."""
    keys = jax.vmap(lambda u: jax.random.fold_in(key, u))(uids)
    dt = jnp.dtype(dtype).type
    return 1.0 + dt(dev.sigma_d2d) * _normal(_split(keys, 3)[0], (n,), dtype)


def program(key: jax.Array, targets: jax.Array, uids: jax.Array, d2d: jax.Array,
            wv: WV, dev: Device, dtype=jnp.float32) -> jax.Array:
    """Conductances that `wv` programs into columns `uids` with targets
    (C, N) and efficiencies `d2d` (`d2d_for`): coarse open-loop SET from
    HRS, then the fine loop."""
    dt = jnp.dtype(dtype).type
    c, n = targets.shape
    targets = targets.astype(dtype)
    d2d = d2d.astype(dtype)
    keys = jax.vmap(lambda u: jax.random.fold_in(key, u))(uids)
    _, k_coarse, k_loop = _split(keys, 3)
    g_max = dt(dev.g_max)

    # Coarse SET: pulse counts from the nominal landing curve from g = 0.
    landings = [jnp.zeros((), dtype)]
    for _ in range(wv.max_coarse_pulses):
        g0 = landings[-1]
        landings.append(jnp.clip(
            g0 + dt(dev.coarse_step) * _taper(g0, True, dev, dt), 0.0, g_max))
    err = jnp.abs(jnp.stack(landings)[:, None, None] - targets[None])
    n_coarse = jnp.argmin(err, axis=0).astype(dtype)
    k_c2c, k_map = _split(k_coarse, 2)
    c2c = 1.0 + dt(dev.sigma_c2c) * _normal(k_c2c, (n,), dtype)
    nmap = dt(dev.pulse_sigma(dev.coarse_step)) * _normal(k_map, (n,), dtype)
    pulsed = n_coarse > 0
    step = dt(dev.coarse_step) * _taper(jnp.zeros_like(targets), True, dev, dt) * d2d
    g = jnp.zeros_like(targets) + step * n_coarse * c2c
    g = g + jnp.where(pulsed, nmap * jnp.sqrt(jnp.maximum(n_coarse, 1.0)), 0.0)
    g = jnp.where(pulsed, jnp.clip(g, 0.0, g_max), 0.0)

    if wv.ternary:
        t_grid = _adc_grid(hadamard(targets), wv, dev, dt)
    sigma_fine = dt(dev.pulse_sigma(dev.fine_step))

    def verify(k_v, g):
        """(decision in {-1, 0, +1}, +1 where g reads too high; pulses)."""
        k_uc, _ = _split(k_v, 2)
        if wv.ternary:
            # N Hadamard reads with read noise, each compared one-shot.
            noise = dt(wv.sigma_read) * _normal(k_uc, (1, n), dtype).reshape(c, n)
            diff = hadamard(g) + noise - t_grid
            sign = jnp.where(diff < -wv.threshold, -1.0,
                             jnp.where(diff > wv.threshold, 1.0, 0.0)).astype(dtype)
            agg, thr = hadamard(sign), wv.tau_w
            pulses = jnp.ones_like(g)
        else:
            # M one-hot reads per cell, SAR-converted over [0, FS], averaged.
            noise = dt(wv.sigma_read) * _normal(k_uc, (wv.mra_reads, n), dtype)
            reads = _sar(g[:, None, :] + noise, 0.0, wv, dev, dt)
            agg, thr = jnp.mean(reads, axis=1) - targets, wv.threshold
            pulses = jnp.clip(jnp.round(jnp.abs(agg) / dt(dev.fine_step)),
                              1.0, float(wv.max_pulses))
        decision = jnp.where(agg > thr, 1.0, jnp.where(agg < -thr, -1.0, 0.0))
        return decision.astype(dtype), pulses

    def body(st):
        it, g, streak, frozen = st
        k_it = jax.vmap(lambda k: jax.random.fold_in(k, it))(k_loop)
        k_v, k_w = _split(k_it, 2)
        decision, pulses = verify(k_v, g)
        streak = jnp.where(decision == 0.0, streak + 1, 0)
        frozen_new = frozen | ((it >= wv.freeze_after) & (streak >= wv.k_streak))
        # Write: pulses against the sign of the deviation; each pulse adds
        # mapping noise, a random walk over the burst.
        k_c2c, k_map = _split(k_w, 2)
        c2c = 1.0 + dt(dev.sigma_c2c) * _normal(k_c2c, (n,), dtype)
        nmap = sigma_fine * _normal(k_map, (n,), dtype)
        col_active = ~jnp.all(frozen, axis=-1, keepdims=True)
        act = (~frozen) & (decision != 0.0) & col_active
        n_p = jnp.where(act, pulses, 0.0).astype(dtype)
        direction = jnp.where(act, -decision, 0.0).astype(dtype)
        eff = _taper(g, direction > 0, dev, dt)
        delta = direction * dt(dev.fine_step) * eff * d2d * n_p * c2c
        nmap = nmap * jnp.sqrt(jnp.maximum(n_p, 1.0))
        g_new = jnp.clip(g + delta + jnp.where(n_p > 0, nmap, 0.0), 0.0, g_max)
        g = jnp.where(n_p > 0, g_new, g)
        return it + 1, g, streak, frozen_new

    def cond(st):
        it, _, _, frozen = st
        return (it < wv.max_fine_iters) & jnp.any(~frozen)

    init = (jnp.int32(0), g, jnp.zeros((c, n), jnp.int32), jnp.zeros((c, n), bool))
    return jax.lax.while_loop(cond, body, init)[1]


program_jit = jax.jit(program, static_argnames=("wv", "dev", "dtype"))
