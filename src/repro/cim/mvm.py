"""Noisy analog matrix-vector multiply through programmed macro tiles.

The inference datapath of the paper's CBA macro (Fig. 2 / 6(b)), end to
end in cell-LSB units:

1. **Input DAC, bit-serial.**  Activations are scaled per token to a
   signed `dac_bits` code and streamed as binary row-drive planes —
   one plane per magnitude bit and polarity (positive and negative
   magnitudes drive separate phases; their ADC results subtract
   digitally).  ``dac_bits=None`` models an ideal analog driver: the
   raw activation drives the rows in a single plane.  The plane stack
   is built as one vectorized bit-extraction (no Python list append).
2. **Analog column sums + per-slice ADC, every tile at once.**  All
   planes multiply into EVERY macro tile's signed conductance pair in a
   single fused dispatch (`kernels/acim_vmm.acim_vmm_tiled`,
   `use_pallas`-gated with a bit-identical scanned reference): per-read
   TIA/ADC thermal noise lands on the analog partial sums, the fused
   clamp+quantize ADC epilogue and 2^(Bc*l) slice recombination run per
   tile, and tiles sum over the row partition — all inside the one
   kernel.  Noise for the whole (tile, plane, token) lattice is drawn
   by ONE batched `sample_token_read_noise` call.
3. **Digital recombination.**  Plane outputs recombine with their
   bit weights and the per-token DAC scale, and the per-output-channel
   quantization scale dequantizes to model units.

Read-noise RNG policy (DESIGN.md Sec. 17): every read draws from

    leaf key -> [uid] -> [layer] -> tile -> plane -> token_id

where the leaf `key` child is the executor's per-access key (swapped
every engine step), `uid`/`layer_id` ride the `CIMWeight` itself and
fold IN-JIT (so the executor's per-access rekey is one fold + a
broadcast, not a per-leaf vmap), and `token_id` defaults to the
flattened batch index but is overridden with the REQUEST id by the
serving scheduler (`token_stream_ids`).  A token's noise therefore
depends only on (access key, uid, layer, tile, plane, token id) — NOT
on which slot it occupies or how many other tokens share the batch —
so the analog forward is batch-composition-invariant.  The sampler
(`readout.noise.sample_token_read_noise`) and the per-slice ADC
quantizer (`readout.converter.sar_quantize`, reached through the
kernel epilogue) are the SAME models the WV verify path reads through
— one readout subsystem, DESIGN.md Sec. 12.

In the ideal limit (``dac_bits=None``, ``adc_bits=None``,
``sigma_read_lsb=0``) the whole pipeline collapses algebraically to
``x @ materialize(w)`` computed in f32 (reassociation-level error only)
— the materialize-vs-analog equivalence contract.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import rng
from repro.kernels.acim_vmm import ops as vmm_ops
from repro.readout import noise as ro_noise

from .tile import CIMWeight

__all__ = [
    "CIMConfig",
    "cim_vmm",
    "cim_matmul",
    "planes_per_token",
    "token_stream_ids",
    "current_token_ids",
    "batch_mesh",
]


@dataclasses.dataclass(frozen=True)
class CIMConfig:
    """Analog inference configuration (static under jit).

    `None` for dac_bits/adc_bits selects the ideal converter on that
    side — the knobs the equivalence contract turns to infinity.
    """

    macro_rows: int = 128            # max rows per crossbar macro tile
    dac_bits: int | None = 6         # input DAC resolution; None = ideal analog
    adc_bits: int | None = 10        # per-slice column ADC; None = ideal
    full_scale_frac: float = 1.0     # ADC range as fraction of +-R*(2^Bc-1)
    sigma_read_lsb: float = 0.0      # per-read TIA/ADC noise std (cell-LSB)
    use_pallas: bool = False         # fused Pallas kernel (interpret off-TPU)

    def __post_init__(self):
        # dac_bits counts sign + magnitude: >= 2 leaves >= 1 magnitude
        # bit; 1 would stream zero planes.
        if self.dac_bits is not None and self.dac_bits < 2:
            raise ValueError(f"dac_bits must be >= 2 or None: {self.dac_bits}")
        if self.adc_bits is not None and self.adc_bits < 1:
            raise ValueError(f"adc_bits must be >= 1 or None: {self.adc_bits}")
        if self.macro_rows < 1:
            raise ValueError(f"macro_rows must be >= 1: {self.macro_rows}")

    def replace(self, **kw) -> "CIMConfig":
        return dataclasses.replace(self, **kw)


def planes_per_token(cfg: CIMConfig) -> int:
    """Row-drive planes (= reads of every physical column) per token."""
    if cfg.dac_bits is None:
        return 1
    return 2 * (cfg.dac_bits - 1)  # magnitude bits x {pos, neg} phases


# --------------------------------------------------------------- token ids
# Ambient per-row token-id stream for the CIM noise sub-streams.  The
# serving scheduler wraps its jitted decode body in `token_stream_ids(
# rids)` so every analog leaf folds the REQUEST id (a traced argument of
# the compiled step — no retrace) instead of the flattened batch slot.
# Entered at trace time; the captured array is a tracer of the enclosing
# jit, which is exactly what makes the compiled step slot-invariant.
_TOKEN_IDS: list = []


@contextlib.contextmanager
def token_stream_ids(ids: jax.Array):
    """Route `ids` ((T,) int32) into every `cim_matmul` in the block."""
    _TOKEN_IDS.append(ids)
    try:
        yield
    finally:
        _TOKEN_IDS.pop()


def current_token_ids() -> jax.Array | None:
    """The ambient token-id stream, or None (= flattened batch index)."""
    return _TOKEN_IDS[-1] if _TOKEN_IDS else None


# Ambient batch mesh, entered at trace time like the token ids: the
# serving scheduler's batch-sharded steps wrap their bodies in
# `batch_mesh(mesh)`.  XLA cannot partition a Pallas kernel across
# devices, so under a mesh each device runs the leaf kernel on its own
# tokens ("data") and output channels ("model") inside `shard_map`.
_BATCH_MESH: list = []


@contextlib.contextmanager
def batch_mesh(mesh):
    """Run every `cim_matmul` in the block sharded over `mesh`: tokens
    over "data", output channels over "model" (None: no mesh)."""
    _BATCH_MESH.append(mesh)
    try:
        yield
    finally:
        _BATCH_MESH.pop()


def _through_tiles(planes, ids, g_pos, g_neg, *, key, cfg, bc, full_scale,
                   m_total=None, m_offset=0):
    """(P, T, K) row-drive planes through every macro tile -> (P, T, M).

    The read noise of tokens `ids` ((T,) int32) is drawn here, from the
    leaf `key` (None: clean path).  A token's draw depends only on its
    id, so drawing a device's own tokens gives the rows one device
    would.  Each key draws all `m_total` output channels (default: M);
    the `m_offset` window of M is kept.
    """
    p, t, k = planes.shape
    n_tiles, s, _, m = g_pos.shape
    noise = None
    if key is not None:
        noise = ro_noise.sample_token_read_noise(
            key, t, s, m_total or m, cfg.sigma_read_lsb,
            token_ids=ids, tiles=n_tiles, planes=p,
        )  # (T_tiles, S, P*T, m_total)
        if m_total is not None:
            noise = jax.lax.dynamic_slice_in_dim(noise, m_offset, m, axis=3)
    acc = vmm_ops.acim_vmm_tiled(
        planes.reshape(p * t, k), g_pos, g_neg, bc=bc, adc_bits=cfg.adc_bits,
        full_scale=full_scale, noise=noise, use_pallas=cfg.use_pallas,
    )
    return acc.reshape(p, t, m)


def _over_mesh(tiles, mesh, planes, ids, g_pos, g_neg):
    """`tiles` on each device's tokens and output channels.

    planes (P, T, K) and `ids` split T over "data"; the tile planes
    (Ti, S, R, M) split M over "model" where M divides, as
    `launch.shardings.cim_weight_specs` places them.  A token's plane
    rows and read noise, and an output channel's ADC readouts, are
    independent of the others, so every device computes exactly what
    one device would, with no collective.  Tokens are zero-padded to a
    multiple of the "data" extent and the padding is sliced off.
    Returns (P, T, M).
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    t, m = planes.shape[1], g_pos.shape[-1]
    n_data = sizes.get("data", 1)
    data = "data" if n_data > 1 else None
    model = "model" if sizes.get("model", 1) > 1 and m % sizes["model"] == 0 else None
    pad = (-t) % n_data
    if pad:
        planes = jnp.pad(planes, ((0, 0), (0, pad), (0, 0)))
    if ids is None:  # clean path: no draw reads them
        ids = jnp.zeros((t,), jnp.int32)
    ids = jnp.pad(ids.astype(jnp.int32), (0, pad))
    g_spec = P(None, None, None, model)

    def local(x, i, gp, gn):
        if model is None:
            return tiles(x, i, gp, gn)
        m_loc = gp.shape[-1]
        return tiles(x, i, gp, gn, m_total=m,
                     m_offset=jax.lax.axis_index("model") * m_loc)

    fn = jax.shard_map(
        local, mesh=mesh, in_specs=(P(None, data, None), P(data), g_spec, g_spec),
        out_specs=P(None, data, model), check_vma=False,
    )
    return fn(planes, ids, g_pos, g_neg)[:, :t]


def cim_vmm(
    x: jax.Array,
    g_pos: jax.Array,
    g_neg: jax.Array,
    *,
    bc: int,
    adc_bits: int | None,
    full_scale: float,
    noise: jax.Array | None = None,
    use_pallas: bool = False,
) -> jax.Array:
    """One macro-tile readout: the shared serving/benchmark entry point.

    (B, R) row drives x (S, R, M) signed slice pairs -> (B, M) f32, with
    pre-ADC `noise` (S, B, M) and the fused ADC epilogue.  Dispatches to
    the Pallas kernel (interpret mode off-TPU) or the bit-identical
    unfused reference.
    """
    return vmm_ops.acim_vmm(
        x, g_pos, g_neg, bc=bc, adc_bits=adc_bits, full_scale=full_scale,
        noise=noise, use_pallas=use_pallas,
    )


def _dac_stream(xf: jax.Array, cfg: CIMConfig) -> tuple[jax.Array, jax.Array]:
    """(T, K) f32 activations -> (P, T, K) row-drive planes, (P, T) weights.

    Ideal driver: one plane, unit weight.  Bit-serial: per-token absmax
    scaling to a signed `dac_bits` code, positive and negative magnitudes
    split into binary planes LSB-first; plane p recombines with weight
    +-2^bit * token_scale.  The whole plane stack is one broadcast bit
    extraction — plane order [pos b0..b_{n-1}, neg b0..b_{n-1}], the same
    stream order the per-plane loop produced.
    """
    if cfg.dac_bits is None:
        return xf[None], jnp.ones((1, xf.shape[0]), jnp.float32)
    n_mag = cfg.dac_bits - 1
    q_max = float((1 << n_mag) - 1)
    s_tok = jnp.max(jnp.abs(xf), axis=-1, keepdims=True) / q_max
    s_tok = jnp.maximum(s_tok, 1e-12)
    q = jnp.clip(jnp.round(xf / s_tok), -q_max, q_max).astype(jnp.int32)
    mag = jnp.stack([jnp.maximum(q, 0), jnp.maximum(-q, 0)])   # (2, T, K)
    bits = jnp.arange(n_mag, dtype=jnp.int32)
    planes = ((mag[:, None] >> bits[None, :, None, None]) & 1).astype(
        jnp.float32
    )                                                          # (2, n_mag, T, K)
    signs = jnp.array([1.0, -1.0], jnp.float32)
    bit_w = signs[:, None] * (2.0 ** bits.astype(jnp.float32))[None, :]
    weights = bit_w.reshape(-1)[:, None] * s_tok[:, 0][None, :]  # (P, T)
    t, k = xf.shape
    return planes.reshape(2 * n_mag, t, k), weights


def cim_matmul(
    x: jax.Array, w: CIMWeight, *, token_ids: jax.Array | None = None
) -> jax.Array:
    """Analog forward for one weight leaf: x (..., K) -> (..., M).

    Drop-in for `models.layers.matmul` (f32 accumulation, result cast to
    x.dtype) computing through the live conductance tiles instead of a
    materialized dense weight — ONE fused kernel dispatch and (when
    noisy) ONE batched noise draw for the whole leaf.  `token_ids`
    overrides the per-row noise sub-stream ids (default: ambient
    `token_stream_ids` context, else the flattened batch index).
    """
    cfg: CIMConfig = w.cfg
    if w.g_pos.ndim != 4:
        raise ValueError(
            f"CIMWeight {w.name!r}: tile planes must be layer-sliced 4-D "
            f"(T, S, R, M) at matmul time, got shape {w.g_pos.shape} — "
            "slice stacked leaves (tree.map / lax.scan) before the forward"
        )
    lead, k = x.shape[:-1], x.shape[-1]
    if k != w.rows_in:
        raise ValueError(
            f"CIMWeight {w.name!r}: input features {k} do not match the "
            f"leaf's {w.rows_in} input rows (tile geometry "
            f"{w.g_pos.shape} = (tiles, slices, rows, outputs))"
        )
    xf = x.reshape(-1, k).astype(jnp.float32)
    t = xf.shape[0]
    if token_ids is None:
        token_ids = current_token_ids()
    if token_ids is not None and token_ids.shape != (t,):
        raise ValueError(
            f"CIMWeight {w.name!r}: token_ids shape {token_ids.shape} does "
            f"not match the {t} flattened input rows"
        )

    planes, weights = _dac_stream(xf, cfg)        # (P, T, K), (P, T)
    p = planes.shape[0]
    n_tiles, s, r, m = w.g_pos.shape
    pad = n_tiles * r - k
    if pad:
        planes = jnp.pad(planes, ((0, 0), (0, 0), (0, pad)))

    key = None
    if cfg.sigma_read_lsb > 0.0:
        key = w.key
        if w.uid is not None:
            key = rng.fold_in(key, w.uid)
        if w.layer_id is not None:
            key = rng.fold_in(key, w.layer_id)
        if token_ids is None:
            token_ids = jnp.arange(t, dtype=jnp.int32)
    tiles = functools.partial(
        _through_tiles, key=key, cfg=cfg, bc=w.bc,
        full_scale=cfg.full_scale_frac * 2.0 * r * float(w.levels - 1),
    )
    mesh = _BATCH_MESH[-1] if _BATCH_MESH else None
    if mesh is None:
        acc = tiles(planes, token_ids, w.g_pos, w.g_neg)
    else:
        acc = _over_mesh(tiles, mesh, planes, token_ids, w.g_pos, w.g_neg)

    # Digital shift-and-add in f32: HIGHEST keeps the plane sums out of a
    # bf16 MXU pass on TPU.
    y = jnp.einsum(
        "pt,ptm->tm", weights, acc, precision=jax.lax.Precision.HIGHEST,
    )
    y = y * w.scale[None, :]
    return y.reshape(*lead, m).astype(x.dtype)
