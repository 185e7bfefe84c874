"""Bucketed whole-model programming pipeline (DESIGN.md Sec. 10).

Model deployment used to program one leaf at a time: every leaf shape
re-traced `program_columns`, and every leaf's report blocked on host
syncs — throwing away exactly the parallelism the paper buys (columns
are independent; the whole model is one giant column batch).

This module is the shared hot path for model-scale programming:

* `bucket_sizes` decomposes the total column count into a small menu of
  power-of-two buckets, so an arbitrary model compiles at most
  log2(max/min)+1 distinct dispatch shapes — and different models reuse
  the same compiled sizes.
* `get_program_fn` is the ONE jit cache for batched programming.  Both
  deployment (`core.programmer`) and scrubbing (`lifetime.refresh`)
  dispatch through it, so a refresh after a deploy hits warm compiles.
  Inputs are donated (targets/d2d buffers are bucket temporaries) and
  the column axis can be sharded over a device mesh.
* `program_packed_columns` runs many independently-packed column blocks
  (one per weight leaf) through the bucket dispatches and splits the
  results back per block.

Per-column RNG (see `core.rng`): every column draws from
``fold_in(key, uid)``, so a column's programmed value depends only on
(key, uid) — not on bucket boundaries or padding.  That is what makes
the bucketed path bit-identical to the per-leaf path.

The module also keeps two counters the benchmarks/tests assert on:
`compile_count()` (distinct traced dispatch shapes — must stay <= the
number of buckets) and `host_sync_count()` (`host_fetch` calls — a
batched deploy performs exactly one).  Since the obs refactor
(DESIGN.md Sec. 14) both live in the global telemetry registry
(`repro.obs.metrics.registry`, keys ``pipeline.compiles`` /
``pipeline.host_syncs``); the functions here are thin compatibility
wrappers over it.
"""

from __future__ import annotations

import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.obs import metrics as obs_metrics

from . import device as dev_mod
from . import rng
from .cost import CircuitCost
from .types import FaultConfig, WVConfig
from .wv import WVStats, ladder_loop_work, program_columns

__all__ = [
    "bucket_sizes",
    "get_program_fn",
    "program_packed_columns",
    "sample_d2d_for",
    "host_fetch",
    "loop_work",
    "compile_count",
    "host_sync_count",
    "reset_counters",
]

DEFAULT_MIN_BUCKET = 256
DEFAULT_MAX_BUCKET = 1 << 18

_FN_CACHE: dict = {}
_TRACED: set = set()

# Registry keys for the pipeline's contract counters (obs.metrics).
COMPILE_COUNTER = "pipeline.compiles"
SYNC_COUNTER = "pipeline.host_syncs"


def compile_count() -> int:
    """Distinct (config, bucket-shape) dispatches traced so far."""
    return int(obs_metrics.value(COMPILE_COUNTER))


def host_sync_count() -> int:
    """`host_fetch` device->host synchronizations performed so far."""
    return int(obs_metrics.value(SYNC_COUNTER))


def reset_counters() -> None:
    """Zero the pipeline's registry counters (the jit cache survives)."""
    obs_metrics.reset("pipeline.")


def host_fetch(tree):
    """The pipeline's single device->host transfer point (counted)."""
    return obs_metrics.fetch(tree, counter=SYNC_COUNTER)


def donates() -> bool:
    """Whether `get_program_fn` donates its targets/d2d arguments.

    Donation is skipped on CPU (unsupported there; jax only warns).
    Callers that keep a dispatched buffer alive (persistent ArrayState)
    must pass a copy when this is True.
    """
    return jax.default_backend() != "cpu"


def bucket_sizes(
    c_total: int,
    min_bucket: int = DEFAULT_MIN_BUCKET,
    max_bucket: int = DEFAULT_MAX_BUCKET,
) -> list[int]:
    """Greedy power-of-two decomposition of a column count.

    Returns bucket sizes summing to >= c_total, each a power of two in
    [min_bucket, max_bucket].  Only the LAST bucket is padded (by at
    most min_bucket - 1 columns), and the menu of possible sizes has
    log2(max/min)+1 entries, which bounds the jit cache.
    """
    assert min_bucket > 0 and min_bucket & (min_bucket - 1) == 0, min_bucket
    assert max_bucket >= min_bucket and max_bucket & (max_bucket - 1) == 0, (
        max_bucket
    )
    sizes: list[int] = []
    rem = c_total
    while rem >= min_bucket:
        s = min(max_bucket, 1 << (rem.bit_length() - 1))
        sizes.append(s)
        rem -= s
    if rem > 0 or not sizes:
        sizes.append(min_bucket)
    return sizes


def get_program_fn(
    cfg: WVConfig,
    cost: CircuitCost,
    mesh: Mesh | None = None,
    mesh_axes: tuple | None = None,
    with_fault: bool = False,
):
    """The shared batched-programming dispatch: (key, targets, d2d, col_ids).

    Returns a jitted callable ``fn(key, (C, N) targets, (C, N) d2d,
    (C,) col_ids) -> (g, WVStats)`` cached per (cfg, cost, mesh);
    ``fn.lower(...)`` lowers the same jitted program.  The
    targets/d2d buffers are donated (they are bucket temporaries); when
    `mesh` is given the column axis is sharded over `mesh_axes`
    (default: all mesh axes) with zero cross-device traffic inside the
    WV loop.

    With `with_fault=True` the callable takes a trailing
    :class:`device.FaultMap` of (C, N) leaves (persistent silicon state
    — never donated) and programs under it.  Fault-free dispatches keep
    their own cache entry, so turning faults on never invalidates the
    warm zero-fault compile.
    """
    cache_key = (cfg, cost, mesh, mesh_axes, with_fault)
    entry = _FN_CACHE.get(cache_key)
    if entry is None:

        if with_fault:
            def raw(key, targets, d2d, col_ids, fault):
                return program_columns(
                    key, targets, cfg, cost=cost, d2d=d2d, col_ids=col_ids,
                    fault=fault,
                )
        else:
            def raw(key, targets, d2d, col_ids):
                return program_columns(
                    key, targets, cfg, cost=cost, d2d=d2d, col_ids=col_ids
                )

        kw: dict = {}
        if donates():
            kw["donate_argnums"] = (1, 2)
        if mesh is not None:
            # Each device programs its own column shard with its own WV
            # loop (shard_map): columns are independent, so no collective
            # runs inside the loop, and the Pallas kernels — which XLA
            # cannot partition — see one device's shard.
            ax = mesh_axes if mesh_axes is not None else tuple(mesh.axis_names)
            col2, col1 = P(ax, None), P(ax)
            ins = (P(), col2, col2, col1)
            if with_fault:
                ins = ins + (dev_mod.FaultMap(col2, col2, col2),)
            outs = (col2, col1)  # prefix: all WVStats leaves
            # check_vma off: the body is purely per-shard (no collective),
            # and the WV loop's zero-initialised carries are unvarying.
            raw = jax.shard_map(
                raw, mesh=mesh, in_specs=ins, out_specs=outs, check_vma=False
            )
            def named(specs):
                return jax.tree.map(
                    lambda s: NamedSharding(mesh, s), specs,
                    is_leaf=lambda s: isinstance(s, P),
                )

            kw["in_shardings"] = named(ins)
            kw["out_shardings"] = named(outs)
        jfn = jax.jit(raw, **kw)

        def entry(key, targets, d2d, col_ids, *fault):
            tk = (cache_key, targets.shape)
            if tk not in _TRACED:
                _TRACED.add(tk)
                obs_metrics.inc(COMPILE_COUNTER)
                obs.instant(
                    "pipeline.compile", cat="pipeline",
                    bucket=int(targets.shape[0]), n_cells=int(targets.shape[1]),
                )
            return jfn(key, targets, d2d, col_ids, *fault)

        entry.lower = jfn.lower  # the dispatched program, for inspection
        _FN_CACHE[cache_key] = entry
    return entry


def sample_d2d_for(key, col_ids, shape, dev_cfg):
    """Per-column-stream d2d sample, mirroring `program_columns`' own
    key schedule (`k_d2d` = first of the column key's 3-way split) so a
    caller-side sample equals what the engine would draw internally."""
    k_d2d = rng.split(rng.fold_col_keys(key, col_ids), 3)[0]
    return dev_mod.sample_d2d(k_d2d, shape, dev_cfg)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def loop_work(
    iterations: jax.Array, take: int, shards: int, max_fine_iters: int
) -> jax.Array:
    """One bucket's WV-loop work as int32 ``[active, loop, compactions]``:
    `active` sums the iterations of its `take` real columns; `loop` is
    the column-trips its loops carried, Σ over each shard's ladder
    stages of trips x capacity (`wv.ladder_loop_work`, filler
    included); `compactions` counts the compacted stages that ran.
    ``active / loop`` is the loop's occupancy: the share of its
    column-trips spent on unfinished columns."""
    it = iterations.astype(jnp.int32)
    work, shrinks = jax.vmap(lambda x: ladder_loop_work(x, max_fine_iters))(
        it.reshape(shards, -1)
    )
    return jnp.stack([jnp.sum(it[:take]), jnp.sum(work), jnp.sum(shrinks)])


def program_packed_columns(
    key: jax.Array,
    blocks: Sequence[jax.Array],
    cfg: WVConfig,
    cost: CircuitCost | None = None,
    *,
    mesh: Mesh | None = None,
    mesh_axes: tuple | None = None,
    min_bucket: int = DEFAULT_MIN_BUCKET,
    max_bucket: int = DEFAULT_MAX_BUCKET,
    uid_base: int = 0,
    uids: jax.Array | None = None,
    pad_uid_base: int | None = None,
    fault_cfg: FaultConfig | None = None,
) -> tuple[
    list[jax.Array], list[WVStats], list[jax.Array],
    list[dev_mod.FaultMap] | list[None], jax.Array,
]:
    """Program many packed column blocks in a few bucketed dispatches.

    Each bucket is one dispatch of `program_columns` with per-column
    streams, so a bucket of at least ``2 * wv.COMPACT_FLOOR`` columns (per
    shard) compacts its unfinished columns into halving ladder stages
    inside that dispatch; smaller buckets run one loop.

    Args:
      key: master PRNG key (column sub-streams derive from it).
      blocks: list of (C_i, N) target-level arrays (e.g. one per leaf).
      cfg / cost: WV configuration and circuit constants.
      mesh / mesh_axes: optional device mesh to shard the column axis.
      min_bucket / max_bucket: power-of-two bucket bounds.
      uid_base: first column uid (block b's column j gets uid
        ``uid_base + sum(C_<b) + j``) — must match the per-leaf path's
        numbering for bit-identical results.  Filler uids for bucket
        padding start at ``uid_base + c_total``.
      uids: optional explicit (sum C_i,) int32 column uids overriding
        the contiguous numbering — the spare-column pass programs
        non-contiguous physical columns (`core.remap`).
      pad_uid_base: first filler uid (defaults to ``uid_base +
        c_total``); with explicit `uids` pass a value past the whole
        allocated uid range.
      fault_cfg: optional fault population; when set (and non-trivial),
        the silicon fault map is sampled per uid (same master key — a
        bucketed and a per-leaf deploy see the same silicon) and
        programming runs under it.  Returned per block so callers can
        persist it alongside d2d.

    Returns (g_blocks, stats_blocks, d2d_blocks, fault_blocks,
    works): the first four split back to the input block boundaries
    (`fault_blocks` is a list of None when no fault config is given);
    `works` is a (buckets, 3) int32 array of each bucket's
    ``[active, loop, compactions]`` (`loop_work`).  Everything
    stays on device; no host syncs.  The `deploy.dispatch` span covers
    the d2d draw and the dispatches, and ends when they are enqueued,
    not when the device finishes them.
    """
    if cost is None:
        cost = CircuitCost()
    sizes = [int(b.shape[0]) for b in blocks]
    c_total = sum(sizes)
    if c_total == 0:
        return [], [], [], [], jnp.zeros((0, 3), jnp.int32)
    n = int(blocks[0].shape[1])
    sizes_plan = bucket_sizes(c_total, min_bucket, max_bucket)
    # A bucket's columns split into equal contiguous shards, one WV loop
    # per device, so the loop's trip count is taken per shard.
    shards = (
        1 if mesh is None else
        math.prod(mesh.shape[a] for a in (mesh_axes or mesh.axis_names))
    )
    with obs.span(
        "deploy.dispatch", cat="pipeline",
        columns=c_total, buckets=len(sizes_plan), blocks=len(blocks),
    ):
        targets = jnp.concatenate(blocks, axis=0) if len(blocks) > 1 else blocks[0]
        targets = targets.astype(jnp.float32)
        if uids is None:
            uids = uid_base + jnp.arange(c_total, dtype=jnp.int32)
        else:
            uids = jnp.asarray(uids, jnp.int32)
            assert uids.shape == (c_total,), (uids.shape, c_total)
        if pad_uid_base is None:
            pad_uid_base = uid_base + c_total
        # d2d is sampled OUTSIDE the donated dispatch: it is persistent array
        # state (ArrayState.d2d) while the padded bucket buffers are
        # temporaries.  Same sub-streams as the engine would use internally.
        d2d = sample_d2d_for(key, uids, (c_total, n), cfg.device)
        # The fault map is persistent silicon state like d2d: sampled here
        # (salted key domain — write-noise streams are untouched) and passed
        # through every dispatch, never resampled inside.
        with_fault = fault_cfg is not None and fault_cfg.any_faults
        fault = (
            dev_mod.sample_fault_map(key, uids, (c_total, n), fault_cfg, cfg.device)
            if with_fault
            else None
        )

        fn = get_program_fn(
            cfg, cost, mesh=mesh, mesh_axes=mesh_axes, with_fault=with_fault
        )
        g_parts, stat_parts, loop_parts = [], [], []
        off = 0
        for size in sizes_plan:
            take = min(size, c_total - off)
            tb = targets[off : off + take]
            db = d2d[off : off + take]
            ub = uids[off : off + take]
            fb = (
                jax.tree.map(lambda x: x[off : off + take], fault)
                if with_fault else None
            )
            pad = size - take
            if pad:
                # Filler columns: zero targets, fresh uids past the real
                # range (their streams never alias a real column's), unit
                # d2d, inert fault rows.  Their rows are sliced off below.
                tb = jnp.pad(tb, ((0, pad), (0, 0)))
                db = jnp.pad(db, ((0, pad), (0, 0)), constant_values=1.0)
                ub = jnp.concatenate(
                    [ub, pad_uid_base + jnp.arange(pad, dtype=jnp.int32)]
                )
                if with_fault:
                    filler = dev_mod.empty_fault_map((pad, n))
                    fb = jax.tree.map(
                        lambda x, f: jnp.concatenate([x, f]), fb, filler
                    )
            elif donates():
                # A full-range slice short-circuits to the SAME array, so a
                # single exact-size bucket would donate the caller's block
                # (persistent ArrayState.targets) / the returned d2d.  Copy
                # before donating in that case only.
                if tb is targets:
                    tb = jnp.copy(tb)
                if db is d2d:
                    db = jnp.copy(db)
            fargs = (fb,) if with_fault else ()
            g_b, st_b = fn(key, tb, db, ub, *fargs)
            g_parts.append(g_b[:take])
            stat_parts.append(jax.tree.map(lambda x: x[:take], st_b))
            loop_parts.append(
                loop_work(st_b.iterations, take, shards, cfg.max_fine_iters)
            )
            off += take

        works = jnp.stack(loop_parts)
        g_all = jnp.concatenate(g_parts) if len(g_parts) > 1 else g_parts[0]
        stats_all = (
            jax.tree.map(lambda *xs: jnp.concatenate(xs), *stat_parts)
            if len(stat_parts) > 1
            else stat_parts[0]
        )
        g_blocks, stats_blocks, d2d_blocks, fault_blocks = [], [], [], []
        off = 0
        for c_i in sizes:
            g_blocks.append(g_all[off : off + c_i])
            stats_blocks.append(jax.tree.map(lambda x: x[off : off + c_i], stats_all))
            d2d_blocks.append(d2d[off : off + c_i])
            fault_blocks.append(
                jax.tree.map(lambda x: x[off : off + c_i], fault)
                if with_fault else None
            )
            off += c_i
    return g_blocks, stats_blocks, d2d_blocks, fault_blocks, works
