"""Per-(arch x shape x mesh) sharding assignments (DESIGN.md Sec. 4).

Parameters: FSDP over "data" on the input dim, TP over "model" on the
output dim; MoE experts EP-sharded over "model" with FSDP over "data" on
d_model; embeddings vocab-sharded over "model".  Caches: the *sequence*
axis shards over "model" (GQA kv-head counts of 4-8 cannot fill a
16-wide axis; sequence always can), batch over ("pod","data").
Non-divisible dims (15/25 heads, 1601 patches) rely on GSPMD padding.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed.sharding import ShardingRules
from repro.models import ModelConfig


def param_rules(cfg: ModelConfig) -> ShardingRules:
    rules = [
        # --- MoE expert stacks: (L, E, din, dout) ---
        (r"moe.*w_gate|moe.*w_up", P(None, "model", "data", None)),
        (r"moe.*w_down", P(None, "model", None, "data")),
        (r"moe.*router", P(None, None, None)),
        # --- embeddings / heads ---
        (r"tok_embed", P("model", "data")),
        (r"lm_head", P(None, "data", "model") if cfg.n_codebooks > 1
         else P("data", "model")),
        # --- rwkv6 ---
        (r"cm_v", P(None, "model", "data")),
        (r"cm_k|cm_r", P(None, "data", "model")),
        (r"w_r\b|w_k\b|w_v\b|w_g\b", P(None, "data", "model")),
        (r"w_o\b", P(None, "model", "data")),
        (r"decay_a|decay_b|decay_base|mix_|bonus_u|ln_x", P()),
        # --- ssm ---
        (r"ssm.*in_x|ssm.*in_z|ssm.*w_dt", P(None, "data", "model")),
        (r"ssm.*w_bc", P(None, "data", None)),
        (r"ssm.*a_log|ssm.*d_skip|ssm.*dt_bias", P()),
        (r"ssm.*out", P(None, "model", "data")),
        # --- attention / dense mlp stacks: (L, din, dout) ---
        (r"wq|wk\b|wv\b|w_gate|w_up", P(None, "data", "model")),
        (r"wo\b|w_down", P(None, "model", "data")),
        # norms, gates, scalars: replicated
    ]
    return ShardingRules(rules=rules, default=P())


def _sanitize(mesh: Mesh, spec: P, shape) -> P:
    """jit in_shardings require exact divisibility on ARGUMENT dims (GSPMD
    padding only applies to internal values).  Drop any axis assignment
    whose mesh extent does not divide the dim (e.g. vocab 32001, 1601
    image patches) — that dim is stored replicated instead."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    out = []
    for i, entry in enumerate(spec):
        if entry is None or i >= len(shape):
            out.append(None if i >= len(shape) else entry)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        extent = 1
        for a in axes:
            extent *= sizes.get(a, 1)
        out.append(entry if shape[i] % extent == 0 else None)
    return P(*out[: len(shape)])


def batch_axes(mesh: Mesh, global_batch: int):
    """Largest prefix of ("pod","data") that divides the batch."""
    axes = [a for a in ("pod", "data") if a in mesh.axis_names]
    sizes = {a: dict(zip(mesh.axis_names, mesh.devices.shape))[a] for a in axes}
    total = 1
    chosen = []
    for a in axes:
        if global_batch % (total * sizes[a]) == 0:
            chosen.append(a)
            total *= sizes[a]
    return tuple(chosen) if chosen else None


def batch_sharding(mesh: Mesh, tree: Any, global_batch: int) -> Any:
    ba = batch_axes(mesh, global_batch)

    def spec(x):
        nd = len(x.shape)
        if nd == 0:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, P(ba, *([None] * (nd - 1))))

    return jax.tree.map(spec, tree)


def cache_sharding(mesh: Mesh, cache_tree: Any, cfg: ModelConfig, global_batch: int) -> Any:
    """KV caches (L, B, S, KV, hd): seq over "model", batch over data axes.
    RWKV/SSM states shard their widest feature dim over "model"."""
    ba = batch_axes(mesh, global_batch)
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_tree)
    out = []
    for path, leaf in flat:
        name = jax.tree_util.keystr(path)
        nd = len(leaf.shape)
        if "pos" in name:
            spec = P()
        elif "wkv" in name:           # (L, B, H, hd, hd)
            spec = P(None, ba, "model", None, None)
        elif "shift" in name:         # (L, B, D)
            spec = P(None, ba, "model")
        elif "ssm_h" in name:         # (L, B, d, n)
            spec = P(None, ba, "model", None)
        elif nd == 5:                  # (L, B, S, KV, hd)
            spec = P(None, ba, "model", None, None)
        else:
            spec = P(*([None] * nd))
        out.append(NamedSharding(mesh, _sanitize(mesh, spec, leaf.shape)))
    return jax.tree_util.tree_unflatten(treedef, out)


def decode_batch_sharding(mesh: Mesh, cache_tree: Any) -> Any:
    """Continuous-batching decode cache: ONLY the batch axis shards,
    over "data" (DESIGN.md Sec. 18).

    Deliberately NOT `cache_sharding`: that spec also shards the
    sequence axis over "model", which splits each attention softmax
    reduction across devices and re-associates the float accumulation —
    breaking the scheduler's bit-identity contract (a request's tokens
    must be identical in any shard layout).  Sharding only the batch
    axis keeps every per-slot reduction local to one device: decode
    rows are independent, so the math per row is untouched and tokens
    stay bitwise equal to the unsharded run, while the decode batch and
    cache memory scale with the "data" axis.  Model/TP parallelism
    composes orthogonally: CIM tile planes keep sharding their output
    channels over "model" (`cim_weight_specs`).

    Batch sizes not divisible by the "data" extent fall back to
    replicated via `_sanitize` (jit argument dims must divide exactly),
    and extent-1 mesh axes are dropped entirely (`_drop_trivial`): GSPMD
    canonicalizes them away in jit OUTPUT shardings, so keeping them in
    the committed input sharding would make the second decode call see
    a "different" layout and silently re-lower the whole step — a
    hidden post-warmup compile the trace-count contract cannot see.
    Trailing unsharded dims are dropped from the spec for the same
    reason: jit outputs carry `P(None, "data")`, not `P(None, "data",
    None, None, None)`, and an admission that sees the other spelling
    misses the dispatch cache.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_tree)
    out = []
    for path, leaf in flat:
        name = jax.tree_util.keystr(path)
        nd = len(leaf.shape)
        if "pos" in name:              # (B,)
            spec = P("data")
        elif nd >= 2:                  # stacked (L, B, ...) layouts
            spec = P(None, "data", *([None] * (nd - 2)))
        else:
            spec = P(*([None] * nd))
        spec = _drop_trivial(mesh, _sanitize(mesh, spec, leaf.shape))
        out.append(NamedSharding(mesh, spec))
    return jax.tree_util.tree_unflatten(treedef, out)


def decode_vec_sharding(mesh: Mesh, n_slots: int) -> NamedSharding:
    """Sharding for the scheduler's per-slot (B,) vectors (cur tokens,
    rids, gens): batch over "data", matching `decode_batch_sharding`."""
    return NamedSharding(
        mesh, _drop_trivial(mesh, _sanitize(mesh, P("data"), (n_slots,)))
    )


def _drop_trivial(mesh: Mesh, spec: P) -> P:
    """Remove mesh axes of extent 1 and trailing unsharded dims from a
    spec (both are no-ops, and GSPMD strips them from jit output
    shardings)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    out = []
    for entry in spec:
        if entry is None:
            out.append(None)
            continue
        axes = tuple(
            a for a in (entry if isinstance(entry, tuple) else (entry,))
            if sizes.get(a, 1) > 1
        )
        out.append(axes if len(axes) > 1 else (axes[0] if axes else None))
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def cim_weight_specs(mesh: Mesh, w: Any) -> dict[str, NamedSharding]:
    """Sharding for one `cim.CIMWeight`'s children (analog serving TP).

    Tile planes g_pos/g_neg ([L,] T, S, R, M) and the dequant scale
    ([L,] M) shard their output-channel axis M over "model" — the same
    TP assignment the dense (L, din, dout) projections use, so the
    analog forward's per-slice ADC readouts stay local to the shard
    that consumes them.  Noise keys and the per-layer `layer_id` index
    are replicated (a few bytes).  Non-divisible M falls back to
    replicated via `_sanitize`.
    """
    def out_spec(arr):
        spec = P(*([None] * (arr.ndim - 1)), "model")
        return NamedSharding(mesh, _sanitize(mesh, spec, arr.shape))

    specs = {
        "g_pos": out_spec(w.g_pos),
        "g_neg": out_spec(w.g_neg),
        "scale": out_spec(w.scale),
        "key": NamedSharding(mesh, P()),
    }
    if w.layer_id is not None:
        specs["layer_id"] = NamedSharding(mesh, P())
    return specs


def shard_cim_weight(mesh: Mesh, w: Any) -> Any:
    """device_put a `CIMWeight`'s children onto the mesh per the specs."""
    import dataclasses

    specs = cim_weight_specs(mesh, w)
    return dataclasses.replace(
        w, **{k: jax.device_put(getattr(w, k), s) for k, s in specs.items()}
    )


def state_sharding(mesh: Mesh, state_tree: Any, cfg: ModelConfig) -> Any:
    """TrainState sharding: params + AdamW m/v share the param rules."""
    from repro.distributed.sharding import shard_params_tree

    rules = param_rules(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(state_tree)
    out = []
    for path, leaf in flat:
        name = jax.tree_util.keystr(path)
        if leaf.ndim == 0:
            out.append(NamedSharding(mesh, P()))
            continue
        spec = rules.spec(name)
        spec = P(*spec[: leaf.ndim]) if len(spec) > leaf.ndim else spec
        out.append(NamedSharding(mesh, _sanitize(mesh, spec, leaf.shape)))
    return jax.tree_util.tree_unflatten(treedef, out)
