"""Bucketed whole-model programming pipeline (DESIGN.md Sec. 10).

Covers the ISSUE-2 contracts: bucketed-vs-per-leaf bit-identity, fused
Pallas wv_step-in-loop parity with the unfused engine and the ref
oracle, no-retrace bucketing (compiles <= buckets), the single-host-sync
stats path, the scalar coarse-pulse scan, and statistical equivalence of
the per-column RNG policy with the legacy batch-shaped draws, and the
exactness of the fine loop's active-column compaction.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import WVConfig, WVMethod, pipeline, program_columns
from repro.core.programmer import deploy_arrays, deploy_params
from repro.core.types import DeviceConfig, FaultConfig


@pytest.fixture(scope="module")
def small_params():
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    return {
        "blk0": {
            "w": jax.random.normal(ks[0], (40, 24)) * 0.05,
            "scale": jnp.ones((24,)),  # 1D: stays digital
        },
        "blk1": {
            "w": jax.random.normal(ks[1], (64, 16)) * 0.05,
            "w2": jax.random.normal(ks[2], (33, 20)) * 0.05,
        },
        "embed": jax.random.normal(ks[3], (64, 8)) * 0.05,  # excluded
    }


@pytest.fixture(scope="module")
def fast_cfg():
    return WVConfig(method=WVMethod.HARP, max_fine_iters=14)


def test_bucket_sizes():
    assert pipeline.bucket_sizes(480, 64) == [256, 128, 64, 64]
    assert pipeline.bucket_sizes(512, 64) == [512]
    assert pipeline.bucket_sizes(40, 64) == [64]
    assert pipeline.bucket_sizes(5000, 256, 1024) == [1024] * 4 + [512, 256, 256]
    for c, lo, hi in [(480, 64, 1 << 18), (7, 4, 16), (4097, 256, 1024)]:
        sizes = pipeline.bucket_sizes(c, lo, hi)
        assert sum(sizes) >= c
        assert sum(sizes) - c < lo  # only the last bucket pads
        assert all(s & (s - 1) == 0 and lo <= s <= hi for s in sizes)


def test_bucketed_matches_per_leaf(small_params, fast_cfg):
    """The tentpole contract: bucketed multi-leaf programming is
    BIT-identical to programming each leaf alone (per-column RNG
    sub-streams make results independent of batch composition)."""
    key = jax.random.PRNGKey(7)
    dep_b, rep_b = deploy_arrays(
        key, small_params, fast_cfg, batched=True, min_bucket=64
    )
    dep_l, rep_l = deploy_arrays(key, small_params, fast_cfg, batched=False)
    for name in dep_l.arrays:
        np.testing.assert_array_equal(
            np.asarray(dep_b.arrays[name].g), np.asarray(dep_l.arrays[name].g), name
        )
        np.testing.assert_array_equal(
            np.asarray(dep_b.arrays[name].d2d),
            np.asarray(dep_l.arrays[name].d2d),
            name,
        )
    for a, b in zip(
        jax.tree_util.tree_leaves(dep_b.materialize()),
        jax.tree_util.tree_leaves(dep_l.materialize()),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # Device-side collect and host-side merge agree on the aggregates.
    assert rep_b.num_columns == rep_l.num_columns
    assert rep_b.num_cells == rep_l.num_cells
    assert rep_b.mean_iterations == pytest.approx(rep_l.mean_iterations, rel=1e-5)
    assert rep_b.total_energy_pj == pytest.approx(rep_l.total_energy_pj, rel=1e-5)
    assert rep_b.critical_latency_ns == pytest.approx(
        rep_l.critical_latency_ns, rel=1e-6
    )
    assert rep_b.rms_cell_error_lsb == pytest.approx(
        rep_l.rms_cell_error_lsb, rel=1e-4
    )
    assert set(rep_b.leaves) == set(rep_l.leaves)
    assert all("embed" not in k and "scale" not in k for k in rep_b.leaves)


def test_deploy_params_delegates_to_pipeline(small_params, fast_cfg):
    key = jax.random.PRNGKey(3)
    dense, _ = deploy_params(key, small_params, fast_cfg)
    dep, _ = deploy_arrays(key, small_params, fast_cfg)
    for a, b in zip(
        jax.tree_util.tree_leaves(dense),
        jax.tree_util.tree_leaves(dep.materialize()),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_columns_independent_of_batch_composition(fast_cfg):
    """A column's programmed value depends only on (key, uid) — not on
    which other columns rode in the same dispatch."""
    key = jax.random.PRNGKey(11)
    t = jax.random.randint(jax.random.PRNGKey(2), (96, 32), 0, 8).astype(
        jnp.float32
    )
    ids = jnp.arange(96, dtype=jnp.int32)
    g_all, _ = program_columns(key, t, fast_cfg, col_ids=ids)
    g_sub, _ = program_columns(key, t[32:64], fast_cfg, col_ids=ids[32:64])
    np.testing.assert_array_equal(np.asarray(g_all[32:64]), np.asarray(g_sub))


def test_mesh_sharded_dispatch_matches(small_params, fast_cfg):
    """The column axis can be sharded over a mesh; results are unchanged
    (columns are independent — no cross-device traffic in the WV loop)."""
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("cols",))
    key = jax.random.PRNGKey(21)
    dep_m, _ = deploy_arrays(
        key, small_params, fast_cfg, batched=True, min_bucket=64, mesh=mesh
    )
    dep, _ = deploy_arrays(
        key, small_params, fast_cfg, batched=True, min_bucket=64
    )
    for name in dep.arrays:
        np.testing.assert_array_equal(
            np.asarray(dep_m.arrays[name].g), np.asarray(dep.arrays[name].g)
        )


def test_no_retrace_and_single_host_sync(small_params, fast_cfg):
    """Compile count <= number of buckets; redeploying the same shapes
    hits the warm cache; exactly one host sync per batched deploy."""
    dep, _ = deploy_arrays(
        jax.random.PRNGKey(0), small_params, fast_cfg, batched=True, min_bucket=64
    )
    n_buckets = len(pipeline.bucket_sizes(dep.num_columns, 64))
    # A config no other test dispatches -> its jit cache starts cold.
    cfg = fast_cfg.replace(max_fine_iters=9)
    pipeline.reset_counters()
    deploy_arrays(
        jax.random.PRNGKey(1), small_params, cfg, batched=True, min_bucket=64
    )
    assert 1 <= pipeline.compile_count() <= n_buckets
    assert pipeline.host_sync_count() == 1
    c0 = pipeline.compile_count()
    deploy_arrays(
        jax.random.PRNGKey(2), small_params, cfg, batched=True, min_bucket=64
    )
    assert pipeline.compile_count() == c0  # no retrace on redeploy
    assert pipeline.host_sync_count() == 2


@pytest.mark.parametrize(
    "method", [WVMethod.HARP, WVMethod.CW_SC, WVMethod.MRA, WVMethod.HD_PV]
)
def test_pallas_wv_step_in_loop_parity(method):
    """cfg.use_pallas routes the fine-WV cell update through the fused
    Pallas kernel; pre-sampled write noise makes it bit-identical to the
    unfused jnp path across ternary AND magnitude methods."""
    cfg = WVConfig(method=method, max_fine_iters=14)
    t = jax.random.randint(jax.random.PRNGKey(4), (64, 32), 0, 8).astype(
        jnp.float32
    )
    key = jax.random.PRNGKey(5)
    g0, s0 = jax.jit(lambda k, x: program_columns(k, x, cfg))(key, t)
    cfg_p = cfg.replace(use_pallas=True)
    g1, s1 = jax.jit(lambda k, x: program_columns(k, x, cfg_p))(key, t)
    np.testing.assert_allclose(np.asarray(g0), np.asarray(g1), atol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(s0.iterations), np.asarray(s1.iterations)
    )
    np.testing.assert_allclose(
        np.asarray(s0.energy_pj), np.asarray(s1.energy_pj), rtol=1e-5
    )


_COMPACT_CASES = [
    (method, faults, pallas)
    for method in (WVMethod.HARP, WVMethod.HD_PV, WVMethod.MRA, WVMethod.CW_SC)
    for faults, pallas in ((False, False), (True, False), (True, True))
]


@pytest.mark.parametrize(
    "method,faults,pallas", _COMPACT_CASES,
    ids=[f"{m.value}-{'faults' if f else 'clean'}-{'pallas' if p else 'jnp'}"
         for m, f, p in _COMPACT_CASES],
)
def test_compacted_bucket_bit_identical(method, faults, pallas):
    """An 8,192-column bucket runs the staged loop (8192 -> 4096 -> 2048
    columns); the same uids in 256-column buckets run the single loop.
    Per-column streams and invariant frozen columns make every
    conductance and every WVStats field bit-identical, with a fault map
    and a give-up budget as without, fused kernel or not."""
    cfg = WVConfig(
        method=method, use_pallas=pallas,
        give_up_pulses=40 if faults else None,
    )
    fc = (
        FaultConfig(p_stuck_hrs=0.01, p_stuck_lrs=0.005, p_weak=0.02)
        if faults else None
    )
    t = jax.random.randint(jax.random.PRNGKey(17), (8192, 32), 0, 8).astype(
        jnp.float32
    )
    key = jax.random.PRNGKey(18)
    out = {}
    for bucket in (8192, 256):
        g, st, _, _, works = pipeline.program_packed_columns(
            key, [t], cfg, min_bucket=bucket, max_bucket=bucket, fault_cfg=fc,
        )
        out[bucket] = (g[0], st[0], np.asarray(works))
    (g_c, st_c, w_c), (g_s, st_s, w_s) = out[8192], out[256]
    assert w_c[0, 2] >= 1 and w_s[:, 2].sum() == 0  # the ladder engaged
    if faults:
        assert float(jnp.sum(st_c.gave_up)) > 0  # the budget bit
    np.testing.assert_array_equal(np.asarray(g_c), np.asarray(g_s))
    for field, a, b in zip(st_c._fields, st_c, st_s):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), field)


def test_event_mode_noise_parity():
    """map_noise_mode="event" disables the kernel's sqrt(n) nmap scaling;
    fused and unfused paths must still agree."""
    cfg = WVConfig(
        method=WVMethod.HD_PV,
        max_fine_iters=10,
        device=DeviceConfig(map_noise_mode="event"),
    )
    t = jax.random.randint(jax.random.PRNGKey(6), (32, 32), 0, 8).astype(
        jnp.float32
    )
    key = jax.random.PRNGKey(8)
    g0, _ = program_columns(key, t, cfg)
    g1, _ = program_columns(key, t, cfg.replace(use_pallas=True))
    np.testing.assert_allclose(np.asarray(g0), np.asarray(g1), atol=1e-5)


def test_per_column_rng_statistically_equivalent():
    """The RNG policy change (batch-shaped draws -> per-column
    sub-streams) preserves the programming statistics (DESIGN.md
    Sec. 10): mean RMS error and iteration count agree within CLT
    noise on a 512-column batch."""
    cfg = WVConfig(method=WVMethod.HARP)
    t = jax.random.randint(jax.random.PRNGKey(9), (512, 32), 0, 8).astype(
        jnp.float32
    )
    key = jax.random.PRNGKey(10)
    _, s_legacy = jax.jit(lambda k, x: program_columns(k, x, cfg))(key, t)
    ids = jnp.arange(512, dtype=jnp.int32)
    _, s_v2 = jax.jit(lambda k, x, i: program_columns(k, x, cfg, col_ids=i))(
        key, t, ids
    )
    rms_a = float(jnp.mean(s_legacy.rms_error_lsb))
    rms_b = float(jnp.mean(s_v2.rms_error_lsb))
    assert rms_b == pytest.approx(rms_a, rel=0.15), (rms_a, rms_b)
    it_a = float(jnp.mean(s_legacy.iterations))
    it_b = float(jnp.mean(s_v2.iterations))
    assert it_b == pytest.approx(it_a, rel=0.15), (it_a, it_b)


def test_scalar_coarse_scan_matches_per_cell_reference():
    """The coarse look-up now scans ONE scalar nominal trajectory; it
    must reproduce the old per-cell (P, C, N) scan exactly."""
    from repro.core.device import _effective_step
    from repro.core.wv import _characterized_coarse_pulses

    dev = DeviceConfig()
    targets = jax.random.uniform(
        jax.random.PRNGKey(12), (37, 32), minval=0.0, maxval=7.0
    )

    def reference(targets, dev_cfg, max_pulses):  # the pre-PR per-cell scan
        def body(g_nom, _):
            g_next = jnp.clip(
                g_nom
                + _effective_step(
                    g_nom, jnp.ones_like(g_nom), dev_cfg, dev_cfg.coarse_step_lsb
                ),
                0.0,
                dev_cfg.g_max_lsb,
            )
            return g_next, g_next

        g0 = jnp.zeros_like(targets)
        _, traj = jax.lax.scan(body, g0, None, length=max_pulses)
        landings = jnp.concatenate([g0[None], traj], axis=0)
        err = jnp.abs(landings - targets[None])
        return jnp.argmin(err, axis=0).astype(jnp.float32)

    np.testing.assert_array_equal(
        np.asarray(_characterized_coarse_pulses(targets, dev, 10)),
        np.asarray(reference(targets, dev, 10)),
    )


def test_refresh_shares_pipeline_cache():
    """lifetime.refresh dispatches re-programming through the pipeline's
    shared entry point (same jit cache as deployment)."""
    from repro.core.cost import CircuitCost
    from repro.lifetime.drift import DriftConfig, init_cell_state
    from repro.lifetime.refresh import RefreshConfig, RefreshPolicy, apply_refresh

    cfg = WVConfig(method=WVMethod.HARP, max_fine_iters=12)
    cost = CircuitCost()
    targets = jax.random.randint(jax.random.PRNGKey(13), (64, 32), 0, 8).astype(
        jnp.float32
    )
    key = jax.random.PRNGKey(14)
    ids = jnp.arange(64, dtype=jnp.int32)
    d2d = pipeline.sample_d2d_for(key, ids, targets.shape, cfg.device)
    fn = pipeline.get_program_fn(cfg, cost)
    g, _ = fn(key, targets, d2d, ids)
    state = init_cell_state(
        jax.random.PRNGKey(15), g, d2d, cfg.device, DriftConfig()
    )
    pipeline.reset_counters()
    state, out = apply_refresh(
        jax.random.PRNGKey(16), state, targets, cfg, cost, DriftConfig(),
        RefreshConfig(policy=RefreshPolicy.PERIODIC, period_epochs=1), epoch=0,
    )
    assert out.n_reprogrammed == 64
    # (64, 32) was already traced by the deploy-style dispatch above:
    # the refresh re-program hit the warm cache.
    assert pipeline.compile_count() == 0
