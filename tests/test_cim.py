"""Analog compute-in-memory serving (repro.cim, DESIGN.md Sec. 11).

Covers the ISSUE-3 contracts:
* acim_vmm high-bit / zero-noise parity vs a float matmul across dtypes;
* tile pack -> unpack roundtrip vs the quant.pack layout;
* fused (Pallas) vs unfused reference bit-identity of the CIM forward;
* analog-served logits == digitally materialized logits in the ideal
  limit (DAC/ADC -> infinity, read noise -> 0);
* read-noise RNG policy: bit-reproducible across batch shapes, fresh
  per access;
* serving traffic -> real per-array read-disturb counts in lifetime;
* cost-model inference phase accounting.
"""

import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cim import (
    CIMConfig,
    CIMExecutor,
    CIMWeight,
    build_weight,
    cim_matmul,
    cim_vmm,
    planes_per_token,
    slice_planes,
    token_stream_ids,
)
from repro.cim.tile import rekey
from repro.core import ADCConfig, CircuitCost, WVConfig, WVMethod
from repro.core.cost import inference_token_cost
from repro.core.programmer import ArrayState, deploy_arrays
from repro.lifetime import DriftConfig, LifetimeSimulator, RefreshConfig, RefreshPolicy
from repro.models import ModelConfig, init_params
from repro.models.transformer import forward
from repro.quant import pack_columns, unpack_columns
from repro.serving import ServeEngine

IDEAL = CIMConfig(dac_bits=None, adc_bits=None, sigma_read_lsb=0.0)


# ------------------------------------------------------------------ helpers
def _tiny_cfg() -> ModelConfig:
    return ModelConfig(
        name="cim-test", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
        head_dim=16, d_ff=64, vocab_size=32, dtype=jnp.float32,
        attn_chunk_q=16, attn_chunk_kv=16, remat=False, tie_embeddings=False,
    )


@pytest.fixture(scope="module")
def deployed_tiny():
    cfg = _tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    wv = WVConfig(method=WVMethod.HARP, max_fine_iters=12, max_coarse_iters=4)
    deployed, _ = deploy_arrays(jax.random.PRNGKey(1), params, wv)
    return cfg, deployed


def _synthetic_state(key, k_in=48, m_out=20, n_cells=32, bc=3, slices=2):
    """Perfectly programmed ArrayState for a random int weight matrix."""
    q_max = (1 << (bc * slices)) - 1
    q = jax.random.randint(key, (k_in, m_out), -q_max, q_max + 1)
    scale = 0.01 * (1.0 + jnp.arange(m_out, dtype=jnp.float32))[None, :]
    cols, layout = pack_columns(q, n_cells, bc, slices)
    return ArrayState(
        g=cols, targets=cols, d2d=jnp.ones_like(cols), scale=scale,
        layout=layout, shape=(k_in, m_out), dtype=jnp.float32,
    ), q


# ------------------------------------------------- kernel-level parity
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("adc_bits", [None, 24])
def test_acim_vmm_highbit_zero_noise_is_float_matmul(dtype, adc_bits):
    """ADC bits -> infinity + zero noise collapses to the f32 matmul."""
    x = jax.random.normal(jax.random.PRNGKey(0), (9, 32)).astype(dtype)
    gp = jax.random.randint(jax.random.PRNGKey(1), (2, 32, 40), 0, 8).astype(jnp.float32)
    gn = jax.random.randint(jax.random.PRNGKey(2), (2, 32, 40), 0, 8).astype(jnp.float32)
    w_eff = sum(
        float(1 << (3 * l)) * (gp[l] - gn[l]) for l in range(2)
    )
    want = x.astype(jnp.float32) @ w_eff
    for use_pallas in (False, True):
        got = cim_vmm(
            x, gp, gn, bc=3, adc_bits=adc_bits, full_scale=2.0 * 32 * 7,
            use_pallas=use_pallas,
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=5e-3
        )


def test_acim_vmm_noise_enters_before_adc():
    """Noise shifts each slice's partial sum pre-quantization."""
    x = jnp.ones((1, 4))
    gp = jnp.array([[[2.0]] * 4])  # (1, 4, 1)
    gn = jnp.zeros((1, 4, 1))
    nz = jnp.full((1, 1, 1), 3.0)
    clean = cim_vmm(x, gp, gn, bc=3, adc_bits=None, full_scale=56.0,
                    use_pallas=False)
    noisy = cim_vmm(x, gp, gn, bc=3, adc_bits=None, full_scale=56.0,
                    noise=nz, use_pallas=False)
    np.testing.assert_allclose(np.asarray(noisy - clean), 3.0)


# ------------------------------------------------------ tile layout
def test_tile_roundtrip_matches_quant_pack():
    """slice_planes + slice recombination == quant.pack's unpack."""
    state, q = _synthetic_state(jax.random.PRNGKey(3))
    gp, gn = slice_planes(state.g, state.layout)
    w_signed = sum(
        float(1 << (state.layout.bc * l)) * (gp[l] - gn[l])
        for l in range(state.layout.slices)
    )
    np.testing.assert_allclose(
        np.asarray(w_signed),
        np.asarray(unpack_columns(state.g, state.layout)),
        rtol=0, atol=0,
    )
    np.testing.assert_array_equal(np.asarray(w_signed), np.asarray(q))


@pytest.mark.parametrize("macro_rows", [16, 32, 128])
def test_tiled_ideal_matmul_matches_materialize(macro_rows):
    """Ideal analog forward through tiles == x @ materialize()."""
    state, _ = _synthetic_state(jax.random.PRNGKey(4), k_in=70, m_out=12)
    cfg = dataclasses.replace(IDEAL, macro_rows=macro_rows)
    w = build_weight(state, cfg, jax.random.PRNGKey(5), name="t")
    assert w.tile_rows <= macro_rows
    x = jax.random.normal(jax.random.PRNGKey(6), (5, 70), jnp.float32)
    got = cim_matmul(x, w)
    want = x @ state.materialize(dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_stacked_weight_slices_like_dense_leaf():
    """A stacked CIMWeight sliced by tree.map equals per-layer tiling."""
    k_in, m = 64, 10
    state, q = _synthetic_state(jax.random.PRNGKey(7), k_in=k_in, m_out=m)
    stacked = dataclasses.replace(state, shape=(2, k_in // 2, m))
    w = build_weight(stacked, IDEAL, jax.random.PRNGKey(8), name="s")
    assert w.g_pos.ndim == 5 and w.g_pos.shape[0] == 2
    x = jax.random.normal(jax.random.PRNGKey(9), (3, k_in // 2), jnp.float32)
    dense = state.materialize(dtype=jnp.float32)  # (K, M)
    for idx in range(2):
        wl = jax.tree.map(lambda a: a[idx], w)
        assert isinstance(wl, CIMWeight)
        got = cim_matmul(x, wl)
        want = x @ dense[idx * (k_in // 2) : (idx + 1) * (k_in // 2)]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


# --------------------------------------------- fused vs unfused forward
def test_cim_forward_fused_vs_reference_bit_identical():
    """The full noisy bit-serial forward: Pallas == reference, bitwise."""
    state, _ = _synthetic_state(jax.random.PRNGKey(10), k_in=48, m_out=24)
    base = CIMConfig(dac_bits=5, adc_bits=9, sigma_read_lsb=0.4, macro_rows=32)
    key = jax.random.PRNGKey(11)
    w_ref = rekey(build_weight(state, base, key, name="b"), key)
    w_pal = rekey(
        build_weight(state, base.replace(use_pallas=True), key, name="b"), key
    )
    x = jax.random.normal(jax.random.PRNGKey(12), (6, 48), jnp.float32)
    y_ref = cim_matmul(x, w_ref)
    y_pal = cim_matmul(x, w_pal)
    np.testing.assert_array_equal(np.asarray(y_ref), np.asarray(y_pal))
    # and under jit
    y_ref_j = jax.jit(cim_matmul)(x, w_ref)
    y_pal_j = jax.jit(cim_matmul)(x, w_pal)
    np.testing.assert_array_equal(np.asarray(y_ref_j), np.asarray(y_pal_j))


def test_request_id_stream_batch_composition_invariant():
    """ISSUE-9 tentpole: request ids (not batch slots) key the CIM noise
    sub-streams, so a row's analog output depends only on its own id —
    bit-identical alone, in any slot, and under the ambient
    `token_stream_ids` context the serving scheduler installs."""
    state, _ = _synthetic_state(jax.random.PRNGKey(20), k_in=48, m_out=16)
    cfg = CIMConfig(dac_bits=4, adc_bits=9, sigma_read_lsb=0.4)
    key = jax.random.PRNGKey(21)
    w = rekey(build_weight(state, cfg, key, name="inv"), key)
    x = jax.random.normal(jax.random.PRNGKey(22), (5, 48), jnp.float32)
    ids = jnp.array([11, 3, 7, 5, 2], jnp.int32)
    y = cim_matmul(x, w, token_ids=ids)
    for row in (0, 2, 4):  # alone (batch of 1) vs inside the full batch
        y1 = cim_matmul(x[row : row + 1], w, token_ids=ids[row : row + 1])
        np.testing.assert_array_equal(np.asarray(y1[0]), np.asarray(y[row]))
    perm = jnp.array([4, 0, 3, 1, 2])  # same requests, shuffled slots
    y_shuf = cim_matmul(x[perm], w, token_ids=ids[perm])
    np.testing.assert_array_equal(np.asarray(y_shuf), np.asarray(y[perm]))
    with token_stream_ids(ids):  # scheduler-style ambient stream
        y_ctx = cim_matmul(x, w)
    np.testing.assert_array_equal(np.asarray(y_ctx), np.asarray(y))


_MESH_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import re
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.cim import CIMConfig, batch_mesh, build_weight, cim_matmul
    from repro.cim.tile import rekey
    from repro.core.programmer import ArrayState
    from repro.launch.mesh import make_debug_mesh
    from repro.launch.shardings import shard_cim_weight
    from repro.quant import pack_columns

    mesh = make_debug_mesh(2, 2)
    # (outputs, tokens): M divides "model" / not; T divides "data" / not.
    for m_out, t in ((24, 8), (21, 5)):
        q = jax.random.randint(jax.random.PRNGKey(10), (48, m_out), -63, 64)
        cols, layout = pack_columns(q, 32, 3, 2)
        state = ArrayState(
            g=cols, targets=cols, d2d=jnp.ones_like(cols),
            scale=jnp.full((1, m_out), 0.01), layout=layout,
            shape=(48, m_out), dtype=jnp.float32,
        )
        x = jax.random.normal(jax.random.PRNGKey(12), (t, 48), jnp.float32)
        ids = jnp.arange(100, 100 + t, dtype=jnp.int32)
        for pallas in (False, True):
            cfg = CIMConfig(dac_bits=5, adc_bits=9, sigma_read_lsb=0.4,
                            macro_rows=32, use_pallas=pallas)
            key = jax.random.PRNGKey(11)
            w = rekey(build_weight(state, cfg, key, name="b"), key)
            want = jax.jit(lambda x, w, i: cim_matmul(x, w, token_ids=i))(x, w, ids)
            ws = shard_cim_weight(mesh, w)
            # Like `decode_vec_sharding`: replicated where T does not divide.
            on_data = NamedSharding(mesh, P("data" if t % 2 == 0 else None))

            def f(x, w, i):
                with batch_mesh(mesh):
                    return cim_matmul(x, w, token_ids=i)

            args = (jax.device_put(x, on_data), ws, jax.device_put(ids, on_data))
            got = jax.jit(f)(*args)
            assert np.array_equal(np.asarray(got), np.asarray(want)), (m_out, t, pallas)
            split = m_out % 2 == 0
            assert ws.g_pos.sharding.spec == P(None, None, None, "model" if split else None)
            assert ("model" in got.sharding.spec) == split, got.sharding.spec
            if not split:
                continue  # padded tokens are gathered back: nothing to check
            # Planes, activations and outputs stay where they are: the only
            # collective moves the (uint32) noise keys.
            hlo = jax.jit(f).lower(*args).compile().as_text()
            moved = re.findall(
                r"= (\\w+)\\[[^\\]]*\\][^ ]* (?:all-gather|all-reduce|all-to-all|"
                r"collective-permute)\\(", hlo)
            assert set(moved) <= {"u32"}, moved
    print("CIM-MESH-OK")
    """
)


@pytest.mark.skipif(
    (os.cpu_count() or 1) < 4,
    reason="forced multi-device host simulation hangs XLA backend init on <4 cores",
)
def test_cim_matmul_on_data_model_mesh():
    """Under `batch_mesh` on a 2x2 (data, model) mesh the analog forward is
    bit-identical to one device, with tokens split over "data" and the
    tile planes left split over "model" (never gathered)."""
    res = subprocess.run(
        [sys.executable, "-c", _MESH_SCRIPT], capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=300,
    )
    assert res.returncode == 0, res.stdout + res.stderr
    assert "CIM-MESH-OK" in res.stdout, res.stdout + res.stderr


# ------------------------------------------------ RNG policy / noise
def test_read_noise_reproducible_across_batch_shapes():
    state, _ = _synthetic_state(jax.random.PRNGKey(13))
    cfg = CIMConfig(dac_bits=5, adc_bits=10, sigma_read_lsb=0.5)
    w = rekey(build_weight(state, cfg, jax.random.PRNGKey(14)),
              jax.random.PRNGKey(14))
    x2 = jax.random.normal(jax.random.PRNGKey(15), (2, 48), jnp.float32)
    x5 = jnp.concatenate(
        [x2, jax.random.normal(jax.random.PRNGKey(16), (3, 48), jnp.float32)]
    )
    y2 = cim_matmul(x2, w)
    y5 = cim_matmul(x5, w)
    np.testing.assert_array_equal(np.asarray(y2), np.asarray(y5[:2]))


def test_read_noise_fresh_per_access(deployed_tiny):
    cfg, deployed = deployed_tiny
    noisy = CIMConfig(dac_bits=5, adc_bits=10, sigma_read_lsb=0.5)
    toks = jax.random.randint(jax.random.PRNGKey(17), (2, 4), 0, cfg.vocab_size)
    ex = CIMExecutor(deployed, noisy, jax.random.PRNGKey(18))
    la, _, _ = forward(ex.tick(8), {"tokens": toks}, cfg)
    lb, _, _ = forward(ex.tick(8), {"tokens": toks}, cfg)
    assert float(jnp.max(jnp.abs(la - lb))) > 0.0
    # a fresh executor with the same master key replays access 1 exactly
    ex2 = CIMExecutor(deployed, noisy, jax.random.PRNGKey(18))
    lc, _, _ = forward(ex2.tick(8), {"tokens": toks}, cfg)
    np.testing.assert_array_equal(np.asarray(la), np.asarray(lc))


# ------------------------------------- end-to-end equivalence contract
def test_analog_serving_matches_materialized_logits(deployed_tiny):
    """ADC -> infinity, DAC -> infinity, noise -> 0: analog == digital."""
    cfg, deployed = deployed_tiny
    ex = CIMExecutor(deployed, IDEAL, jax.random.PRNGKey(19))
    assert len(ex._analog) == 8  # 7 layer projections + lm_head
    toks = jax.random.randint(jax.random.PRNGKey(20), (2, 6), 0, cfg.vocab_size)
    la, _, _ = forward(ex.params(), {"tokens": toks}, cfg)
    ld, _, _ = forward(deployed.materialize(), {"tokens": toks}, cfg)
    np.testing.assert_allclose(np.asarray(la), np.asarray(ld),
                               rtol=1e-4, atol=1e-5)


def test_serve_engine_analog_generate(deployed_tiny):
    """ServeEngine drives the executor: params per access, reads counted."""
    cfg, deployed = deployed_tiny
    ex = CIMExecutor(deployed, IDEAL, jax.random.PRNGKey(21))
    engine = ServeEngine(cfg, executor=ex)
    toks = jax.random.randint(jax.random.PRNGKey(22), (2, 4), 0, cfg.vocab_size)
    out = engine.generate(toks, max_new=3)
    assert out.shape == (2, 3)
    # prefill (2*4 tokens) + 2 decode accesses (2 tokens each)
    assert ex.tokens_served == 12
    reads = ex.drain_reads()
    assert set(reads) == set(ex._analog)
    assert all(v == 12.0 * ex.planes for v in reads.values())
    assert all(v == 0.0 for v in ex.drain_reads().values())  # drained


# --------------------------------------------- lifetime traffic wiring
def test_cim_reads_drive_read_disturb_drift(deployed_tiny):
    """Served traffic -> real per-array read counts -> measurable drift."""
    cfg, deployed = deployed_tiny
    ex = CIMExecutor(
        deployed, CIMConfig(dac_bits=6, adc_bits=10), jax.random.PRNGKey(23)
    )
    ex.tick(500)  # 500 served tokens of traffic
    drift_cfg = DriftConfig(
        read_disturb_lsb=1e-3, nu_drift=0.0, relax_frac=0.0,
        sigma_relax_lsb=0.0,
    )
    quiet = RefreshConfig(policy=RefreshPolicy.NONE)
    sim = LifetimeSimulator(
        jax.random.PRNGKey(24), deployed, drift_cfg, quiet,
        traffic_fn=ex.drain_reads,
    )
    g_before = {n: st.g for n, st in sim.states.items()}
    rec = sim.step_epoch(dt_s=1.0)
    expect = 500.0 * ex.planes
    analog, digital = 0, 0
    for name, st in sim.states.items():
        if name in ex._analog:
            assert float(st.reads[0, 0]) == expect, name
            # SET-ward read disturb moved unsaturated cells up
            moved = jnp.mean(st.g - g_before[name])
            assert float(moved) > 0.0, name
            analog += 1
        else:
            assert float(st.reads[0, 0]) == 0.0, name
            np.testing.assert_array_equal(
                np.asarray(st.g), np.asarray(g_before[name])
            )
            digital += 1
    assert analog == 8 and digital > 0
    assert rec.reads_per_column > 0.0
    # next epoch with no new traffic: counts drained, no further disturb
    rec2 = sim.step_epoch(dt_s=1.0)
    assert rec2.reads_per_column == 0.0


def test_executor_reviews_aged_arrays(deployed_tiny):
    """update_array (drift/refresh) is visible at the next params()."""
    cfg, deployed = deployed_tiny
    ex = CIMExecutor(deployed, IDEAL, jax.random.PRNGKey(25))
    name = "['layers']['wq']"
    before = ex.params()
    old_g = deployed.arrays[name].g
    try:
        deployed.update_array(name, old_g + 0.5)
        after = ex.params()
        b = before["layers"]["wq"].g_pos
        a = after["layers"]["wq"].g_pos
        assert float(jnp.max(jnp.abs(a - b))) > 0.0
    finally:
        deployed.update_array(name, old_g)
        ex.params()


# ------------------------------------------------------ cost accounting
def test_inference_token_cost_scales_with_planes():
    adc, cost = ADCConfig(), CircuitCost()
    l1, e1 = inference_token_cost(100, 50, planes=1, adc=adc, cost=cost)
    l8, e8 = inference_token_cost(100, 50, planes=8, adc=adc, cost=cost)
    assert l8 > l1 and e8 == pytest.approx(8 * e1)
    assert e1 > 0 and l1 > 0


def test_executor_token_cost(deployed_tiny):
    cfg, deployed = deployed_tiny
    ex = CIMExecutor(
        deployed, CIMConfig(dac_bits=6, adc_bits=10), jax.random.PRNGKey(26)
    )
    assert ex.planes == planes_per_token(ex.cfg) == 10
    lat, en = ex.token_cost()
    assert lat > 0 and en > 0
    ideal = CIMExecutor(deployed, IDEAL, jax.random.PRNGKey(27))
    lat1, en1 = ideal.token_cost()
    assert ideal.planes == 1 and lat1 < lat and en1 < en
    s = ex.summary()
    assert s["analog_leaves"] == 8 and s["planes_per_token"] == 10
