"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Mosaic compiles here for a described (not attached) v5e chip, so what
it refuses — bool relayouts, VMEM overruns — fails this file, not a run
on the chip.  The kernels are called directly with `interpret=False`,
not through the `ops` wrappers, which pick interpret mode off-TPU.  The
topology is described in a fixture, so only the worker that runs these
tests loads the TPU compiler.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.acim_vmm.acim_vmm import acim_vmm_tiled_pallas
from repro.kernels.fwht.fwht import fwht_pallas
from repro.kernels.wv_step.ref import WVCellParams
from repro.kernels.wv_step.wv_step import wv_cell_update_pallas

BUCKET = 1 << 18  # the deploy pipeline's largest column bucket

# Qwen3-0.6B projections as (K, M); K / 128 macro tiles of 128 rows.
QWEN3_06B = {"wq": (1024, 2048), "wo": (2048, 1024), "w_down": (3072, 1024)}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    return text


@pytest.mark.parametrize("n", [32, 64])
@pytest.mark.parametrize("ternary", [True, False])
def test_wv_step_compiles(one_chip, n, ternary):
    p = WVCellParams(
        threshold=4.0, k_streak=2, can_freeze=True, ternary=ternary,
        fine_step=0.25, max_pulses=16.0, g_max=7.0, nonlinearity=0.35,
        reset_asymmetry=0.85, nmap_sqrt_pulses=True,
    )
    f32, shape = jnp.float32, (BUCKET, n)
    _compiled_text(
        one_chip,
        lambda *a: wv_cell_update_pallas(*a, p, interpret=False),
        (shape, f32), (shape, f32), (shape, f32), (shape, jnp.int32),
        (shape, jnp.bool_), (shape, f32), (shape, f32), (shape, f32),
    )


def test_fwht_compiles(one_chip):
    _compiled_text(
        one_chip, lambda x: fwht_pallas(x, interpret=False),
        ((BUCKET, 32), jnp.float32),
    )


@pytest.mark.parametrize("leaf", sorted(QWEN3_06B))
@pytest.mark.parametrize("rows", [80, 640])  # 8 / 64 tokens x 10 DAC planes
def test_acim_vmm_tiled_compiles(one_chip, leaf, rows):
    k, m = QWEN3_06B[leaf]
    t, f32 = k // 128, jnp.float32
    _compiled_text(
        one_chip,
        lambda x, gp, gn, nz: acim_vmm_tiled_pallas(
            x, gp, gn, nz, bc=3, adc_bits=10, full_scale=2.0 * 128 * 7,
            interpret=False,
        ),
        ((rows, k), f32), ((t, 2, 128, m), f32), ((t, 2, 128, m), f32),
        ((t, 2, rows, m), f32),
    )
