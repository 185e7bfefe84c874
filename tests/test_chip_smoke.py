"""chip_smoke.py: refuses to run without a TPU, and its phases run end
to end on the CPU at the Qwen3 smoke widths (kernels interpreted)."""

import importlib.util
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(*args, cwd=REPO, script=SMOKE):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, script, *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


@pytest.mark.parametrize("chips", ["1", "4"])
def test_refuses_cpu(chips):
    r = _run("--chips", chips)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "no TPU" in r.stderr


def test_refuses_outside_checkout(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    lone.write_text(open(SMOKE).read())
    r = _run(cwd=str(tmp_path), script=str(lone))
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout


def test_compile_cache_location(tmp_path):
    """Without JAX_COMPILATION_CACHE_DIR the cache sits at a fixed path
    in the checkout; with it, JAX's own setting is left alone."""
    code = (
        "import jax; from repro.launch.compile_cache import enable_compile_cache;"
        "p = enable_compile_cache(); print(p); print(jax.config.jax_compilation_cache_dir)"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=str(tmp_path),
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    fixed = os.path.join(REPO, ".jax_cache")
    assert out.stdout.split() == [fixed, fixed]
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cc")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=str(tmp_path),
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [str(tmp_path / "cc")] * 2


@pytest.fixture(scope="module")
def smoke():
    return _load_smoke()


@pytest.fixture(scope="module")
def deployed(smoke):
    from repro.configs import get_smoke_config

    cfg = get_smoke_config(smoke.ARCH)
    wv, cim = smoke.smoke_configs()
    wv = wv.replace(max_fine_iters=12)
    params = smoke.init_params(jax.random.PRNGKey(0), cfg)
    dep, report, secs = smoke.program(params, wv, seed=0)
    return cfg, wv, cim, dep, report


def test_program_and_serve_phases(smoke, deployed):
    cfg, wv, cim, dep, report = deployed
    assert report.num_columns == dep.num_columns > 0
    assert set(dep.arrays) == {f"['layers']['{k}']" for k in smoke.LAYER_KEYS}
    size, secs, kernels = smoke.warm_bucket_seconds(dep, wv, seed=0)
    assert size == pipeline_bucket(dep) and secs > 0
    assert kernels == []  # interpreted kernels lower to no TPU custom call
    ex = smoke.build_executor(dep, cim, seed=0, n_layers=cfg.n_layers)
    reqs = smoke.make_requests(0, 4, (5, 20), 4, cfg.vocab_size)
    assert [len(r.prompt) for r in reqs] == [5, 10, 15, 20]
    res = smoke.serve(cfg, ex, reqs, n_slots=2, seed=0)
    assert res["tokens_served"] == 16 and res["host_syncs"] == res["decode_steps"]
    assert len(res["ttft_s"]) == 4 and min(res["ttft_s"]) > 0
    assert res["decode_kernels"] == []


def pipeline_bucket(dep):
    from repro.core import pipeline

    return pipeline.bucket_sizes(dep.num_columns)[0]


def test_check_phases(smoke, deployed):
    cfg, wv, cim, dep, _ = deployed
    from repro.launch.mesh import make_debug_mesh

    ex = smoke.build_executor(dep, cim, seed=0, n_layers=cfg.n_layers)
    step = smoke.step_logits_agreement(cfg, ex, ex, make_debug_mesh(1, 1),
                                       n_slots=2, seed=0)
    assert step["bit_identical"] and step["argmax_agree"] == 1.0
    checks = smoke.kernel_agreement(wv, ex, n_cols=256, rows=20, seed=0)
    checks.update(smoke.programming_agreement(dep, wv, n_cols=64, seed=0))
    assert len(checks) == 3 + 2 * len(smoke.LAYER_KEYS) + 2
    for name, d in checks.items():
        assert d["bit_identical"], (name, d)
    ideal = smoke.ideal_agreement(cfg, dep, n_tokens=8, seed=0)
    assert ideal["max_abs_diff"] <= 1e-4 * max(ideal["max_abs_logit"], 1.0)
    assert ideal["argmax_agree"] == 1.0


_FOUR_SCRIPT = textwrap.dedent(
    """
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, sys.argv[1])
    import chip_smoke as smoke
    from repro.configs import get_smoke_config

    smoke.run_four_chips(get_smoke_config(smoke.ARCH), 0)
    print("FOUR-OK")
    """
)


@pytest.mark.skipif(
    (os.cpu_count() or 1) < 4,
    reason="forced multi-device host simulation hangs XLA backend init on <4 cores",
)
def test_four_chip_phase_on_virtual_devices():
    """The `--chips 4` phase on four CPU devices: the column-sharded deploy
    and the batch-sharded decode agree with their one-device runs."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", _FOUR_SCRIPT, REPO], env=env,
                       cwd=REPO, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "FOUR-OK" in r.stdout
    assert "0 of 8 requests differ" in r.stdout


def test_adc_code_flips(smoke):
    """A quantized disagreement passes only as rare whole-step flips over
    unquantized sums that agree to f32 rounding."""
    step = 1.75
    want = jnp.arange(4096, dtype=jnp.float32).reshape(64, 64) * step
    pre = want + 0.3
    flips = smoke.adc_code_flips(want.at[3, 5].add(8 * step), want,
                                 (pre, pre * (1 + 2.0**-23)), step)
    assert flips["elements_differing"] == 1 and flips["whole_adc_steps"]
    with pytest.raises(RuntimeError, match="whole ADC steps"):
        smoke.adc_code_flips(want.at[3, 5].add(0.5), want, (pre, pre), step)
    with pytest.raises(RuntimeError, match="f32 rounding"):
        smoke.adc_code_flips(want, want, (pre, pre + 0.01), step)
    with pytest.raises(RuntimeError, match="rare"):
        smoke.adc_code_flips(want + step, want, (pre, pre), step)


def test_compile_counter_sees_compiles(smoke):
    with smoke.CompileCounter() as c:
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
    assert c.events
    with smoke.CompileCounter() as c:
        pass
    assert c.events == []
