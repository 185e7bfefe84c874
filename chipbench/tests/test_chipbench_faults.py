"""A run whose timed path is broken underneath comes out not correct,
once for each fault the programming cell can have, and the control (the
reference in bfloat16 in the program's place) fails the check's limit.
The look for a chip is skipped; the rest is the run as on the chip, at
a tiny size."""

import jax.numpy as jnp
import pytest

import chipbench_tiny as tiny
from chipbench import peaks, run
from repro.core import pipeline


@pytest.fixture
def cpu_run(monkeypatch):
    monkeypatch.setattr(run, "_compile_cache", lambda: None)
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    # Programs built with the broken path must not outlive the test.
    monkeypatch.setattr(pipeline, "_FN_CACHE", {})
    monkeypatch.setattr(pipeline, "_TRACED", set())


def _unchanged(program):
    """The write-and-verify loop returns the state it was given."""
    def broken(key, targets, cfg, **kw):
        return program(key, targets, cfg.replace(max_fine_iters=0), **kw)
    return broken


def _half_left_out(program):
    """Half of each bucket's columns are never programmed."""
    def broken(key, targets, cfg, **kw):
        g, stats = program(key, targets, cfg, **kw)
        half = jnp.arange(g.shape[0])[:, None] < g.shape[0] // 2
        return jnp.where(half, g, 0.0), stats
    return broken


def _answer_altered(program):
    """One conductance of each bucket is moved by a fine pulse."""
    def broken(key, targets, cfg, **kw):
        g, stats = program(key, targets, cfg, **kw)
        return g.at[0, 0].add(0.25), stats
    return broken


@pytest.mark.parametrize("fault", [_unchanged, _half_left_out, _answer_altered])
def test_broken_timed_path_is_not_correct(fault, tmp_path, cpu_run, monkeypatch, capsys):
    monkeypatch.setattr(pipeline, "program_columns", fault(pipeline.program_columns))
    root = tiny.make_root(tmp_path)
    rc = run.main(["--workload", tiny.CELL, "--seed", "17", "--seconds", "0.5",
                   "--trace", "0"], root=root, require_tpu=False)
    out = tiny.last_json(capsys.readouterr().out)
    assert rc == 0
    assert out["correct"] is False
    check = out["checks"]["differing_columns_share"]
    assert check["value"] > check["limit"]


def test_lower_precision_control_fails(tmp_path, cpu_run):
    root = tiny.make_root(tmp_path)
    _, driver_mod, ctx = run.load_cell(tiny.CELL, 23, root=root, require_tpu=False)
    reading = driver_mod.control(ctx, deploys=1)
    assert reading > ctx.traffic["limits"]["differing_columns_share"]
    assert reading > 0.5
