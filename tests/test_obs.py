"""Telemetry contracts (DESIGN.md Sec. 14).

The obs subsystem's acceptance criteria live here:

* zero extra syncs / zero retraces — instrumentation rides existing
  fetches: a batched deploy still performs exactly ONE host sync, the
  scheduler still performs exactly one sync per decode step and stays
  retrace-free after warmup, with device metrics on;
* bit-neutrality — deployed conductances and served tokens are
  identical with instrumentation enabled and disabled;
* reset semantics — `obs.reset_all()` gives back-to-back benchmarks in
  one process independent counters/events/charges;
* the trace artifact round-trips: span/instant/ledger events export as
  Chrome/Perfetto trace-event JSON that `repro.obs.report` loads,
  summarizes, and renders (and rejects when empty or malformed);
* instrumentation overhead stays within budget on the decode hot path.
"""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import obs
from repro.core import CircuitCost, WVConfig, WVMethod, pipeline, program_columns
from repro.core.programmer import deploy_arrays
from repro.models import ModelConfig, init_params
from repro.obs import ledger, metrics, report, trace
from repro.serving import ContinuousScheduler, ServeEngine, poisson_requests


@pytest.fixture(autouse=True)
def _fresh_obs():
    """Every test starts and ends with clean telemetry state."""
    obs.reset_all()
    yield
    obs.reset_all()


# ------------------------------------------------------- MetricAccumulator
def test_accumulator_rides_jit_without_retrace():
    acc = metrics.MetricAccumulator.zeros(["tokens", "reads"])
    traces = []

    @jax.jit
    def step(acc, x):
        traces.append(1)  # trace-time side effect
        y = x * 2.0
        return acc.inc("tokens", 1.0).inc("reads", jnp.sum(y)), y

    for i in range(4):
        acc, _ = step(acc, jnp.full((8,), float(i)))
    assert len(traces) == 1, "accumulator operand retraced a warmed dispatch"
    got = jax.device_get(acc.as_dict())
    assert got["tokens"] == 4.0
    assert got["reads"] == sum(2.0 * i * 8 for i in range(4))


def test_accumulator_treedef_stable_and_merge():
    a = metrics.MetricAccumulator.zeros(["x", "y"]).inc("x", 3.0)
    b = metrics.MetricAccumulator.zeros(["x", "y"]).inc("y", 4.0)
    ta = jax.tree_util.tree_structure(a)
    tb = jax.tree_util.tree_structure(b)
    assert ta == tb  # same names => same treedef (no-retrace invariant)
    m = jax.device_get(a.merge(b).as_dict())
    assert (m["x"], m["y"]) == (3.0, 4.0)


def test_registry_fold_prefix_and_scoped_reset():
    metrics.inc("pipeline.compiles", 2)
    metrics.registry.fold({"tokens": 5, "reads": 7.5}, prefix="serve.")
    assert metrics.value("serve.tokens") == 5.0
    metrics.reset("serve.")
    assert metrics.value("serve.tokens") == 0.0
    assert metrics.value("pipeline.compiles") == 2.0  # other prefix survives
    metrics.reset()
    assert metrics.snapshot() == {}


def test_pipeline_counters_are_registry_backed():
    pipeline.reset_counters()
    base = pipeline.host_sync_count()
    pipeline.host_fetch(jnp.ones((4,)))
    assert pipeline.host_sync_count() == base + 1
    assert metrics.value(pipeline.SYNC_COUNTER) == base + 1
    pipeline.reset_counters()
    assert pipeline.host_sync_count() == 0


# ------------------------------------------------------------ trace/ledger
def test_span_instant_counter_events_and_disabled():
    with trace.span("phase.a", cat="t", n=1) as sp:
        sp["result"] = 42
    trace.instant("marker", cat="t")
    evs = trace.events()
    assert [e["ph"] for e in evs] == ["X", "i"]
    assert evs[0]["args"] == {"n": 1, "result": 42}
    assert evs[0]["dur"] >= 0
    with obs.disabled():
        with trace.span("phase.hidden"):
            pass
        ledger.charge("hidden", energy_pj=1.0)
    assert len(trace.events()) == 2  # nothing recorded while disabled
    assert ledger.summary() == {}


def test_ledger_accumulates_and_mirrors_into_trace():
    ledger.charge("deploy", energy_pj=10.0, latency_ns=5.0, reads=3.0)
    ledger.charge("deploy", energy_pj=2.5, tokens=4.0)
    s = ledger.summary()["deploy"]
    assert s["energy_pj"] == 12.5
    assert s["latency_ns"] == 5.0
    assert s["reads"] == 3.0
    assert s["tokens"] == 4.0
    assert s["n_charges"] == 2
    assert ledger.ledger.total("energy_pj") == 12.5
    mirrored = [e for e in trace.events() if e.get("cat") == "ledger"]
    assert len(mirrored) == 2 and mirrored[0]["name"] == "deploy"


def test_reset_all_isolates_back_to_back_benchmarks():
    # benchmark 1
    with trace.span("bench.one"):
        metrics.inc("pipeline.compiles")
        ledger.charge("one", energy_pj=1.0)
    assert trace.events() and ledger.summary() and metrics.snapshot()
    obs.reset_all()  # what benchmarks/run.py does between benchmarks
    # benchmark 2 sees a clean slate
    assert trace.events() == []
    assert ledger.summary() == {}
    assert metrics.snapshot() == {}
    with trace.span("bench.two"):
        pass
    evs = trace.events()
    assert [e["name"] for e in evs] == ["bench.two"]
    assert evs[0]["ts"] < 10e6  # clock rebased: fresh epoch, not process age


# ------------------------------------------------------------- report CLI
def test_trace_export_report_roundtrip(tmp_path, capsys):
    with trace.span("serve.decode", cat="serve"):
        time.sleep(0.001)
    with trace.span("serve.decode", cat="serve"):
        pass
    ledger.charge("serve.analog", tokens=8.0, energy_pj=100.0)
    path = tmp_path / "TRACE_t.json"
    trace.export(path)
    doc = report.load(str(path))
    # Perfetto structure: a dict with a traceEvents list of ph-events
    assert isinstance(doc["traceEvents"], list)
    assert all("ph" in e and "ts" in e for e in doc["traceEvents"])
    rows = {r["phase"]: r for r in report.summarize(doc)}
    assert rows["serve.decode"]["count"] == 2
    assert rows["serve.decode"]["total_ms"] > 0
    assert rows["serve.analog"]["tokens"] == 8.0
    assert rows["serve.analog"]["energy_pj"] == 100.0
    assert report.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "serve.decode" in out and "serve.analog" in out


def test_report_fails_on_empty_and_malformed(tmp_path):
    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps({"traceEvents": []}))
    assert report.main([str(empty)]) == 1  # no spans -> CI smoke fails
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert report.main([str(bad)]) == 1
    missing = tmp_path / "missing.json"
    assert report.main([str(missing)]) == 1
    notrace = tmp_path / "notrace.json"
    notrace.write_text(json.dumps({"foo": 1}))
    assert report.main([str(notrace)]) == 1


# ------------------------------------------------- deploy instrumentation
def _tiny_params():
    k = jax.random.split(jax.random.PRNGKey(0), 2)
    return {
        "wa": jax.random.normal(k[0], (32, 48)) * 0.02,
        "wb": jax.random.normal(k[1], (48, 32)) * 0.02,
        "norm": jnp.ones((32,)),
    }


def test_deploy_bit_neutral_and_single_sync():
    """Instrumented vs uninstrumented deploys: identical conductances;
    the batched deploy still syncs exactly once and re-deploys with
    zero new compiles (the PR 5 contracts, with obs in the path)."""
    params = _tiny_params()
    wv = WVConfig(method=WVMethod.HARP, max_fine_iters=8, max_coarse_iters=3)

    d_on, rep_on = deploy_arrays(jax.random.PRNGKey(1), params, wv)
    with obs.disabled():
        d_off, rep_off = deploy_arrays(jax.random.PRNGKey(1), params, wv)
    for name in d_on.arrays:
        np.testing.assert_array_equal(
            np.asarray(d_on.arrays[name].g), np.asarray(d_off.arrays[name].g)
        )
    assert rep_on.total_reads == rep_off.total_reads > 0
    assert rep_on.total_write_pulses == rep_off.total_write_pulses > 0

    pipeline.reset_counters()
    c0 = pipeline.compile_count()
    deploy_arrays(jax.random.PRNGKey(2), params, wv)
    assert pipeline.host_sync_count() == 1  # ONE sync, metrics included
    assert pipeline.compile_count() == c0  # warm: zero retraces
    # deploy fold landed in the registry and the ledger
    assert metrics.value("deploy.verify_reads") > 0
    assert metrics.value("deploy.write_pulses") > 0
    assert ledger.summary()["deploy"]["energy_pj"] > 0
    spans = [e["name"] for e in trace.events() if e["ph"] == "X"]
    assert "deploy" in spans and "deploy.dispatch" in spans


DEPLOY_SPANS = ("deploy", "deploy.plan", "deploy.dispatch", "deploy.report",
                "deploy.sync", "deploy.fold")


def test_deploy_spans_on_the_profiler_timeline(tmp_path):
    """Inside a profiler capture every deploy span is also a host-plane
    event, nested inside `deploy`, lasting what `obs.trace` recorded."""
    params = _tiny_params()
    wv = WVConfig(method=WVMethod.HARP, max_fine_iters=8, max_coarse_iters=3)
    deploy_arrays(jax.random.PRNGKey(1), params, wv)  # compile outside
    trace.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        deploy_arrays(jax.random.PRNGKey(2), params, wv)
    finally:
        jax.profiler.stop_trace()
    recorded = {e["name"]: e for e in trace.events() if e["ph"] == "X"}
    assert set(recorded) == set(DEPLOY_SPANS)
    (path,) = tmp_path.glob("**/*.xplane.pb")
    host: dict = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in DEPLOY_SPANS:
                        host.setdefault(e.name, []).append(e)
    assert {k: len(v) for k, v in host.items()} == dict.fromkeys(DEPLOY_SPANS, 1)
    deploy = host["deploy"][0]
    for name in DEPLOY_SPANS:
        (e,) = host[name]
        assert deploy.start_ns <= e.start_ns and e.end_ns <= deploy.end_ns, name
        assert abs(e.duration_ns / 1e3 - recorded[name]["dur"]) < 1e3, name


def _staged_loop_work(it: np.ndarray) -> tuple[int, int]:
    """Column-trips and compactions of one bucket loop, replayed from its
    per-column iterations: capacities halve from C while the next stays
    at or above max(C/16, 2048), and a stage runs while more columns are
    unfinished than the next stage holds (none, for the last)."""
    c = it.shape[0]
    caps = [c]
    while caps[-1] // 2 >= max(c // 16, 2048):
        caps.append(caps[-1] // 2)
    work = compactions = t = 0
    for s, cap in enumerate(caps):
        nxt = caps[s + 1] if s + 1 < len(caps) else 0
        start = t
        while t < it.max() and int((it > t).sum()) > nxt:
            t += 1
        work += (t - start) * cap
        compactions += int(s > 0 and t > start)
    return work, compactions


_OCCUPANCY_CASES = [
    (m, b) for b in (256, 8192) for m in (WVMethod.HARP, WVMethod.MRA)
]


@pytest.mark.parametrize(
    "method,bucket", _OCCUPANCY_CASES,
    ids=[m.value if b == 256 else f"{m.value}-{b}" for m, b in _OCCUPANCY_CASES],
)
def test_deploy_loop_occupancy_counters(method, bucket):
    """The loop counters ride the deploy's one host sync: `loop` is each
    bucket's column-trips, Σ over its ladder stages of trips x the
    stage's columns (filler included), `active` the real columns'
    iterations, and `deploy.loop_compactions` the compacted stages that
    ran, all equal to the buckets' loops run again through the shared
    dispatch.  256-column buckets run one loop (trips = the slowest
    column's iterations; HARP's run to the 50-trip cap here, MRA's end
    before it); one 8,192-column bucket compacts, which lifts its
    occupancy above the single loop's."""
    if bucket == 256:
        params = _tiny_params()  # 448 columns: 64 filler
    else:
        params = {"wc": jax.random.normal(jax.random.PRNGKey(4), (128, 512)) * 0.02}
    wv = WVConfig(method=method, max_coarse_iters=3)
    kw = dict(min_bucket=bucket, max_bucket=bucket)
    key = jax.random.PRNGKey(2)
    deploy_arrays(jax.random.PRNGKey(3), params, wv, **kw)  # warm
    pipeline.reset_counters()
    metrics.reset("deploy.")
    deployed, rep = deploy_arrays(key, params, wv, **kw)
    assert pipeline.host_sync_count() == 1
    assert pipeline.compile_count() == 0

    states = sorted(deployed.arrays.values(), key=lambda a: int(a.uids[0]))
    targets = jnp.concatenate([a.targets for a in states])
    uids = jnp.asarray(np.concatenate([a.uids for a in states]), jnp.int32)
    c = int(uids.shape[0])
    d2d = pipeline.sample_d2d_for(key, uids, targets.shape, wv.device)
    fn = pipeline.get_program_fn(wv, CircuitCost())
    active = loop = single = compactions = off = 0
    for size in pipeline.bucket_sizes(c, **kw):
        take = min(size, c - off)
        pad = size - take
        _, st = fn(
            key,
            jnp.pad(targets[off : off + take], ((0, pad), (0, 0))),
            jnp.pad(d2d[off : off + take], ((0, pad), (0, 0)), constant_values=1.0),
            jnp.concatenate([uids[off : off + take], c + jnp.arange(pad, dtype=jnp.int32)]),
        )
        it = np.asarray(st.iterations, np.int64)
        active += int(it[:take].sum())
        work, shrinks = _staged_loop_work(it)
        loop += work
        compactions += shrinks
        single += int(it.max()) * size
        off += take
    assert rep.active_column_iterations == active
    assert rep.loop_column_iterations == loop > active > 0
    assert rep.loop_compactions == compactions
    if bucket == 256:
        assert loop == single and compactions == 0
    else:
        assert compactions >= 1 and active / loop > active / single
    assert metrics.value("deploy.loop_column_iterations") == loop
    assert metrics.value("deploy.active_column_iterations") == active
    assert metrics.value("deploy.loop_compactions") == compactions
    (span,) = [e for e in trace.events() if e["name"] == "deploy" and e["ph"] == "X"][-1:]
    assert span["args"]["columns"] == c
    assert span["args"]["loop_column_iterations"] == loop
    assert span["args"]["active_column_iterations"] == active
    assert span["args"]["loop_compactions"] == compactions


def test_loop_work_counts_each_shards_ladder():
    """Under a mesh every device runs its own shard's ladder, so a
    bucket's loop work is the sum of its shards' replayed ladders."""
    rng = np.random.default_rng(0)
    it = np.minimum(rng.geometric(0.08, size=2 * 8192), 50).astype(np.float32)
    want = [_staged_loop_work(half) for half in np.split(it, 2)]
    got = np.asarray(pipeline.loop_work(jnp.asarray(it), 8000, 2, 50))
    assert got.tolist() == [
        int(it[:8000].sum()), sum(w for w, _ in want), sum(k for _, k in want),
    ]
    assert got[2] >= 2  # both shards compacted


def test_loop_work_equals_trips_run():
    """The counter is what the staged loop ran: the engine's loops,
    executed one trip at a time on the host, carry exactly the
    column-trips and compactions `pipeline.loop_work` derives from the
    iterations (4,096 columns: stages of 4,096 and 2,048)."""
    ran = []

    def counted(cond, body, carry):
        trips = 0
        while bool(cond(carry)):
            carry, trips = body(carry), trips + 1
        ran.append((trips, carry.g.shape[0]))
        return carry

    cfg = WVConfig(method=WVMethod.HARP)
    t = jax.random.randint(jax.random.PRNGKey(5), (4096, 32), 0, 8).astype(jnp.float32)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax.lax, "while_loop", counted)
        _, st = program_columns(
            jax.random.PRNGKey(6), t, cfg, col_ids=jnp.arange(4096, dtype=jnp.int32)
        )
    assert [cap for _, cap in ran] == [4096, 2048]
    _, loop, compactions = np.asarray(
        pipeline.loop_work(st.iterations, 4096, 1, cfg.max_fine_iters)
    ).tolist()
    assert loop == sum(trips * cap for trips, cap in ran)
    assert compactions == int(ran[1][0] > 0) == 1


# ----------------------------------------------- scheduler instrumentation
def _sched_cfg() -> ModelConfig:
    return ModelConfig(
        name="obs-test", n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
        head_dim=16, d_ff=64, vocab_size=64, dtype=jnp.float32,
        attn_chunk_q=16, attn_chunk_kv=16, remat=False, tie_embeddings=False,
    )


@pytest.fixture(scope="module")
def sched_model():
    cfg = _sched_cfg()
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def _run_stream(cfg, params, device_metrics):
    engine = ServeEngine(cfg, params, temperature=0.7)
    sched = ContinuousScheduler(
        engine, n_slots=3, max_len=48, key=jax.random.PRNGKey(5),
        device_metrics=device_metrics,
    )
    sched.warmup(prompt_range=(3, 10))
    warm = dict(sched.trace_counts)
    reqs = poisson_requests(
        3, 6, rate=0.5, vocab=cfg.vocab_size,
        prompt_lens=(3, 10), max_new=(2, 5),
    )
    recs = sched.run(reqs)
    return sched, warm, {r.rid: list(r.tokens) for r in recs}


def test_scheduler_device_metrics_bit_neutral(sched_model):
    """device_metrics on/off: identical served tokens, one sync per
    decode step, zero retraces after warmup — with spans recording."""
    cfg, params = sched_model
    s_on, warm_on, toks_on = _run_stream(cfg, params, device_metrics=True)
    s_off, _, toks_off = _run_stream(cfg, params, device_metrics=False)
    assert toks_on == toks_off  # bit-identical tokens
    for sched, warm in ((s_on, warm_on),):
        assert sched.host_syncs == sched.decode_steps  # ONE sync per step
        assert all(sched.trace_counts[k] == warm[k] for k in warm)
    # fetched step metrics landed in the registry (enabled run only)
    assert metrics.value("serve.decode_steps") >= s_on.decode_steps
    assert metrics.value("serve.decode_tokens") > 0
    assert metrics.value("serve.decode_active_slots") > 0
    names = {e["name"] for e in trace.events() if e["ph"] == "X"}
    assert {"serve.admit", "serve.decode", "serve.run"} <= names


def test_scheduler_instrumentation_overhead_budget(sched_model):
    """Tracing + device metrics must not blow up the decode step.

    Generous budget (CI wall clocks are noisy): the instrumented steady
    state stays within 1.5x + slack of the uninstrumented one.
    """
    cfg, params = sched_model

    def steady_wall(device_metrics, enabled):
        engine = ServeEngine(cfg, params, temperature=0.7)
        sched = ContinuousScheduler(
            engine, n_slots=3, max_len=48, key=jax.random.PRNGKey(5),
            device_metrics=device_metrics,
        )
        sched.warmup(prompt_range=(4, 4))
        sched.reset(keep_traces=True)
        reqs = [
            poisson_requests(
                7, 6, rate=10.0, vocab=cfg.vocab_size,
                prompt_lens=(4, 4), max_new=(30, 30),
            )[i] for i in range(3)
        ]
        if enabled:
            sched.run(reqs)
        else:
            with obs.disabled():
                sched.run(reqs)
        return sched.wall_s / max(sched.decode_steps, 1)

    steady_wall(True, True)  # warm everything once
    base = min(steady_wall(False, False) for _ in range(2))
    inst = min(steady_wall(True, True) for _ in range(2))
    assert inst <= base * 1.5 + 2e-3, (inst, base)


def test_span_overhead_microbenchmark():
    """Host-side span cost itself is tiny (a dict append + two clocks)."""
    n = 2000
    with obs.disabled():  # don't leak 2000 events into other asserts
        t0 = time.perf_counter()
        for _ in range(n):
            with trace.span("micro"):
                pass
        per_span = (time.perf_counter() - t0) / n
    assert per_span < 100e-6, per_span  # < 100 us/span, generously
