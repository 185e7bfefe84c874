"""Continuous-batching request scheduler for analog serving.

`ServeEngine.generate` runs one fixed batch to completion; under a real
arrival stream that leaves decode slots idle whenever sequences finish
at different times.  `ContinuousScheduler` keeps a fixed-shape decode
batch of `n_slots` busy against a request queue:

* **Admission** — arriving requests claim free slots; the prompt is
  right-padded to a power-of-two bucket and prefilled *into the shared
  pre-allocated cache* at the slot index (`models.decoding.prefill`
  with ``true_len`` + `write_cache_slot`).  One compiled dispatch per
  bucket size serves every admission, any slot, any neighbors.
  Admission ORDER among ready requests is pluggable (`admission_policy`):
  "fifo" (arrival), "spf" (shortest prompt first), "edf" (earliest
  TTFT deadline first, `Request.deadline`); `select_next` is the pure,
  property-tested order.
* **Chunked prefill** — with `prefill_chunk_tokens=C`, prompts whose
  bucket exceeds C prefill in C-token chunks interleaved between decode
  steps (`models.decoding.prefill_chunk` writes each chunk into the
  shared cache in place), so a short request's first token no longer
  waits out a long prompt's whole-bucket prefill.  The first chunk
  parks the slot's cache position at `max_len` (interleaved decode
  writes for that row land out of bounds and are dropped); the final
  chunk — the one holding the last REAL token, trailing all-padding
  chunks are never dispatched — restores ``pos`` and samples the first
  token from the same per-request sub-stream as whole-prompt admission,
  so served tokens are bit-identical either way (DESIGN.md Sec. 18).
* **Clock accounting** — `prefill_tokens_per_step` prices prefill
  occupancy proportionally to the physical tokens driven (a 64-token
  bucket charges 4x a 16-token chunk); the legacy constant
  `prefill_cost_steps` remains the default for old baselines.
* **Decode** — every step runs the whole batch through ONE jitted step
  of fixed shape; per-slot positions, per-slot stop bookkeeping, and
  per-slot sampling keys mean batch composition never enters the
  compiled computation's shape.  **Zero retrace across batch
  compositions** is a hard contract: `trace_counts` is asserted flat
  after `warmup()` by tests and `benchmarks/serving_traffic.py`.
* **Per-request RNG** — token i of request `rid` is sampled with
  ``fold_in(fold_in(master_key, rid), i)``, so a request's served
  tokens are bit-identical whether it rides alone or in a full batch,
  and in whichever slot it lands (the decode batch is row-independent:
  attention, matmuls and sampling all act per slot).
* **Accounting** — per-request queue delay, time-to-first-token and
  total latency in decode-step units plus wall clock; exactly ONE
  device->host sync per decode step (the (B,) token fetch), counted in
  `host_syncs` and asserted by the serving benchmark.
* **Analog path** — params are pulled through `ServeEngine.
  access_params` every access, so a `CIMExecutor` ticks real
  read-disturb traffic per scheduled step (prefill ticks the padded
  bucket length — the physical tokens driven through the tiles; decode
  ticks the full batch) and only tiny noise-key leaves change between
  accesses: no retrace.  An optional `maintenance_fn` (e.g. a
  `LifetimeSimulator` epoch with `traffic_fn=executor.drain_reads`)
  interleaves between decode steps without touching the batch state.

Ownership contract (DESIGN.md Sec. 13): the scheduler owns admission
and slot lifecycle, the engine owns step functions and parameter
access, the executor owns traffic/cost accounting.
"""

from __future__ import annotations

import collections
import dataclasses
import math
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.cim import batch_mesh as cim_batch_mesh, token_stream_ids
from repro.models import (
    decode_step,
    init_cache,
    prefill,
    prefill_chunk,
    write_cache_slot,
)

__all__ = [
    "ADMISSION_POLICIES",
    "Request",
    "RequestRecord",
    "ContinuousScheduler",
    "admission_key",
    "select_next",
    "poisson_requests",
]


@dataclasses.dataclass
class Request:
    """One serving request: prompt tokens + generation budget."""

    rid: int                        # unique id (RNG sub-stream + records key)
    prompt: Any                     # 1-D int token ids
    max_new: int                    # generation budget (includes first token)
    arrival: float = 0.0            # arrival time, decode-step units
    eos_id: int | None = None       # per-request stop token
    deadline: float | None = None   # absolute TTFT deadline (step clock):
    #                                 first token must complete by this time


ADMISSION_POLICIES = ("fifo", "spf", "edf")

# Compiler options of every serving step.  XLA may run a chain of bf16
# ops in f32 and round once ("excess precision"), and where it does so
# depends on the fusion, hence on the sharding.  Turned off, every op
# rounds to its dtype, so a batch-sharded step serves the tokens an
# unsharded one does.  No effect on f32 models.
STEP_COMPILER_OPTIONS = {"xla_allow_excess_precision": False}


def admission_key(policy: str, req: Request):
    """Total order over ready requests for one admission decision.

    * "fifo" — arrival order (the pre-policy behavior);
    * "spf"  — shortest prompt first (cheap prefill jumps the queue;
      can starve long prompts under sustained load — it is here as the
      classic TTFT-optimal comparison point, not a recommendation);
    * "edf"  — earliest `Request.deadline` first; deadline-less
      requests sort last (infinite deadline).

    Ties always break (arrival, rid), so every policy is a strict total
    order and admission is deterministic — the EDF ordering property in
    tests/test_serving_scheduler.py holds on exactly this function.
    """
    if policy == "fifo":
        return (req.arrival, req.rid)
    if policy == "spf":
        return (len(req.prompt), req.arrival, req.rid)
    if policy == "edf":
        d = req.deadline if req.deadline is not None else math.inf
        return (d, req.arrival, req.rid)
    raise ValueError(
        f"unknown admission policy {policy!r}; known: {ADMISSION_POLICIES}"
    )


def select_next(ready: list[Request], policy: str) -> Request:
    """The request `policy` admits next from the ready set (pure)."""
    return min(ready, key=lambda r: admission_key(policy, r))


@dataclasses.dataclass
class RequestRecord:
    """Lifecycle + latency accounting for one served request.

    All times are in decode-step units on the scheduler's clock.  The
    admitting prefill occupies the engine for `prefill_cost_steps`
    (default 1.0), and a token emitted by a decode step completes at
    the END of that step — so an unqueued request's total latency is
    ``prefill_cost + (max_new - 1)`` steps.
    """

    rid: int
    arrival: float
    prompt_len: int
    bucket_len: int                 # padded prefill length (physical tokens)
    admit_step: float = 0.0         # admission (prefill dispatch) time
    first_token_step: float = 0.0   # first token completion time
    done_step: float = 0.0          # last token completion time
    deadline: float | None = None   # absolute TTFT deadline, if any
    n_chunks: int = 1               # prefill dispatches (1 = whole-bucket)
    tokens: list = dataclasses.field(default_factory=list)
    # Host clock (time.perf_counter) when the first token reached the
    # host; 0.0 until then.  The only wall-clock field: TTFT on the
    # device that served the request.
    first_token_wall: float = 0.0

    @property
    def n_generated(self) -> int:
        return len(self.tokens)

    @property
    def queue_delay_steps(self) -> float:
        return self.admit_step - self.arrival

    @property
    def ttft_steps(self) -> float:
        return self.first_token_step - self.arrival

    @property
    def latency_steps(self) -> float:
        return self.done_step - self.arrival

    @property
    def deadline_missed(self) -> bool:
        """True when the first token completed after the TTFT deadline."""
        return (
            self.deadline is not None and self.first_token_step > self.deadline
        )


@dataclasses.dataclass
class _ChunkedPrefill:
    """In-flight chunked prefill occupying a reserved slot."""

    req: Request
    padded: np.ndarray              # (1, bucket) right-padded prompt
    bucket: int
    chunk: int                      # C, the per-dispatch token count
    next_start: int = 0

    @property
    def last_start(self) -> int:
        """Start of the chunk holding the last REAL token; trailing
        all-padding chunks are inert junk and are never dispatched."""
        return (len(self.req.prompt) - 1) // self.chunk * self.chunk


def _next_pow2(n: int) -> int:
    return 1 << max(int(n - 1).bit_length(), 0)


class ContinuousScheduler:
    """Slot-based continuous batching over a `ServeEngine`'s step functions.

    Args:
      engine: `ServeEngine` (digital params or a `CIMExecutor`-backed
        analog deployment).  The scheduler builds its own jitted step
        functions (it needs per-slot sampling keys and slot admission)
        but routes every parameter access through the engine so hot
        swaps and executor ticking keep working.
      n_slots: fixed decode batch size.
      max_len: shared cache length; prompt_len + max_new must fit.
      min_prefill_bucket: smallest padded prompt length (buckets are
        powers of two in [min_prefill_bucket, max_len]).
      key: master sampling key; request sub-streams fold from it.
      maintenance_fn: called between decode steps every
        `maintenance_every` steps (lifetime scrub epochs, metrics
        flushes).  Runs on the host between dispatches: it never blocks
        or reshapes the batch.
      device_metrics: compute per-step metrics (active slots, greedy
        agreement) and the in-jit batch-occupancy digest inside the
        jitted decode and fetch them on the SAME device_get as the
        tokens.  Token bits are identical either way; the flag exists
        so tests can assert that.
      name: digest namespace prefix ("serve" by default) — fleet
        replicas pass distinct names so their latency/TTFT/occupancy
        digests stay separable and merge into fleet-wide views.
    """

    def __init__(
        self,
        engine,
        *,
        n_slots: int = 4,
        max_len: int = 128,
        min_prefill_bucket: int = 8,
        key: jax.Array | None = None,
        maintenance_fn: Callable[[], Any] | None = None,
        maintenance_every: int = 0,
        prefill_cost_steps: float = 1.0,
        prefill_tokens_per_step: float | None = None,
        prefill_chunk_tokens: int | None = None,
        admission_policy: str = "fifo",
        batch_mesh=None,
        device_metrics: bool = True,
        name: str = "serve",
    ):
        self.engine = engine
        self.cfg = engine.cfg
        self.mesh = engine.mesh
        self.temperature = float(engine.temperature)
        self.n_slots = n_slots
        self.max_len = max_len
        if min_prefill_bucket < 1 or min_prefill_bucket & (min_prefill_bucket - 1):
            raise ValueError(
                f"min_prefill_bucket must be a power of two: {min_prefill_bucket}"
            )
        self.min_bucket = min_prefill_bucket
        self.prefill_cost_steps = float(prefill_cost_steps)
        # Proportional prefill pricing (step-clock accounting): a prefill
        # of n physical tokens occupies the engine n / rate steps.  None
        # keeps the legacy constant-cost clock for old baselines.
        self.prefill_tokens_per_step = (
            float(prefill_tokens_per_step)
            if prefill_tokens_per_step is not None else None
        )
        if admission_policy not in ADMISSION_POLICIES:
            raise ValueError(
                f"unknown admission policy {admission_policy!r}; "
                f"known: {ADMISSION_POLICIES}"
            )
        self.admission_policy = admission_policy
        if prefill_chunk_tokens is not None:
            c = int(prefill_chunk_tokens)
            if c < 1 or c & (c - 1):
                raise ValueError(
                    f"prefill_chunk_tokens must be a power of two (so every "
                    f"larger power-of-two bucket divides into whole chunks): {c}"
                )
            for nm, cs in (("attn_chunk_q", self.cfg.attn_chunk_q),
                           ("attn_chunk_kv", self.cfg.attn_chunk_kv)):
                if c % cs:
                    raise ValueError(
                        f"prefill_chunk_tokens={c} must be a multiple of "
                        f"{nm}={cs}: chunk boundaries must align with the "
                        "attention kernel's chunk grid for bit-identity "
                        "with whole-prompt prefill"
                    )
            if c >= max_len:
                raise ValueError(
                    f"prefill_chunk_tokens={c} >= max_len={max_len}: nothing "
                    "would ever chunk"
                )
            if self.cfg.is_moe:
                raise ValueError(
                    "chunked prefill does not support MoE blocks (capacity "
                    "routing couples tokens across the sequence)"
                )
        self.prefill_chunk_tokens = (
            int(prefill_chunk_tokens) if prefill_chunk_tokens is not None
            else None
        )
        self.key = key if key is not None else jax.random.PRNGKey(0)
        self.maintenance_fn = maintenance_fn
        self.maintenance_every = maintenance_every
        # Device-side decode metrics (obs, DESIGN.md Sec. 14): computed
        # inside the jitted step and fetched on the SAME device_get as
        # the tokens — never an extra sync, never a retrace (the flag is
        # fixed per scheduler, so each jit has one stable output treedef).
        self.device_metrics = bool(device_metrics)
        # Streaming digests (DESIGN.md Sec. 16): `name` prefixes this
        # scheduler's digest namespace so fleet replicas keep separate
        # histograms that merge into fleet-wide views.  The batch-
        # occupancy digest is an in-jit carry, fetched cumulatively on
        # the per-step token device_get; latency/TTFT/queue digests are
        # host-born (wall clock / step clock) and never touch the device.
        self.name = str(name)
        self._occ_digest = (
            obs.StreamingDigest.zeros(0.0, n_slots + 1.0, n_slots + 1)
            if self.device_metrics else None
        )

        cache = init_cache(self.cfg, n_slots, max_len)
        if set(cache) != {"k", "v", "pos"}:
            raise ValueError(
                "continuous batching needs a pure attention cache (k/v/pos); "
                f"got {sorted(cache)} for block={self.cfg.block}"
            )
        # Data-sharded decode (DESIGN.md Sec. 18): ONLY the batch axis
        # shards, over "data" — sharding the sequence axis would split
        # each attention reduction across devices and break the
        # bit-identity contract.  CIM tile planes shard over "model"
        # independently (`launch.shardings.cim_weight_specs`).
        self.batch_mesh = batch_mesh
        self._vec_sharding = None
        if batch_mesh is not None:
            from repro.launch.shardings import (
                decode_batch_sharding,
                decode_vec_sharding,
            )

            cache = jax.device_put(
                cache, decode_batch_sharding(batch_mesh, cache)
            )
            self._vec_sharding = decode_vec_sharding(batch_mesh, n_slots)
        if self.cfg.pos_embedding == "sinusoidal":
            # decode_step applies cache["pos"][0] as the batch-wide
            # embedding offset; heterogeneous per-slot positions would
            # silently read a neighbor's offset (RoPE is per-slot).
            raise ValueError(
                "continuous batching needs per-slot positions; sinusoidal "
                "embeddings take a batch-wide offset"
            )
        if self.cfg.n_codebooks > 1:
            raise ValueError("multi-codebook heads are not admissible")
        self.cache = cache

        # Trace-time side effects: each counter bumps once per compiled
        # trace, so a steady-state serve asserts them flat.
        self.trace_counts = {"admit": 0, "decode": 0, "chunk": 0}
        self._admit_jit = self._build_admit()
        self._decode_jit = jax.jit(
            self._build_decode(), compiler_options=STEP_COMPILER_OPTIONS
        )
        # Chunk dispatches specialize on (start, is_final) ONLY — the
        # chunk width is fixed and true_len/slot/rid stay traced — so
        # the compile count is bounded by 2 * max_len / C regardless of
        # bucket mix, and warmup() covers every reachable pair.
        self._chunk_jits: dict[tuple[int, bool], Any] = {}
        self._prefilling: dict[int, _ChunkedPrefill] = {}

        self._rid = np.full((n_slots,), -1, np.int32)
        self._gen = np.zeros((n_slots,), np.int32)
        self._cur = np.zeros((n_slots,), np.int32)
        self._slot_req: list[Request | None] = [None] * n_slots
        self.records: dict[int, RequestRecord] = {}
        self.completed: list[RequestRecord] = []
        self.now = 0.0
        self.decode_steps = 0
        self.host_syncs = 0
        self.admit_syncs = 0
        self.admits = 0
        self.tokens_generated = 0
        self.prefill_tokens = 0
        self.wall_s = 0.0
        self.decode_wall_s = 0.0

    # ------------------------------------------------------- step builders
    def _select_token(self, logits: jax.Array, key, rid, gen) -> jax.Array:
        """Sample/argmax ONE slot's next token from its own sub-stream."""
        if self.temperature > 0.0:
            k = jax.random.fold_in(jax.random.fold_in(key, rid), gen)
            return jax.random.categorical(
                k, logits.astype(jnp.float32) / self.temperature
            )
        return jnp.argmax(logits, axis=-1)

    def _build_admit(self):
        cfg, mesh, max_len = self.cfg, self.mesh, self.max_len

        def admit(params, tokens, true_len, rid, master, cache, slot):
            # One jit specializes per padded bucket shape; this bump
            # fires once per specialization (trace time only).
            self.trace_counts["admit"] += 1
            with cim_batch_mesh(self.batch_mesh):
                last, single = prefill(
                    params, {"tokens": tokens}, cfg, mesh,
                    max_len=max_len, true_len=true_len,
                )
            tok = self._select_token(last[0], master, rid, jnp.int32(0))
            cache = write_cache_slot(cache, single, slot)
            return tok.astype(jnp.int32), cache

        return jax.jit(admit, compiler_options=STEP_COMPILER_OPTIONS)

    def _build_decode(self):
        cfg, mesh = self.cfg, self.mesh
        device_metrics = self.device_metrics

        def decode(params, cache, cur, rids, gens, master, dig):
            self.trace_counts["decode"] += 1  # fires at trace time only
            # Analog CIM leaves fold the REQUEST id (a traced argument —
            # no retrace) into their per-row noise sub-streams, so a
            # request's served logits are bit-identical in any slot and
            # any batch composition (DESIGN.md Sec. 17).  Digital params
            # ignore the context entirely.
            with token_stream_ids(rids), cim_batch_mesh(self.batch_mesh):
                logits, cache = decode_step(
                    params, cache, {"tokens": cur[:, None]}, cfg, mesh
                )
            last = logits[:, -1] if logits.ndim == 3 else logits[:, -1, 0]
            toks = jax.vmap(
                lambda l, r, g: self._select_token(l, master, r, g)
            )(last, rids, gens)
            toks = toks.astype(jnp.int32)
            # Step metrics ride the token fetch (never their own sync).
            # The token computation above is untouched either way, so
            # served bits are identical with metrics on or off.
            m = {}
            if device_metrics:
                active = rids >= 0
                n_active = jnp.sum(active).astype(jnp.float32)
                greedy = jnp.argmax(last, axis=-1).astype(jnp.int32)
                m = {
                    "decode_active_slots": n_active,
                    "decode_greedy_agree": jnp.sum(
                        active & (toks == greedy)
                    ).astype(jnp.float32),
                }
                # In-jit streaming digest (DESIGN.md Sec. 16): batch
                # occupancy accumulates inside the compiled step; the
                # carry stays on device and its cumulative counts ride
                # the same per-step fetch as the tokens.
                dig = dig.add(n_active)
            return toks, m, dig, cache

        return decode

    def _get_chunk_jit(self, start: int, final: bool):
        """Compiled dispatch for one prefill chunk at static `start`."""
        fn = self._chunk_jits.get((start, final))
        if fn is not None:
            return fn
        cfg, mesh, max_len = self.cfg, self.mesh, self.max_len

        def chunk(params, cache, tokens, true_len, rid, master, slot):
            self.trace_counts["chunk"] += 1  # fires at trace time only
            with cim_batch_mesh(self.batch_mesh):
                last, cache = prefill_chunk(
                    params, cache, tokens, cfg, mesh, start=start, slot=slot,
                    true_len=true_len if final else None,
                    park_pos=max_len if start == 0 else None,
                )
            if final:
                # Same sub-stream as whole-bucket admission: the first
                # token is bit-identical chunked or not.
                tok = self._select_token(last[0], master, rid, jnp.int32(0))
                return tok.astype(jnp.int32), cache
            return cache

        fn = self._chunk_jits[(start, final)] = jax.jit(
            chunk, compiler_options=STEP_COMPILER_OPTIONS
        )
        return fn

    # ------------------------------------------------------------ plumbing
    def bucket_len(self, prompt_len: int) -> int:
        b = max(_next_pow2(prompt_len), self.min_bucket)
        return min(b, self.max_len)

    def prefill_cost(self, n_tokens: int, bucket: int | None = None) -> float:
        """Step-clock charge for prefilling `n_tokens` physical tokens.

        Proportional when `prefill_tokens_per_step` is set — a 64-token
        bucket occupies the engine 4x as long as a 16-token chunk, which
        is what makes whole-prompt head-of-line blocking visible in
        queue-delay/TTFT accounting.  Legacy fallback: the constant
        `prefill_cost_steps` per whole bucket, pro-rated per chunk (so a
        fully chunked prompt never charges more than the constant).
        """
        if self.prefill_tokens_per_step is not None:
            return n_tokens / self.prefill_tokens_per_step
        if bucket is None or n_tokens >= bucket:
            return self.prefill_cost_steps
        return self.prefill_cost_steps * n_tokens / bucket

    def _free_slot(self) -> int | None:
        free = [
            i for i in range(self.n_slots)
            if self._rid[i] < 0 and i not in self._prefilling
        ]
        return free[0] if free else None

    def active_slots(self) -> int:
        return int(np.sum(self._rid >= 0))

    def _digest_hi(self) -> float:
        """Shared bucket range for the step-clock digests (latency, TTFT,
        queue delay).  Static per scheduler geometry, so replicas with
        the same max_len merge their digests fleet-wide."""
        return 8.0 * self.max_len

    def _finish(self, slot: int, t_done: float | None = None) -> None:
        rec = self.records[self._slot_req[slot].rid]
        rec.done_step = self.now if t_done is None else t_done
        self.completed.append(rec)
        obs.digests.observe(
            f"{self.name}.latency_steps", rec.latency_steps,
            lo=0.0, hi=self._digest_hi(), n_buckets=128,
        )
        self._rid[slot] = -1
        self._gen[slot] = 0
        self._cur[slot] = 0
        self._slot_req[slot] = None

    def _emit(self, slot: int, tok: int, t_done: float) -> bool:
        """Record one generated token (completing at `t_done`); returns
        True if the slot finished."""
        req = self._slot_req[slot]
        rec = self.records[req.rid]
        if not rec.tokens:
            rec.first_token_step = t_done
            rec.first_token_wall = time.perf_counter()
            obs.digests.observe(
                f"{self.name}.ttft_steps", rec.ttft_steps,
                lo=0.0, hi=self._digest_hi(), n_buckets=128,
            )
        rec.tokens.append(tok)
        self._gen[slot] += 1
        self._cur[slot] = tok
        self.tokens_generated += 1
        done = self._gen[slot] >= req.max_new or (
            req.eos_id is not None and tok == req.eos_id
        )
        if done:
            self._finish(slot, t_done)
        return done

    # ------------------------------------------------------------- serving
    def admit(self, req: Request, slot: int | None = None) -> int:
        """Prefill `req` into a free slot of the shared cache.

        Whole-bucket admission (bucket <= `prefill_chunk_tokens`, or
        chunking disabled) dispatches one prefill and emits the first
        token before returning.  Chunked admission reserves the slot and
        dispatches only the FIRST chunk; `run()` (or a manual driver
        calling `prefill_tick()`) interleaves the remaining chunks
        between decode steps, and the first token is emitted by the
        final chunk.
        """
        if slot is None:
            slot = self._free_slot()
        if slot is None:
            raise RuntimeError("no free slot")
        if self._rid[slot] >= 0 or slot in self._prefilling:
            raise RuntimeError(
                f"slot {slot} is occupied by request "
                f"{self._rid[slot] if self._rid[slot] >= 0 else self._prefilling[slot].req.rid}"
            )
        plen = len(req.prompt)
        if plen < 1:
            raise ValueError(f"request {req.rid}: empty prompt")
        if plen + req.max_new > self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt {plen} + max_new {req.max_new} "
                f"exceeds max_len {self.max_len}"
            )
        bucket = self.bucket_len(plen)
        chunk = self.prefill_chunk_tokens
        chunked = chunk is not None and bucket > chunk
        padded_len = bucket if not chunked else (
            ((plen - 1) // chunk + 1) * chunk
        )
        padded = np.zeros((1, padded_len), np.int32)
        padded[0, :plen] = np.asarray(req.prompt, np.int32)
        self.records[req.rid] = RequestRecord(
            rid=req.rid, arrival=req.arrival, prompt_len=plen,
            bucket_len=bucket, admit_step=self.now, deadline=req.deadline,
            n_chunks=(plen - 1) // chunk + 1 if chunked else 1,
        )
        obs.digests.observe(
            f"{self.name}.queue_delay_steps", self.now - req.arrival,
            lo=0.0, hi=self._digest_hi(), n_buckets=128,
        )
        self.admits += 1
        obs.registry.inc("serve.admits")
        self._slot_req[slot] = req
        if chunked:
            self._prefilling[slot] = _ChunkedPrefill(
                req=req, padded=padded, bucket=bucket, chunk=chunk
            )
            self._dispatch_chunk(slot)
            return slot
        with obs.span(
            "serve.admit", cat="serve", rid=req.rid, bucket=bucket, slot=slot
        ):
            params = self.engine.access_params(bucket)  # physical prefill toks
            with jax.transfer_guard_device_to_host("disallow"):
                tok, self.cache = self._admit_jit(
                    params,
                    jnp.asarray(padded),
                    jnp.asarray([plen], jnp.int32),
                    jnp.int32(req.rid),
                    self.key,
                    self.cache,
                    jnp.int32(slot),
                )
            tok = int(jax.device_get(tok))  # the one (small) admit sync
        self.admit_syncs += 1
        self.prefill_tokens += bucket
        obs.registry.inc("serve.prefill_tokens", bucket)
        self._rid[slot] = req.rid
        self._gen[slot] = 0
        # The prefill occupies the engine: advance the clock before the
        # first token completes.
        self.now += self.prefill_cost(bucket, bucket)
        self._emit(slot, tok, self.now)
        return slot

    def _dispatch_chunk(self, slot: int) -> None:
        """Run ONE chunk of the in-flight prefill reserved on `slot`."""
        st = self._prefilling[slot]
        start, chunk = st.next_start, st.chunk
        final = start == st.last_start
        req = st.req
        with obs.span(
            "serve.prefill_chunk", cat="serve", rid=req.rid, start=start,
            slot=slot, final=final,
        ):
            fn = self._get_chunk_jit(start, final)
            tokens = jnp.asarray(st.padded[:, start:start + chunk])
            params = self.engine.access_params(chunk)  # physical chunk toks
            with jax.transfer_guard_device_to_host("disallow"):
                out = fn(
                    params,
                    self.cache,
                    tokens,
                    jnp.asarray([len(req.prompt)], jnp.int32),
                    jnp.int32(req.rid),
                    self.key,
                    jnp.int32(slot),
                )
            if final:
                tok, self.cache = out
                tok = int(jax.device_get(tok))  # the one (small) admit sync
                self.admit_syncs += 1
            else:
                self.cache = out
        self.prefill_tokens += chunk
        obs.registry.inc("serve.prefill_tokens", chunk)
        self.now += self.prefill_cost(chunk, st.bucket)
        st.next_start = start + chunk
        if final:
            del self._prefilling[slot]
            self._rid[slot] = req.rid
            self._gen[slot] = 0
            self._emit(slot, tok, self.now)

    def prefill_tick(self) -> bool:
        """Dispatch ONE pending prefill chunk (the oldest reservation);
        returns False when no chunked prefill is in flight.  `run()`
        calls this once per loop iteration, interleaving chunks between
        decode steps."""
        if not self._prefilling:
            return False
        slot = next(iter(self._prefilling))
        self._dispatch_chunk(slot)
        return True

    def _decode_args(self, params: Any) -> tuple:
        """The compiled decode step's arguments for the current slots."""
        if self._vec_sharding is not None:
            # Host->device placements (allowed under the guard): the
            # per-slot vectors land pre-sharded over "data" so the
            # compiled step never reshards its batch inputs.
            vecs = [
                jax.device_put(v, self._vec_sharding)
                for v in (self._cur, self._rid, self._gen)
            ]
        else:
            vecs = [jnp.asarray(v) for v in (self._cur, self._rid, self._gen)]
        return (params, self.cache, *vecs, self.key, self._occ_digest)

    def lower_decode(self) -> Any:
        """The decode step `step()` dispatches, lowered at the current
        arguments (for inspecting the program); ticks nothing."""
        return self._decode_jit.lower(*self._decode_args(self.engine.params))

    def step(self) -> None:
        """One decode step of the whole batch + slot bookkeeping.

        Exactly one device->host sync: the (B,) token fetch.  ENFORCED,
        not just counted — the dispatch runs under a device->host
        transfer guard, so any implicit sync creeping into the decode
        path (a stray `float()`/`np.asarray` on a device value) raises
        instead of silently serializing the loop.
        """
        t0 = time.perf_counter()
        with obs.span("serve.decode", cat="serve") as sp:
            args = self._decode_args(self.engine.access_params(self.n_slots))
            with jax.transfer_guard_device_to_host("disallow"):
                toks, m, dig, self.cache = self._decode_jit(*args)
            # THE per-step host sync: tokens, step metrics AND the
            # cumulative occupancy digest, one fetch.
            toks, m, dig_h = jax.device_get((toks, m, dig))
            toks = np.asarray(toks)
            self._occ_digest = dig
            self.host_syncs += 1
            self.decode_steps += 1
            obs.registry.inc("serve.decode_steps")
            obs.registry.fold(m, prefix="serve.")
            if dig_h is not None:
                # Cumulative carry -> replace, never merge (DigestRegistry.put)
                obs.digests.put(f"{self.name}.batch_occupancy", dig_h)
            obs.digests.observe(
                f"{self.name}.step_latency_us",
                (time.perf_counter() - t0) * 1e6,
                lo=0.0, hi=1e5, n_buckets=128,
            )
            emitted = 0
            for slot in np.flatnonzero(self._rid >= 0):
                # a decode-emitted token completes at the END of this step
                self._emit(int(slot), int(toks[slot]), self.now + 1.0)
                emitted += 1
            obs.registry.inc("serve.decode_tokens", emitted)
            sp["tokens"] = emitted
        # Decode-only wall clock: excludes admission prefill and
        # interleaved maintenance, so `decode_wall_s / decode_steps` is
        # the analog/digital datapath step time the benchmarks gate on.
        self.decode_wall_s += time.perf_counter() - t0

    def warmup(
        self,
        prompt_lens: list[int] | None = None,
        prompt_range: tuple[int, int] | None = None,
    ) -> None:
        """Compile every dispatch the serve loop will hit, then reset.

        Admits one throwaway request per distinct prefill bucket and
        runs one decode step; afterwards `trace_counts` must stay flat
        for any traffic whose prompts map onto the warmed buckets.
        `prompt_range=(lo, hi)` warms EVERY bucket a prompt length in
        [lo, hi] can map to (the usual serve-loop precondition).
        """
        if prompt_range is not None:
            lo, hi = prompt_range
            plens = list(range(lo, hi + 1))
        else:
            plens = list(prompt_lens or [self.min_bucket])
        # derive the warmed set from the same mapping real traffic
        # uses, so it can never diverge from bucket_len()
        chunk = self.prefill_chunk_tokens
        buckets = sorted({
            self.bucket_len(p) for p in plens
            if chunk is None or self.bucket_len(p) <= chunk
        })
        if chunk is not None:
            # Chunked buckets: warm every reachable (start, is_final)
            # dispatch pair.  One dummy admission per distinct final-
            # chunk offset covers them all (its mid chunks warm every
            # smaller start; chunk jits are bucket-independent).
            lasts = sorted({
                (p - 1) // chunk * chunk for p in plens
                if self.bucket_len(p) > chunk and p + 1 <= self.max_len
            })
            for j, last in enumerate(lasts):
                plen = max(
                    p for p in plens
                    if self.bucket_len(p) > chunk
                    and (p - 1) // chunk * chunk == last
                    and p + 1 <= self.max_len
                )
                slot = self._free_slot()
                if slot is None:
                    self._finish(0)
                    slot = 0
                self.admit(
                    Request(rid=(1 << 29) + j, prompt=[0] * plen, max_new=1,
                            arrival=self.now),
                    slot,
                )
                while slot in self._prefilling:
                    self.prefill_tick()
        for i, b in enumerate(buckets):
            slot = self._free_slot()
            if slot is None:  # more buckets than slots: recycle slot 0
                self._finish(0)
                slot = 0
            # A b-token prompt maps exactly onto bucket b; a clamped top
            # bucket (b == max_len) warms with max_len - 1 (any length in
            # (b/2, b] still maps to b).  A bucket no admissible request
            # can reach (bucket_len(plen) != b once max_new >= 1 is
            # accounted) is skipped.  Dummy rids sit far above real ones.
            plen = min(b, self.max_len - 1)
            if self.bucket_len(plen) != b:
                continue
            self.admit(
                Request(rid=(1 << 30) + i, prompt=[0] * plen,
                        max_new=2 if plen + 2 <= self.max_len else 1,
                        arrival=self.now),
                slot,
            )
        if not self.active_slots():
            # every dummy finished at admission (max_new=1 top buckets):
            # keep one slot live so the decode dispatch compiles too
            plen = max(1, min(self.min_bucket, self.max_len - 2))
            self.admit(
                Request(rid=(1 << 30) + len(buckets), prompt=[0] * plen,
                        max_new=2, arrival=self.now)
            )
        self.step()
        # Second step: the first decode consumes the FRESH occupancy
        # digest (host-born leaves); every later step consumes the
        # previous step's OUTPUT digest, whose sharding a batch_mesh
        # jit stamps differently.  Both variants must be compiled here,
        # or the first post-warmup steady-state step silently re-lowers
        # (invisible to trace_counts — jax reuses the python trace).
        self.step()
        self.reset(keep_traces=True)

    def reset(self, keep_traces: bool = False) -> None:
        """Clear slot state, records and counters (compiled fns survive)."""
        self._rid[:] = -1
        self._gen[:] = 0
        self._cur[:] = 0
        self._slot_req = [None] * self.n_slots
        self._prefilling = {}
        self.records = {}
        self.completed = []
        self.now = 0.0
        self.decode_steps = 0
        self.host_syncs = 0
        self.admit_syncs = 0
        self.admits = 0
        self.tokens_generated = 0
        self.prefill_tokens = 0
        self.wall_s = 0.0
        self.decode_wall_s = 0.0
        if self.device_metrics:
            self._occ_digest = obs.StreamingDigest.zeros(
                0.0, self.n_slots + 1.0, self.n_slots + 1
            )
        obs.digests.reset(f"{self.name}.")
        if not keep_traces:
            self.trace_counts = {"admit": 0, "decode": 0, "chunk": 0}

    def run(
        self, requests: list[Request], *, max_steps: int = 1_000_000
    ) -> list[RequestRecord]:
        """Serve an arrival stream to completion.

        The clock is the decode step: each step advances `now` by 1,
        prefills charge `prefill_cost`, and idle periods fast-forward to
        the next arrival.  Ready requests (arrived, not yet admitted)
        are admitted into free slots in `admission_policy` order; with
        chunked prefill enabled, ONE pending chunk is dispatched per
        loop iteration before the decode step, so long-prompt prefills
        interleave with (rather than block) decode traffic.  Returns
        the completed `RequestRecord`s sorted by rid.
        """
        pending = collections.deque(
            sorted(requests, key=lambda r: (r.arrival, r.rid))
        )
        ready: list[Request] = []
        t0 = time.perf_counter()
        steps0 = self.decode_steps
        with obs.span(
            "serve.run", cat="serve", requests=len(requests),
            n_slots=self.n_slots, policy=self.admission_policy,
        ) as sp:
            while pending or ready or self.active_slots() or self._prefilling:
                while pending and pending[0].arrival <= self.now:
                    ready.append(pending.popleft())
                progressed = False
                while ready and self._free_slot() is not None:
                    req = select_next(ready, self.admission_policy)
                    ready.remove(req)
                    self.admit(req)
                    progressed = True
                    # admission advanced the clock: newly arrived
                    # requests join the ready set before the next pick
                    while pending and pending[0].arrival <= self.now:
                        ready.append(pending.popleft())
                if self.prefill_tick():
                    progressed = True
                if self.active_slots():
                    self.step()
                    self.now += 1.0
                    progressed = True
                    if (
                        self.maintenance_fn is not None
                        and self.maintenance_every > 0
                        and self.decode_steps % self.maintenance_every == 0
                    ):
                        with obs.span("serve.maintenance", cat="serve"):
                            self.maintenance_fn()
                    if self.decode_steps - steps0 >= max_steps:
                        break
                if not progressed:
                    if not pending:  # every remaining request finished
                        break
                    self.now = max(self.now, pending[0].arrival)
            sp["decode_steps"] = self.decode_steps - steps0
            sp["completed"] = len(self.completed)
        self.wall_s += time.perf_counter() - t0
        return sorted(self.completed, key=lambda r: r.rid)

    # ----------------------------------------------------------- reporting
    def digest_stats(self) -> dict[str, dict]:
        """This scheduler's digest summaries (percentiles, no arrays)."""
        prefix = f"{self.name}."
        return {
            n: d.summary()
            for n, d in (
                (n, obs.digests.get(n)) for n in obs.digests.names()
            )
            if n.startswith(prefix)
        }

    def latency_stats(self) -> dict[str, float]:
        """Aggregate latency/throughput stats over completed requests.

        Percentiles use `obs.rank_quantile` — the SAME rank-based
        definition `StreamingDigest.quantile` estimates — so the exact
        stats here and the streaming `digest_stats()` agree to bucket
        resolution (asserted by tests).  np.percentile's interpolating
        default disagrees with the digests on small samples, which is
        exactly the p99 regime these numbers gate.
        """
        lats = np.array([r.latency_steps for r in self.completed])
        ttfts = np.array([r.ttft_steps for r in self.completed])
        queue = np.array([r.queue_delay_steps for r in self.completed])
        steps = max(self.decode_steps, 1)
        out = {
            "completed": float(len(self.completed)),
            "decode_steps": float(self.decode_steps),
            "tokens_generated": float(self.tokens_generated),
            "tokens_per_step": self.tokens_generated / steps,
            "wall_s": self.wall_s,
            "tokens_per_s": (
                self.tokens_generated / self.wall_s if self.wall_s > 0 else 0.0
            ),
            "decode_wall_s": self.decode_wall_s,
            "decode_step_us": self.decode_wall_s / steps * 1e6,
            "decode_tokens_per_s": (
                self.tokens_generated / self.decode_wall_s
                if self.decode_wall_s > 0 else 0.0
            ),
        }
        if len(lats):
            out.update(
                p50_latency_steps=obs.rank_quantile(lats, 0.50),
                p99_latency_steps=obs.rank_quantile(lats, 0.99),
                p50_ttft_steps=obs.rank_quantile(ttfts, 0.50),
                p99_ttft_steps=obs.rank_quantile(ttfts, 0.99),
                mean_queue_delay_steps=float(queue.mean()),
            )
        with_deadline = [r for r in self.completed if r.deadline is not None]
        if with_deadline:
            missed = sum(r.deadline_missed for r in with_deadline)
            out["deadline_requests"] = float(len(with_deadline))
            out["deadline_misses"] = float(missed)
            out["deadline_miss_rate"] = missed / len(with_deadline)
        return out


def poisson_requests(
    seed: int,
    n: int,
    *,
    rate: float,
    vocab: int,
    prompt_lens: tuple[int, int] = (4, 24),
    max_new: tuple[int, int] = (4, 16),
    eos_id: int | None = None,
    start_rid: int = 0,
    long_prompt_lens: tuple[int, int] | None = None,
    long_frac: float = 0.0,
    ttft_slack: tuple[float, float] | None = None,
) -> list[Request]:
    """A Poisson arrival stream of variable-length requests.

    `rate` is the offered load in requests per decode step; inter-arrival
    times are Exp(1/rate).  Prompt lengths and generation budgets draw
    uniformly from their (lo, hi) ranges.

    `long_prompt_lens` + `long_frac` mix in a heavy-tail fraction of
    long prompts (the SLO benchmark's head-of-line-blocking stressor);
    `ttft_slack=(lo, hi)` attaches a TTFT deadline of ``arrival +
    Uniform(lo, hi)`` steps to every request (EDF admission input and
    the deadline-miss-rate denominator).
    """
    g = np.random.default_rng(seed)
    arrivals = np.cumsum(g.exponential(1.0 / rate, size=n))
    reqs = []
    for i in range(n):
        lens = prompt_lens
        if long_prompt_lens is not None and g.random() < long_frac:
            lens = long_prompt_lens
        plen = int(g.integers(lens[0], lens[1] + 1))
        deadline = None
        if ttft_slack is not None:
            deadline = float(
                arrivals[i] + g.uniform(ttft_slack[0], ttft_slack[1])
            )
        reqs.append(
            Request(
                rid=start_rid + i,
                prompt=g.integers(0, vocab, size=plen).astype(np.int32),
                max_new=int(g.integers(max_new[0], max_new[1] + 1)),
                arrival=float(arrivals[i]),
                eos_id=eos_id,
                deadline=deadline,
            )
        )
    return reqs
