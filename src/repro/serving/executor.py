"""Executors: the data plane behind the schedulers.

SimExecutor      — discrete-event: step costs come from a LatencyModel
                   (calibrated to the paper's Fig. 1 testbed). Used for the
                   paper-scale reproduction benchmarks.
JaxExecutor      — a real JAX engine: tiny model, slot-based KV cache,
                   per-column active-mask decode (the TPU mapping of the
                   decode-mask matrix), measured wall-clock latencies.
PagedJaxExecutor — same engine over a paged KV arena (kv_pool.KVPagePool +
                   model.decode_step_paged): admission is bounded by the
                   page pool — actual residency — not a fixed slot count
                   (DESIGN.md §3 adaptation #2). Exposes page_budget() for
                   SLICE's memory-aware selection.

Both JAX executors record ``last_logits`` ([len(tasks), vocab] in task
order) after every decode — the paged-vs-slot equivalence contract tested
in tests/test_kv_pool.py — and ``last_prefill_logits`` after every
completed prefill (atomic or final chunk), the chunked-vs-monolithic
contract tested in tests/test_chunked_prefill.py. With
``prefill_chunk_size`` set, ``prefill_chunk(task, n)`` processes the next
n prompt tokens through AOT-compiled chunk-size buckets ({chunk} ∪
{2^k < chunk}, mirroring the pow-2 decode buckets); prompt tokens are a
deterministic function of (seed, task) so the atomic and chunked paths
see the same prompt. With ``prefix_cache=True`` the paged executor dedups
shared page-aligned prompt prefixes through a radix index + refcounted
pages (DESIGN.md §6) — prefill skips the cached prefix, decode reads it
through the shared page tables, logits unchanged.

Host-offload KV swap (DESIGN.md §7): ``suspend(task)`` moves a resident
task's private pages to a host-side KVSwapArena (shared prefix pages
stay resident), ``resume(task)`` brings them back bit-exact. The paged
executor implements the real transfers (jax.device_get/put); SimExecutor
prices them through ``LatencyModel.swap_ms`` (the ``swap_bw_gbps`` term).

Speculative decoding (DESIGN.md §8): ``decode(tasks, depths)`` with
per-task speculation depths drafts token windows through a tiny
DraftModel (serving.spec_decode), verifies them in one AOT-bucketed
``model.verify_step_paged`` call, commits the greedy-accepted prefix
plus a bonus token (``last_commits`` reports per-task counts), and rolls
back rejected-draft pages (``KVPagePool.truncate``) — the committed
stream is identical to non-speculative greedy decode. SimExecutor prices
draft+verify through the LatencyModel spec terms and samples acceptance
from persistent per-task streams.

Async pipelining (DESIGN.md §10): with ``async_dispatch=True`` the paged
executor stops forcing per-step syncs — decode/prefill dispatch their XLA
calls and return immediately, the in-flight results ride a bounded
DispatchQueue (serving.pipeline), next-step tokens chain on-device
through per-row argmax scalars, and swap gathers materialize on a
background thread tracked by a TransferLedger. Observation surfaces
(``last_tok``/``last_logits``/``last_commits``/…) are commit-forcing
properties, so every caller sees exactly the synchronous engine's values
— byte-identical greedy streams (tests/test_async_engine.py) — just
later. The default stays sync: the reference all regression gates pin.
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.latency_model import LatencyModel, MeasuredLatencyModel
from repro.core.selection import PageBudget, StateBudget
from repro.core.task import Task
from repro.serving.kv_pool import KVPagePool, OutOfPages
from repro.serving.kv_swap import HostArenaFull, KVSwapArena
from repro.serving.state_store import (CacheStore, OutOfStates,
                                       SSMStateStore)
from repro.serving.pipeline import (DispatchQueue, GapStats, PendingStep,
                                    TransferLedger)
from repro.serving.trace import span


_PREFILL_PRIOR = [(64, 10.0), (512, 40.0)]   # prefill ms prior until measured


def _pow2_buckets(limit: int):
    """1, 2, 4, ... capped at limit — the compiled decode batch shapes shared
    by bucketed compaction and the paged executor."""
    b = 1
    while b < limit:
        yield b
        b *= 2
    yield limit


def _chunk_pieces(n: int, chunk: int):
    """Decompose an n-token prefill request into compiled chunk buckets:
    full ``chunk``-size pieces plus a power-of-two decomposition of the
    remainder — so the AOT bucket set {chunk} ∪ {2^k < chunk} covers every
    request size, mirroring the pow-2 decode buckets."""
    pieces = []
    while n > 0:
        if n >= chunk:
            pieces.append(chunk)
            n -= chunk
        else:
            p = 1
            while p * 2 <= n:
                p *= 2
            pieces.append(p)
            n -= p
    return pieces


def _prompt_tokens(seed: int, task: Task, vocab: int, length: int):
    """Deterministic per-task prompt tokens, shared by the atomic and chunked
    prefill paths (and across executors at equal seed) so chunked-vs-
    monolithic logit equivalence is well-defined.

    Tasks carrying shared-prefix metadata (task.prefix_group, DESIGN.md §6)
    open with tokens drawn from a per-GROUP stream instead of the per-task
    stream, so two tasks of one group really do share their first
    prefix_len prompt tokens — the content contract the radix prefix cache
    deduplicates on."""
    rng = np.random.default_rng((seed + 1) * 100_003 + task.task_id)
    toks = rng.integers(0, vocab, (1, length))
    k = min(getattr(task, "prefix_len", 0) or 0, length)
    if k > 0 and getattr(task, "prefix_group", None) is not None:
        grng = np.random.default_rng(
            (seed + 1) * 7_919 + 1_000_003 * (task.prefix_group + 1))
        toks[0, :k] = grng.integers(0, vocab, (k,))
    return toks


def _probe_latency_curve(executor: "Executor", warm_tasks, probes):
    """Warm min-of-3 decode timings at each probe batch size over tasks the
    caller has already admitted to the engine."""
    samples = []
    for b in probes:
        sub = warm_tasks[:b]
        executor.decode(sub)  # warm compile/caches
        ms = min(executor.decode(sub) for _ in range(3))
        samples.append((b, ms))
    return MeasuredLatencyModel(samples, _PREFILL_PRIOR)


class Executor:
    """Returns elapsed milliseconds for each operation."""

    def prefill(self, task: Task) -> float:
        raise NotImplementedError

    def prefill_chunk(self, task: Task, n_tokens: int) -> Tuple[float, bool]:
        """Process the next ``n_tokens`` of a task's prompt (DESIGN.md §5).
        Returns (elapsed ms, done) — done=True when the whole (effective)
        prompt is cached; the FINAL chunk's logits seed the first token."""
        raise NotImplementedError

    def decode(self, tasks: Sequence[Task],
               depths: Optional[Sequence[int]] = None) -> float:
        """One decode iteration. With ``depths`` None (the default) every
        task produces exactly one token — the classic path. With per-task
        speculation depths (DESIGN.md §8) an executor built for spec
        decoding drafts up to depths[i] tokens per task, verifies them in
        one step, and reports the committed token count per task in
        ``last_commits`` (always >= 1: rejected windows still commit the
        bonus token). The committed stream is greedy-identical either
        way."""
        raise NotImplementedError

    def suspend(self, task: Task) -> float:
        """Swap a resident task's private KV pages to host memory
        (DESIGN.md §7), freeing device pages while preserving logical
        length. The task must be resume()d before it decodes again."""
        raise NotImplementedError(f"{type(self).__name__} has no KV swap")

    def resume(self, task: Task) -> float:
        """Bring a suspended task's KV back onto the device. Raises
        kv_pool.OutOfPages (task stays suspended) when the pool cannot
        host it right now."""
        raise NotImplementedError(f"{type(self).__name__} has no KV swap")

    def release(self, task: Task) -> None:
        pass

    def latency_model(self) -> LatencyModel:
        raise NotImplementedError


class SimExecutor(Executor):
    def __init__(self, lat: LatencyModel, scheduling_overhead_ms: float = 0.0,
                 name: Optional[str] = None):
        self.lat = lat
        self.name = name               # fleet-instance identity (DESIGN.md §11)
        self.overhead = scheduling_overhead_ms
        self.decode_steps = 0
        self.prefill_steps = 0
        self.chunk_steps = 0
        self._chunk_progress: Dict[int, int] = {}
        # KV swap accounting (DESIGN.md §7): transfers are priced by the
        # latency model's swap_bw_gbps term; resident KV is the task's
        # prompt plus every token decoded so far.
        self.suspend_count = 0
        self.resume_count = 0
        self.swapped_bytes = 0.0
        self._swapped_tokens: Dict[int, int] = {}
        # Speculative decoding (DESIGN.md §8): draft+verify cost comes from
        # the latency model's spec terms; acceptance is sampled per task
        # from a persistent stream at the model's spec_accept_rate, so a
        # run is deterministic at equal seed/call order.
        self.spec_steps = 0
        self.drafted_tokens = 0
        self.accepted_tokens = 0
        self.last_commits: Optional[List[int]] = None
        self._accept_rng: Dict[int, Any] = {}

    def prefill(self, task: Task) -> float:
        self.prefill_steps += 1
        return self.lat.prefill_ms(task.prompt_len) + self.overhead

    def prefill_chunk(self, task: Task, n_tokens: int) -> Tuple[float, bool]:
        done = self._chunk_progress.get(task.task_id, 0)
        n = min(n_tokens, task.prompt_len - done)
        self.chunk_steps += 1
        done += n
        if done >= task.prompt_len:
            self._chunk_progress.pop(task.task_id, None)
            self.prefill_steps += 1
            return self.lat.prefill_ms(n) + self.overhead, True
        self._chunk_progress[task.task_id] = done
        return self.lat.prefill_ms(n) + self.overhead, False

    def decode(self, tasks: Sequence[Task],
               depths: Optional[Sequence[int]] = None) -> float:
        self.decode_steps += 1
        if depths is None or not any(depths):
            self.last_commits = [1] * len(tasks)
            return self.lat.decode_ms(len(tasks)) + self.overhead
        k = max(depths)
        b = len(tasks)
        commits: List[int] = []
        for t, d in zip(tasks, depths):
            d = max(0, min(int(d), t.output_len - t.tokens_done - 1))
            rng = self._accept_rng.get(t.task_id)
            if rng is None:
                rng = np.random.default_rng(9_176 + 613 * t.task_id)
                self._accept_rng[t.task_id] = rng
            n_acc = 0
            while n_acc < d and rng.random() < self.lat.spec_accept_rate:
                n_acc += 1
            self.drafted_tokens += d
            self.accepted_tokens += n_acc
            commits.append(n_acc + 1)
        self.spec_steps += 1
        self.last_commits = commits
        return (self.lat.verify_ms(b, k) + self.lat.draft_ms(b, k)
                + self.overhead)

    def suspend(self, task: Task) -> float:
        tid = task.task_id
        if tid in self._swapped_tokens:
            raise RuntimeError(f"task {tid} already suspended")
        n = task.prompt_len + task.tokens_done
        self._swapped_tokens[tid] = n
        self.suspend_count += 1
        self.swapped_bytes += n * self.lat.kv_bytes_per_token
        return self.lat.swap_ms(n) + self.overhead

    def resume(self, task: Task) -> float:
        tid = task.task_id
        if tid not in self._swapped_tokens:
            raise RuntimeError(f"task {tid} is not suspended")
        n = self._swapped_tokens.pop(tid)
        self.resume_count += 1
        self.swapped_bytes += n * self.lat.kv_bytes_per_token
        return self.lat.swap_ms(n) + self.overhead

    def release(self, task: Task) -> None:
        self._chunk_progress.pop(task.task_id, None)
        self._swapped_tokens.pop(task.task_id, None)
        self._accept_rng.pop(task.task_id, None)

    def latency_model(self) -> LatencyModel:
        return self.lat


class PagedSimExecutor(SimExecutor):
    """SimExecutor + the held-page reporting a paged engine provides
    (used by benchmarks/kv_swap.py and tests/test_kv_swap.py): prefill
    pins the task's peak pages — deterministic and conservative, a real
    engine grows into them — suspend releases them (sim has no sharing,
    so every page is private), resume re-pins, release frees. ``budget``
    is the PageBudget to hand the scheduler."""

    def __init__(self, lat: LatencyModel, total_pages: int, page_size: int,
                 scheduling_overhead_ms: float = 0.0,
                 name: Optional[str] = None):
        super().__init__(lat, scheduling_overhead_ms, name=name)
        self.held: Dict[int, int] = {}
        self.budget = PageBudget(
            total_pages=total_pages, page_size=page_size,
            held_pages=lambda t: self.held.get(t.task_id, 0))

    @property
    def used_pages(self) -> int:
        """Pages currently pinned — the sim-side analogue of
        PagePool.used_pages, so fleet leak checks read uniformly."""
        return sum(self.held.values())

    def prefill(self, task: Task) -> float:
        self.held[task.task_id] = self.budget.pages_for(task)
        return super().prefill(task)

    def suspend(self, task: Task) -> float:
        self.held[task.task_id] = 0
        return super().suspend(task)

    def resume(self, task: Task) -> float:
        self.held[task.task_id] = self.budget.pages_for(task)
        return super().resume(task)

    def release(self, task: Task) -> None:
        self.held.pop(task.task_id, None)
        super().release(task)


class JaxExecutor(Executor):
    """Real JAX engine over repro.models with a fixed slot array.

    Decode runs the whole slot array with a per-slot active mask — the direct
    XLA-friendly image of the decode-mask-matrix column. With
    ``compact_buckets`` the active slots are gathered into the smallest
    power-of-two bucket first so step cost actually falls with column
    sparsity (DESIGN.md §3 adaptation #1).
    """

    def __init__(self, cfg, params=None, max_slots: int = 16,
                 max_seq: int = 512, seed: int = 0,
                 compact_buckets: bool = False,
                 prefill_chunk_size: Optional[int] = None):
        import jax
        import jax.numpy as jnp
        from repro.models import model as M
        if prefill_chunk_size is not None and (not cfg.has_attention
                                               or cfg.has_ssm):
            raise ValueError("chunked prefill needs a pure-attention arch "
                             "(SSM chunk-state carry is not implemented); "
                             "use atomic prefill")
        self.jax, self.jnp, self.M = jax, jnp, M
        self.cfg = cfg
        self.params = params if params is not None else M.init_params(
            cfg, jax.random.PRNGKey(seed))
        self.max_slots = max_slots
        self.max_seq = max_seq
        self.seed = seed
        self.compact_buckets = compact_buckets
        self.prefill_chunk_size = prefill_chunk_size
        self.cache = M.init_cache(cfg, max_slots, max_seq)
        self.slot_of: Dict[int, int] = {}
        self.free = list(range(max_slots))
        self.tokens = jnp.zeros((max_slots,), jnp.int32)
        self._decode_jit = jax.jit(
            lambda p, c, t, a: M.decode_step(cfg, p, c, t, a)
        ).lower(self.params, self.cache, self.tokens,
                jnp.zeros((max_slots,), bool)).compile()
        self._bucket_jit: Dict[int, Any] = {}
        if compact_buckets:
            self._build_bucket_steps()
        self._chunk_jit: Dict[int, Any] = {}
        self._chunk_progress: Dict[int, int] = {}
        if prefill_chunk_size is not None:
            self._build_chunk_steps()
        self._prefill_jit = {}
        self.last_logits: Optional[np.ndarray] = None
        self.last_prefill_logits: Optional[np.ndarray] = None

    # -- chunked prefill (DESIGN.md §5) --
    # One compiled step per chunk-size bucket ({chunk} ∪ {2^k < chunk}),
    # gathering a 1-row sub-cache at the task's slot, appending the chunk at
    # the row's current length (model.prefill_chunk), and scattering back —
    # the same gather/scatter trick as bucketed compaction, so chunk offset
    # is data, not shape, and compile count stays O(log chunk).
    def _build_chunk_steps(self):
        jax, jnp, M = self.jax, self.jnp, self.M
        cfg = self.cfg

        def step(params, cache, toks, idx):
            sub = {k: cache[k][:, idx] for k in ("k", "v")}
            sub["length"] = cache["length"][idx]
            sub["kv_pos"] = cache["kv_pos"][idx]
            logits, new_sub = M.prefill_chunk(cfg, params, sub, toks)
            out = dict(cache)
            for k in ("k", "v"):
                out[k] = cache[k].at[:, idx].set(new_sub[k])
            out["length"] = cache["length"].at[idx].set(new_sub["length"])
            out["kv_pos"] = cache["kv_pos"].at[idx].set(new_sub["kv_pos"])
            return logits, out

        # _pow2_buckets yields its limit, so this covers every _chunk_pieces
        # output: {prefill_chunk_size} ∪ {2^k < prefill_chunk_size}
        for c in sorted(set(_pow2_buckets(self.prefill_chunk_size))):
            toks = jnp.zeros((1, c), jnp.int32)
            idx = jnp.zeros((1,), jnp.int32)
            self._chunk_jit[c] = jax.jit(step).lower(
                self.params, self.cache, toks, idx).compile()

    def prefill_chunk(self, task: Task, n_tokens: int) -> Tuple[float, bool]:
        if self.prefill_chunk_size is None:
            raise RuntimeError("executor built without prefill_chunk_size")
        jnp = self.jnp
        s = self._assign_slot(task)
        L = min(task.prompt_len, self.max_seq // 2)
        done = self._chunk_progress.get(task.task_id, 0)
        if done >= L:     # progress kept until release: appending again
            raise RuntimeError(f"task {task.task_id} already prefilled")
        n = min(n_tokens, L - done)
        toks_full = _prompt_tokens(self.seed, task,
                                   self.cfg.vocab_size, L)
        ms = 0.0
        logits = None
        for c in _chunk_pieces(n, self.prefill_chunk_size):
            piece = jnp.asarray(toks_full[:, done:done + c], jnp.int32)
            idx = jnp.asarray([s], jnp.int32)
            t0 = time.perf_counter()
            logits, self.cache = self._chunk_jit[c](
                self.params, self.cache, piece, idx)
            logits.block_until_ready()
            ms += (time.perf_counter() - t0) * 1000.0
            done += c
        self._chunk_progress[task.task_id] = done
        if done >= L:
            self.last_prefill_logits = np.asarray(logits)
            self.tokens = self.tokens.at[s].set(int(jnp.argmax(logits[0])))
            return ms, True
        return ms, False

    # -- bucketed compaction (DESIGN.md §3 adaptation #1) --
    # Masked decode over the full slot array costs l(max_slots) regardless of
    # how sparse the decode-mask column is — erasing the l(b) economics
    # SLICE's admission math relies on. Compaction gathers the active slots'
    # state into the smallest power-of-two bucket, decodes that, and
    # scatters back: step cost really falls with column sparsity, with only
    # log2(max_slots) compiled variants.
    def _build_bucket_steps(self):
        jax, jnp, M = self.jax, self.jnp, self.M
        cfg = self.cfg
        state_keys = [k for k in ("k", "v", "ssm", "conv") if k in self.cache]

        def step(params, cache, tokens, idx, valid):
            sub = {k: cache[k][:, idx] for k in state_keys}
            sub["length"] = cache["length"][idx]
            if "kv_pos" in cache:
                sub["kv_pos"] = cache["kv_pos"][idx]
            logits, new_sub = M.decode_step(cfg, params, sub, tokens[idx],
                                            active=valid)
            out = dict(cache)
            for k in state_keys:
                out[k] = cache[k].at[:, idx].set(new_sub[k])
            out["length"] = cache["length"].at[idx].set(new_sub["length"])
            if "kv_pos" in cache:
                out["kv_pos"] = cache["kv_pos"].at[idx].set(new_sub["kv_pos"])
            return logits, out

        for b in _pow2_buckets(self.max_slots):
            idx = jnp.zeros((b,), jnp.int32)
            valid = jnp.zeros((b,), bool)
            self._bucket_jit[b] = jax.jit(step).lower(
                self.params, self.cache, self.tokens, idx, valid).compile()

    # -- slots --
    def _assign_slot(self, task: Task) -> int:
        if task.task_id in self.slot_of:
            return self.slot_of[task.task_id]
        if not self.free:
            raise RuntimeError("out of KV slots; release finished tasks first")
        s = self.free.pop(0)
        self.slot_of[task.task_id] = s
        return s

    def release(self, task: Task) -> None:
        self._chunk_progress.pop(task.task_id, None)
        s = self.slot_of.pop(task.task_id, None)
        if s is not None:
            self.free.append(s)
            length = self.cache["length"]
            self.cache["length"] = length.at[s].set(0)
            if "kv_pos" in self.cache:
                self.cache["kv_pos"] = self.cache["kv_pos"].at[s].set(-1)

    # -- ops --
    def prefill(self, task: Task) -> float:
        jax, jnp, M = self.jax, self.jnp, self.M
        s = self._assign_slot(task)
        L = min(task.prompt_len, self.max_seq // 2)
        key = (L,)
        toks = jnp.asarray(_prompt_tokens(self.seed, task,
                                          self.cfg.vocab_size, L), jnp.int32)
        if key not in self._prefill_jit:
            # AOT-compile so jit tracing/compilation never pollutes the
            # measured latency (it would look like a 1s prefill and trip the
            # deadline-feasibility pruner).
            fn = jax.jit(
                lambda p, t: M.prefill(self.cfg, p, t, buf_len=self.max_seq))
            self._prefill_jit[key] = fn.lower(self.params, toks).compile()
        t0 = time.perf_counter()
        last, cache1 = self._prefill_jit[key](self.params, toks)
        last.block_until_ready()
        ms = (time.perf_counter() - t0) * 1000.0
        # splice the single-row cache into slot s
        for k in ("k", "v"):
            if k in self.cache:
                self.cache[k] = self.cache[k].at[:, s].set(cache1[k][:, 0])
        for k in ("ssm", "conv"):
            if k in self.cache:
                self.cache[k] = self.cache[k].at[:, s].set(cache1[k][:, 0])
        if "kv_pos" in self.cache:
            self.cache["kv_pos"] = self.cache["kv_pos"].at[s].set(cache1["kv_pos"][0])
        self.cache["length"] = self.cache["length"].at[s].set(cache1["length"][0])
        self.last_prefill_logits = np.asarray(last)
        self.tokens = self.tokens.at[s].set(int(jnp.argmax(last[0])))
        return ms

    def decode(self, tasks: Sequence[Task],
               depths: Optional[Sequence[int]] = None) -> float:
        jnp = self.jnp
        if depths is not None and any(depths):
            raise RuntimeError("slot executor has no speculative decoding; "
                               "use PagedJaxExecutor(spec_decode=True)")
        slots = [self._assign_slot(t) for t in tasks]
        if self.compact_buckets:
            b = 1
            while b < len(slots):
                b *= 2
            b = min(b, self.max_slots)
            # pad with slots NOT in the active set: duplicate indices in the
            # scatter-back could otherwise drop an active slot's update
            # (identity writes to distinct inactive slots are harmless).
            taken = set(slots)
            pads = [s for s in range(self.max_slots) if s not in taken]
            idx = np.asarray(slots + pads[: b - len(slots)], np.int32)
            valid = np.zeros((b,), bool)
            valid[: len(slots)] = True
            t0 = time.perf_counter()
            logits, self.cache = self._bucket_jit[b](
                self.params, self.cache, self.tokens, jnp.asarray(idx),
                jnp.asarray(valid))
            logits.block_until_ready()
            ms = (time.perf_counter() - t0) * 1000.0
            self.last_logits = np.asarray(logits)[: len(slots)]
            new_toks = jnp.argmax(logits, -1).astype(jnp.int32)
            upd = jnp.zeros((self.max_slots,), bool).at[jnp.asarray(idx)].set(
                jnp.asarray(valid))
            scatter = jnp.zeros((self.max_slots,), jnp.int32).at[
                jnp.asarray(idx)].set(new_toks)
            self.tokens = jnp.where(upd, scatter, self.tokens)
            return ms
        active = np.zeros((self.max_slots,), bool)
        active[slots] = True
        t0 = time.perf_counter()
        logits, self.cache = self._decode_jit(
            self.params, self.cache, self.tokens, jnp.asarray(active))
        logits.block_until_ready()
        ms = (time.perf_counter() - t0) * 1000.0
        self.last_logits = np.asarray(logits)[slots]
        new_toks = jnp.argmax(logits, -1).astype(jnp.int32)
        self.tokens = jnp.where(jnp.asarray(active), new_toks, self.tokens)
        return ms

    def latency_model(self) -> LatencyModel:
        """Measure l(b) on the live engine (warm jit) — MeasuredLatencyModel."""
        from repro.core.task import qa_task
        probes = [b for b in (1, 2, 4, 8, self.max_slots) if b <= self.max_slots]
        warm_tasks = [qa_task() for _ in range(self.max_slots)]
        for t in warm_tasks:
            self._assign_slot(t)
        lat = _probe_latency_curve(self, warm_tasks, probes)
        for t in warm_tasks:
            self.release(t)
        return lat


class PagedJaxExecutor(Executor):
    """Real JAX engine over a paged KV arena with continuous batching.

    Where JaxExecutor reserves a contiguous ``max_seq`` buffer per slot —
    admission capped at ``max_slots`` no matter how short the sequences —
    this executor backs every task with ``ceil(tokens / page_size)`` pages
    from a shared pool. Concurrency is whatever fits in the pool: at equal
    KV bytes, short-sequence workloads admit a strictly larger batch
    (benchmarks/kv_pressure.py, EXPERIMENTS.md §KV-paging).

    The decode step batch is bucketed to the next power of two (compiled
    once per bucket, AOT) and runs model.decode_step_paged: page-table
    indirection in the data plane, either as a pure-jnp gather (portable,
    default) or the Pallas scalar-prefetch kernel (``use_paged_kernel=True``,
    DESIGN.md §3 adaptation #2).

    With ``prefix_cache=True`` a radix index over page-aligned prompt
    blocks (serving.prefix_cache, DESIGN.md §6) dedups shared prompt
    prefixes: prefill acquires the cached pages (pool.share) and computes
    only the uncached suffix; chunked prefill starts at the first uncached
    chunk; admission (page_budget) counts shared pages once and treats
    idle cached pages as reclaimable headroom (evicted on pressure).

    Tensor parallelism (DESIGN.md §9): with ``mesh=`` (a ('data','model')
    jax mesh, see launch.mesh.make_serving_mesh) the engine shards weights
    by launch.sharding.param_specs and the page arena by page_specs — per-
    device KV-head slabs over 'model', page tables replicated — and lowers
    every AOT step inside the mesh + activation_partitioning context (the
    dry-run idiom), so decode/chunk/verify run as sharded columns. The
    host-side control plane (pool, radix cache, swap arena, draft model)
    is untouched: suspend/resume gather/scatter every device's head slab
    through the same device_get/put path. Logits match the single-device
    engine to < 1e-5 (tests/test_sharded.py).

    Cache kinds (DESIGN.md §12): attention layers grow paged KV; SSM layers
    (mamba2, hymba's mamba half) carry ONE constant-size recurrent-state
    slot per task — ``[H, P, N]`` SSD state + conv tail per layer — in a
    ``SSMStateStore``-managed arena that rides the same ``self.pages`` dict,
    so one AOT decode step mixes both kinds for hybrid archs. The KV pool
    stays the logical token-length ledger for EVERY arch (pure-SSM archs
    get zero-width k/v pages), which is what keeps admission, swap, and the
    serving loop arch-generic. Because recurrent state is a running summary
    rather than a per-token log, features that rewind/share/shard per-token
    KV (spec decode, prefix cache, executor-level chunked prefill, mesh)
    raise for SSM/hybrid archs — deviations listed in DESIGN.md §12.

    Restrictions: sequences are hard-capped at max_seq (the paged cache is
    append-only; it never ring-wraps like the slot path's long-context mode).
    Mesh mode shards the jnp paged-attention path through GSPMD; the Pallas
    kernel would need a shard_map wrapper, so mesh + use_paged_kernel raises.
    """

    def __init__(self, cfg, params=None, n_pages: int = 64,
                 page_size: int = 16, max_seq: int = 512, seed: int = 0,
                 max_batch: int = 16, use_paged_kernel: bool = False,
                 prefill_chunk_size: Optional[int] = None,
                 prefix_cache: bool = False,
                 prefix_cache_pages: Optional[int] = None,
                 host_arena_bytes: Optional[int] = None,
                 spec_decode: bool = False, draft_cfg=None,
                 draft_params=None, max_spec_depth: int = 4,
                 mesh=None, async_dispatch: bool = False,
                 max_in_flight: int = 2,
                 n_state_slots: Optional[int] = None):
        import jax
        import jax.numpy as jnp
        from repro.models import model as M
        if not (cfg.has_attention or cfg.has_ssm):
            raise ValueError("PagedJaxExecutor needs an attention and/or "
                             "SSM mixer; use JaxExecutor")
        if cfg.has_ssm:
            gated = [name for name, on in (
                ("spec_decode", spec_decode),
                ("prefix_cache", prefix_cache),
                ("prefill_chunk_size", prefill_chunk_size is not None),
                ("mesh", mesh is not None)) if on]
            if gated:
                raise ValueError(
                    f"{'/'.join(gated)} unsupported for SSM/hybrid archs: "
                    "recurrent state is a running summary, not a per-token "
                    "log — it cannot be rewound, prefix-shared, chunk-"
                    "restarted at the executor level, or sharded "
                    "(DESIGN.md §12)")
        # Sliding-window archs are safe WITHOUT a window mask here: the slot
        # engine only applies the window when buf_len <= window, and this
        # engine hard-caps sequences at max_seq, so q_pos - pos < max_seq <=
        # window keeps the mask inert in exactly that regime. Beyond max_seq
        # the slot ring would silently wrap; we raise instead (decode()).
        self.jax, self.jnp, self.M = jax, jnp, M
        self.cfg = cfg
        self.mesh = mesh
        if mesh is None:
            self.params = params if params is not None else M.init_params(
                cfg, jax.random.PRNGKey(seed))
            self.pages = M.init_paged_cache(cfg, n_pages, page_size)
        else:
            self._init_sharded(params, seed, n_pages, page_size,
                               use_paged_kernel)
        self.page_size = page_size
        self.n_pages = n_pages
        self.max_seq = max_seq
        self.max_batch = max_batch
        self.seed = seed
        self.use_paged_kernel = use_paged_kernel
        self.prefill_chunk_size = prefill_chunk_size
        self.pool = KVPagePool(n_pages, page_size)
        # Host-offload KV swap (DESIGN.md §7): suspended tasks' private
        # page contents live here until resume; host_arena_bytes models
        # the edge device's limited host RAM (None = unbounded).
        self.arena = KVSwapArena(page_size, capacity_bytes=host_arena_bytes)
        # Prefix sharing (DESIGN.md §6): radix index over page-aligned
        # prompt blocks; cache hits share physical pages via pool refcounts.
        self.prefix_cache = None
        if prefix_cache:
            from repro.serving.prefix_cache import RadixPrefixCache
            self.prefix_cache = RadixPrefixCache(
                self.pool, max_pages=prefix_cache_pages or n_pages)
        self.max_pages_per_seq = -(-max_seq // page_size)
        # Cache-kind subsystem (DESIGN.md §12): SSM/hybrid archs add a
        # constant-size recurrent-state arena — slot-allocated by the
        # SSMStateStore exactly as the pool allocates pages — merged into
        # self.pages so AOT lowering/donation/async chaining carry it with
        # zero extra plumbing. CacheStore is the cross-kind audit facade.
        self.states = None
        self.n_state_slots = 0
        if cfg.has_ssm:
            self.n_state_slots = (n_state_slots if n_state_slots is not None
                                  else 2 * max_batch)
            self.states = SSMStateStore(self.n_state_slots)
            self.pages.update(M.init_state_arena(cfg, self.n_state_slots))
        self.store = CacheStore(cfg, self.pool, self.states)
        self._last_tok: Dict[int, int] = {}
        self._last_logits: Optional[np.ndarray] = None
        self._last_prefill_logits: Optional[np.ndarray] = None
        # lazy device-side sources for the two logits surfaces: commits
        # park the device array here and the property materializes it on
        # first read, so a pipelined run never pays [b, vocab] host copies
        # for logits nobody looks at
        self._last_logits_src = None
        self._last_prefill_logits_src = None
        # Async pipelining (DESIGN.md §10). The queue/ledger/stats exist in
        # both modes — sync books its blocking time straight into wait_ms,
        # async splits dispatch from commit — so the loop and benchmarks
        # read one surface regardless of mode.
        self.async_dispatch = async_dispatch
        self._sync_depth = 0          # _sync_mode() nesting (latency probes)
        self.gap_stats = GapStats()
        self.ledger = TransferLedger()
        self._queue = DispatchQueue(self._commit_step,
                                    max_in_flight=max_in_flight,
                                    rollback=self._rollback_step,
                                    stats=self.gap_stats)
        # device-resident last-token chain links: tid -> (argmax array,
        # row) into an in-flight step's lazy per-row argmax, so cycle
        # N+1's decode chains on-device off cycle N without a host
        # round-trip. _last_am remembers the newest decode's (ids, bucket,
        # argmax) so the steady state (same batch, same order) passes the
        # whole array through as the next token vector — zero per-row ops.
        self._tok_dev: Dict[int, Any] = {}
        self._last_am: Optional[Tuple[Tuple[int, ...], int, Any]] = None
        # step-input device cache (async steady state): name -> (batch
        # key, host truth snapshot, device copy). Reused only when the
        # freshly built host truth still equals the snapshot, so stale
        # entries can never change results — they just cost a re-upload.
        self._in_cache: Dict[str, Tuple[Any, np.ndarray, Any]] = {}
        self._argmax_jit = jax.jit(
            lambda l: jnp.argmax(l, -1).astype(jnp.int32))
        self._swap_pool = None        # lazy background transfer worker
        self._step_jit: Dict[int, Any] = {}
        self._build_steps()
        self._chunk_jit: Dict[int, Any] = {}
        self._chunk_progress: Dict[int, int] = {}
        if prefill_chunk_size is not None:
            self._build_chunk_steps()
        self._prefill_jit: Dict[Tuple[int, ...], Any] = {}
        self._suffix_jit: Dict[int, Any] = {}
        self._toks_memo: Dict[int, np.ndarray] = {}   # task_id -> prompt
        self._gtoks: Dict[int, np.ndarray] = {}       # group -> prefix toks
        # Speculative decoding (DESIGN.md §8): a tiny on-device draft model
        # proposes per-task windows, model.verify_step_paged checks them in
        # one AOT-bucketed call (buckets over batch x max-depth), and
        # rejected-draft pages are rolled back (pool.truncate). The
        # committed stream is greedy-identical to depth-0 decode.
        self.draft = None
        self.spec_depth = 0
        self.spec_steps = 0
        self._accepted_tokens = 0
        self._last_commits: Optional[List[int]] = None
        self._gen: Dict[int, List[int]] = {}     # committed generated toks
        self._verify_jit: Dict[Tuple[int, int], Any] = {}
        if spec_decode:
            from repro.serving.spec_decode import (DraftModel,
                                                   default_draft_config)
            if max_spec_depth < 1:
                raise ValueError("max_spec_depth must be >= 1")
            self.spec_depth = max_spec_depth
            self.draft = DraftModel(
                draft_cfg if draft_cfg is not None
                else default_draft_config(cfg),
                params=draft_params, max_slots=max_batch, max_seq=max_seq,
                seed=seed)
            if self.draft.cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {self.draft.cfg.vocab_size} != target "
                    f"vocab {cfg.vocab_size}: proposals would not be "
                    "valid target token ids")
            self._build_verify_steps()

    # -- mesh plumbing (DESIGN.md §9) --
    def _init_sharded(self, params, seed: int, n_pages: int, page_size: int,
                      use_paged_kernel: bool) -> None:
        """Tensor-parallel mode: place params and the page arena in their
        canonical shardings BEFORE any step is lowered — AOT input
        shardings are taken from the example arrays, so the layout pinned
        here must be preserved by every later update. Fresh params and
        pages are generated directly in their shardings (jit with
        out_shardings): building the full arrays first would put the whole
        model on one device, which for a model that needs the mesh is an
        out-of-memory error before sharding ever happens."""
        jax, M, cfg, mesh = self.jax, self.M, self.cfg, self.mesh
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.launch import sharding as shard_rules
        from repro.launch.mesh import batch_axes
        if use_paged_kernel:
            raise ValueError(
                "mesh mode shards the jnp paged-attention path via "
                "GSPMD; the Pallas kernel needs a shard_map wrapper "
                "(not implemented) — drop use_paged_kernel")
        if "model" not in mesh.axis_names or "data" not in mesh.axis_names:
            raise ValueError(
                "serving mesh needs ('data', 'model') axes — see "
                "launch.mesh.make_serving_mesh")
        self._batch_axes = batch_axes(mesh)
        self._repl_sh = NamedSharding(mesh, PartitionSpec())
        self._page_sh = shard_rules.to_shardings(
            mesh, shard_rules.page_specs(cfg, mesh))
        param_sh = shard_rules.to_shardings(
            mesh, shard_rules.param_specs(cfg, mesh, train=False))
        if params is None:
            self.params = jax.jit(lambda k: M.init_params(cfg, k),
                                  out_shardings=param_sh)(
                jax.random.PRNGKey(seed))
        else:
            self.params = jax.device_put(params, param_sh)
        self.pages = jax.jit(lambda: M.init_paged_cache(cfg, n_pages,
                                                        page_size),
                             out_shardings=self._page_sh)()

    def _dev_in(self, x):
        """Commit a step input as replicated over the mesh: AOT-compiled
        calls reject inputs whose shardings differ from the lowered
        examples, and fresh np/jnp arrays would land on one device.
        Identity in single-device mode."""
        x = self.jnp.asarray(x)
        if self.mesh is None:
            return x
        return self.jax.device_put(x, self._repl_sh)

    def _canonicalize_pages(self) -> None:
        """Re-pin the page arena to its canonical sharding after an eager
        (non-AOT) update — prefill splices, CoW page copies, swap-in
        scatters. Eager ops on sharded operands let GSPMD pick the result
        layout, and the next compiled step requires the canonical one."""
        if self.mesh is not None:
            self.pages = self.jax.device_put(self.pages, self._page_sh)

    def _lower(self, fn, example_args, pages_out: bool = False,
               extra_repl: int = 0):
        """AOT-compile ``fn`` against example args. In mesh mode the
        lowering runs inside the mesh + activation_partitioning context
        (the dryrun.py idiom) so sharded params/pages and the shard()
        constraints in the model code take effect; ``pages_out`` pins the
        (logits, *extras, pages) output to (replicated..., canonical page
        sharding), keeping self.pages stable across steps. ``extra_repl``
        counts replicated outputs between logits and pages (the decode
        step's fused argmax).

        A ``pages_out`` step owns the arena: argument 1 (the pages) is
        donated, so the step updates the arena in place and the arrays
        passed in are deleted by the call. ``self.pages`` is the only live
        reference to the arena (DESIGN.md §3)."""
        jax = self.jax
        donate = (1,) if pages_out else ()
        out_sh = ((self._repl_sh,) * (1 + extra_repl) + (self._page_sh,)
                  if pages_out and self.mesh is not None else None)
        step = jax.jit(fn, out_shardings=out_sh, donate_argnums=donate)
        if self.mesh is None:
            return step.lower(*example_args).compile()
        from repro.models.partitioning import activation_partitioning
        with self.mesh, activation_partitioning(self._batch_axes, "model"):
            return step.lower(*example_args).compile()

    # -- async pipelining (DESIGN.md §10) --
    def _async_on(self) -> bool:
        return self.async_dispatch and self._sync_depth == 0

    @contextlib.contextmanager
    def _sync_mode(self):
        """Force synchronous semantics for a block — latency-model probes
        must measure real step time, not dispatch-only time."""
        self._commit_pending()
        self._sync_depth += 1
        try:
            yield
        finally:
            self._sync_depth -= 1

    def _commit_pending(self) -> None:
        q = getattr(self, "_queue", None)
        if q is not None and len(q):
            q.commit_all()

    def _cached_in(self, name: str, key, host: np.ndarray):
        """Reuse the previous cycle's device copy of a step input when
        the freshly built host truth is unchanged — steady-state
        dispatch-ahead cycles then run transfer-free, chaining tokens
        and lengths off the step's own fused outputs."""
        ent = self._in_cache.get(name)
        if ent is not None and ent[0] == key and np.array_equal(ent[1], host):
            return ent[2]
        dev = self._dev_in(host)
        self._in_cache[name] = (key, host, dev)
        return dev

    def _push(self, step: PendingStep) -> float:
        """Enqueue a dispatched step; returns the ms the push spent
        committing older steps (the stall path). That time is already
        booked as ``wait_ms`` by the queue, so dispatch-site timers must
        subtract it or host_gap would double-count every stall."""
        w0 = self.gap_stats.wait_ms
        self._queue.push(step)
        return self.gap_stats.wait_ms - w0

    def drain(self) -> None:
        """Commit every in-flight step and wait out background transfers —
        the end-of-run barrier the serving loop issues before reading
        final metrics."""
        self._commit_pending()
        self.ledger.wait()

    def _commit_step(self, step: PendingStep) -> None:
        """Observe one in-flight step's device results (the only sync
        point in async mode) and apply its deferred host-state updates.
        Runs in dispatch order via the DispatchQueue."""
        p = step.payload
        if step.kind == "prefill":
            tid = p["tid"]
            # only the argmax scalar must land now (the first-token chain);
            # the full logits row stays on device until someone actually
            # reads last_prefill_logits (lazy materialization — copying
            # [1, vocab] per commit would serialize host on the transfer)
            arr, r = p["tok_dev"][tid]
            self._last_prefill_logits = None
            self._last_prefill_logits_src = p["logits"]
            self._set_first_token(tid, int(np.asarray(arr)[r]))
            self._pop_tok_dev(tid, p["tok_dev"].get(tid))
        elif step.kind == "decode":
            ids = step.task_ids
            self._last_logits = None
            self._last_logits_src = (p["logits"], len(ids))
            toks = np.asarray(p["argmax"])[: len(ids)]
            for i, tok in zip(ids, toks):
                self._last_tok[i] = int(tok)
                self._pop_tok_dev(i, p["tok_dev"].get(i))
                self._gen.setdefault(i, []).append(int(tok))
            self._last_commits = [1] * len(ids)
        elif step.kind == "verify":
            self._commit_verify(step)
        else:  # pragma: no cover - future step kinds
            raise ValueError(f"unknown pending step kind {step.kind!r}")

    def _pop_tok_dev(self, tid: int, entry) -> None:
        """Drop the in-flight chain link this step registered — but only
        if a later in-flight step has not already replaced it (identity
        check): commits must never erase a newer chain link."""
        if entry is not None and self._tok_dev.get(tid) is entry:
            del self._tok_dev[tid]

    def _rollback_step(self, step: PendingStep) -> None:
        """Drain-on-error: rewind the pool-side reservations an
        uncommitted step made at dispatch, newest first, so a poisoned
        pipeline suffix leaves committed state consistent. Device results
        are simply dropped (functional arrays — nothing to undo)."""
        p = step.payload
        if step.kind == "prefill":
            tid = p["tid"]
            if p.get("fresh"):          # this dispatch allocated the task
                if self.pool.holds(tid):
                    self.pool.free(tid)
                self._chunk_progress.pop(tid, None)
            elif "pre_len" in p and self.pool.holds(tid):
                self.pool.truncate(tid, p["pre_len"])
                if "pre_progress" in p:
                    self._chunk_progress[tid] = p["pre_progress"]
            self._pop_tok_dev(tid, p["tok_dev"].get(tid))
        else:
            for i, ln in p.get("pre_lengths", {}).items():
                if self.pool.holds(i):
                    self.pool.truncate(i, ln)
                self._pop_tok_dev(i, p["tok_dev"].get(i))
                if step.kind == "verify" and self.draft is not None:
                    self.draft.drop(i)

    def _chain_tok(self, tid: int):
        """Next-step input token for ``tid``: a lazy scalar sliced from the
        in-flight argmax when one exists (cycle N+1 chaining off cycle
        N's un-observed logits), else the committed host value."""
        e = self._tok_dev.get(tid)
        if e is not None:
            arr, r = e
            return arr[r]
        return np.int32(self._last_tok[tid])

    def _chain_vector(self, ids: List[int], b: int):
        """Steady-state fast path: when the previous in-flight decode had
        the same tasks in the same rows at the same bucket, its argmax
        array IS the next token vector (pad rows carry stale argmaxes —
        inert under the active mask). Returns None when any link went
        stale (commit, suspend, finish, reorder) — callers fall back to
        per-row chaining."""
        prev = self._last_am
        if prev is None:
            return None
        pids, pb, am = prev
        if pb != b or pids != tuple(ids):
            return None
        for r, i in enumerate(ids):
            e = self._tok_dev.get(i)
            if e is None or e[0] is not am or e[1] != r:
                return None
        return am

    # observation surfaces: reading any of them forces the pending
    # pipeline to commit, so callers (loop, tests, benchmarks) always see
    # exactly the synchronous engine's values — the byte-identity contract
    @property
    def last_tok(self) -> Dict[int, int]:
        self._commit_pending()
        return self._last_tok

    @property
    def last_logits(self) -> Optional[np.ndarray]:
        self._commit_pending()
        if self._last_logits_src is not None:
            arr, n = self._last_logits_src
            self._last_logits = np.asarray(arr)[:n]
            self._last_logits_src = None
        return self._last_logits

    @property
    def last_prefill_logits(self) -> Optional[np.ndarray]:
        self._commit_pending()
        if self._last_prefill_logits_src is not None:
            self._last_prefill_logits = np.asarray(
                self._last_prefill_logits_src)
            self._last_prefill_logits_src = None
        return self._last_prefill_logits

    @property
    def last_commits(self) -> Optional[List[int]]:
        self._commit_pending()
        return self._last_commits

    @property
    def accepted_tokens(self) -> int:
        self._commit_pending()
        return self._accepted_tokens

    # -- compiled steps (one per power-of-two batch bucket) --
    def _build_steps(self):
        jnp, M = self.jnp, self.M
        cfg, maxp = self.cfg, self.max_pages_per_seq

        def step(params, pages, pt, lengths, tokens, active, *slots):
            # fused argmax + next-lengths: one compiled call yields the
            # next-token vector AND next cycle's length vector, so the
            # async chain feeds both straight back in (DESIGN.md §10) —
            # no second dispatch, no host round-trips, and commits copy
            # b ints instead of materializing [b, vocab] logits.
            # SSM/hybrid archs thread a per-row state-slot vector (*slots
            # empty for dense archs — their trace is byte-identical to the
            # pre-cache-kind engine, DESIGN.md §12)
            kw = {"state_slots": slots[0]} if slots else {}
            logits, pages = M.decode_step_paged(
                cfg, params, pages, pt, lengths, tokens, active,
                use_kernel=self.use_paged_kernel, **kw)
            return (logits, jnp.argmax(logits, -1).astype(jnp.int32),
                    lengths + active.astype(jnp.int32), pages)

        for b in _pow2_buckets(self.max_batch):
            pt = self._dev_in(jnp.full((b, maxp), -1, jnp.int32))
            ln = self._dev_in(jnp.zeros((b,), jnp.int32))
            tk = self._dev_in(jnp.zeros((b,), jnp.int32))
            av = self._dev_in(jnp.zeros((b,), bool))
            extra = ((self._dev_in(jnp.full((b,), -1, jnp.int32)),)
                     if cfg.has_ssm else ())
            self._step_jit[b] = self._lower(
                step, (self.params, self.pages, pt, ln, tk, av) + extra,
                pages_out=True, extra_repl=2)

    # -- chunked prefill (DESIGN.md §5): one compiled step per chunk-size
    # bucket; pages for each chunk are allocated incrementally as the chunk
    # arrives, never reserved at the prompt's peak up front.
    def _build_chunk_steps(self):
        jnp, M = self.jnp, self.M
        cfg, maxp = self.cfg, self.max_pages_per_seq

        def step(params, pages, pt, lengths, toks):
            return M.prefill_chunk_paged(cfg, params, pages, pt, lengths,
                                         toks, use_kernel=self.use_paged_kernel)

        # _pow2_buckets yields its limit, so this covers every _chunk_pieces
        # output: {prefill_chunk_size} ∪ {2^k < prefill_chunk_size}
        for c in sorted(set(_pow2_buckets(self.prefill_chunk_size))):
            pt = self._dev_in(jnp.full((1, maxp), -1, jnp.int32))
            ln = self._dev_in(jnp.zeros((1,), jnp.int32))
            toks = self._dev_in(jnp.zeros((1, c), jnp.int32))
            self._chunk_jit[c] = self._lower(
                step, (self.params, self.pages, pt, ln, toks),
                pages_out=True)

    # -- speculative decoding (DESIGN.md §8): one compiled verify step per
    # (batch bucket, depth bucket) — tokens [b, K+1] where K covers the
    # largest per-row depth in the call; shallower rows ride the same shape
    # with their pad positions causally inert (untabled scatter + masked
    # attention), so compile count stays O(log batch * log depth).
    def _build_verify_steps(self):
        jnp, M = self.jnp, self.M
        cfg, maxp = self.cfg, self.max_pages_per_seq

        def step(params, pages, pt, lengths, toks):
            return M.verify_step_paged(cfg, params, pages, pt, lengths,
                                       toks, use_kernel=self.use_paged_kernel)

        for b in _pow2_buckets(self.max_batch):
            for K in _pow2_buckets(self.spec_depth):
                pt = self._dev_in(jnp.full((b, maxp), -1, jnp.int32))
                ln = self._dev_in(jnp.zeros((b,), jnp.int32))
                toks = self._dev_in(jnp.zeros((b, K + 1), jnp.int32))
                self._verify_jit[(b, K)] = self._lower(
                    step, (self.params, self.pages, pt, ln, toks),
                    pages_out=True)

    def _set_first_token(self, tid: int, tok: int) -> None:
        """Record a completed prefill's first output token — and, with spec
        decoding on, start the committed-generation history the draft
        model's catch-up replays. The history is kept for EVERY paged
        engine (not just spec ones): it is how the async equivalence
        drivers reconstruct full token streams without forcing a commit
        per step (tests/helpers.py drive_async)."""
        self._last_tok[tid] = tok
        self._gen[tid] = [tok]

    def _committed_tokens(self, task: Task) -> np.ndarray:
        """Token ids at the committed cached positions 0..pool.length-1:
        the (effective) prompt followed by generated tokens. The last
        committed token (``last_tok``, KV not yet written) is NOT included
        — it is the first token the next decode/verify window feeds."""
        tid = task.task_id
        L = self.pool.length(tid)
        prompt = self._task_tokens(task)[0]
        if L <= prompt.shape[0]:
            return prompt[:L]
        gen = self._gen.get(tid, [])
        return np.concatenate(
            [prompt, np.asarray(gen, dtype=prompt.dtype)])[:L]

    def generated_tokens(self, task: Task) -> List[int]:
        """Committed generated token ids so far — the greedy-equivalence
        contract surface (tests/test_spec_decode.py) and the stream the
        async drivers reconstruct from (tests/helpers.py drive_async).
        Reading it forces pending pipeline commits."""
        self._commit_pending()
        return list(self._gen.get(task.task_id, []))

    # -- prefix sharing (DESIGN.md §6) --
    def _effective_prompt(self, task: Task) -> int:
        return min(task.prompt_len, self.max_seq // 2)

    def _task_tokens(self, task: Task) -> np.ndarray:
        """Memoized per-task prompt tokens — cached_prompt_tokens sits on
        the scheduler's per-reschedule pruning path, so the rng draw must
        not repeat per call. Purged on release()."""
        toks = self._toks_memo.get(task.task_id)
        if toks is None:
            toks = _prompt_tokens(self.seed, task, self.cfg.vocab_size,
                                  self._effective_prompt(task))
            self._toks_memo[task.task_id] = toks
        return toks

    def _group_tokens(self, group: int, k: int) -> np.ndarray:
        """First k tokens of a prefix group's stream (bulk rng draws are
        prefix-consistent, so the memo only ever grows)."""
        cur = self._gtoks.get(group)
        if cur is None or cur.shape[0] < k:
            grng = np.random.default_rng(
                (self.seed + 1) * 7_919 + 1_000_003 * (group + 1))
            cur = grng.integers(0, self.cfg.vocab_size, (max(k, 1),))
            self._gtoks[group] = cur
        return cur[:k]

    def _reserve(self, fn):
        """Run a pool reservation, evicting LRU prefix-cache pages until it
        fits before giving up: cached-but-idle prefix KV is reclaimable
        headroom, not spent memory. OutOfPages still propagates when the
        pool is genuinely full of live sequences."""
        while True:
            try:
                return fn()
            except OutOfPages:
                cache = self.prefix_cache
                if cache is None:
                    raise
                # escalate the eviction batch (1, 2, 4, ...): owner-shared
                # leaves free nothing, so fixed-size nibbles could rescan
                # the trie once per indexed node before finding a free page
                before = self.pool.free_pages
                batch = 1
                while (cache.pages_indexed > 0
                       and self.pool.free_pages == before):
                    if cache.evict(batch) == 0:
                        break
                    batch *= 2
                if self.pool.free_pages == before:
                    raise

    def _ensure_range_writable(self, tid: int, start: int, end: int) -> None:
        """Copy-on-write defense: every page receiving tokens [start, end)
        must be private to ``tid``. With page-aligned prefix matching a
        shared page is an immutable full block — a task's own writes land
        past the shared boundary in fresh pages — so this only fires on
        boundary cases, but it guarantees divergent suffixes never alias
        (pool.fork copies the bookkeeping; the device page is copied
        here)."""
        if end <= start:
            return
        psz = self.page_size
        for idx in range(start // psz, (end - 1) // psz + 1):
            forked = self._reserve(lambda i=idx: self.pool.fork(tid, i))
            if forked is not None:
                old, new = forked
                for name in ("k_pages", "v_pages"):
                    self.pages[name] = self.pages[name].at[:, new].set(
                        self.pages[name][:, old])
                self._canonicalize_pages()

    def _acquire_prefix(self, task: Task, toks_np) -> int:
        """Register this task over the cached page-aligned prefix of its
        prompt (pool.share — zero copies). Capped at L-1 tokens so at least
        one suffix token is always recomputed: its logits seed the first
        output token. Returns tokens skipped (0 on miss/disabled)."""
        if self.prefix_cache is None:
            return 0
        hit, _ = self.prefix_cache.acquire(task.task_id, toks_np[0],
                                           max_tokens=toks_np.shape[1] - 1)
        return hit

    def _insert_prefix(self, task: Task, toks_np,
                       upto: Optional[int] = None) -> None:
        """Index the full-page prefix of a (possibly partial) prefill so
        later tasks with the same opening tokens share its pages. Chunked
        prefill inserts after every chunk — full pages of a mid-prefill
        prompt are already immutable, and early insertion is what lets an
        interleaved same-group prefill start hitting before this one
        completes."""
        if self.prefix_cache is None:
            return
        n = toks_np.shape[1] if upto is None else min(upto, toks_np.shape[1])
        n_full = n // self.page_size
        if n_full:
            self.prefix_cache.insert(
                toks_np[0, : n_full * self.page_size],
                self.pool.page_table(task.task_id)[:n_full])

    def cached_prompt_tokens(self, task: Task) -> int:
        """Prompt tokens already resident for this task: its own prefill
        progress, or the radix cache's matched prefix. The scheduler uses
        this as TTFT credit (deadline-feasibility prices only the uncached
        prompt tail)."""
        L = self._effective_prompt(task)
        if self.pool.holds(task.task_id):
            return min(self.pool.length(task.task_id), L)
        if self.prefix_cache is None:
            return 0
        matched, _ = self.prefix_cache.match(self._task_tokens(task)[0],
                                             touch=False)
        cap = ((L - 1) // self.page_size) * self.page_size
        return min(matched, max(cap, 0))

    def prompt_progress(self, task: Task) -> int:
        """Prompt tokens cached so far (includes prefix-cache credit) — the
        serving loop advances Task.prefill_done_tokens from this, so a
        cache-hit task's TTFT accounting reflects the skipped chunks."""
        return self._chunk_progress.get(task.task_id, 0)

    def prefill_chunk(self, task: Task, n_tokens: int) -> Tuple[float, bool]:
        if self.prefill_chunk_size is None:
            raise RuntimeError("executor built without prefill_chunk_size")
        jnp = self.jnp
        tid = task.task_id
        L = self._effective_prompt(task)
        done = self._chunk_progress.get(tid, 0)
        if done >= L or (done == 0 and self.pool.holds(tid)):
            raise RuntimeError(f"task {tid} already prefilled")
        toks_full = self._task_tokens(task)
        if done == 0:
            # chunked prefill starts at the first uncached chunk: the
            # matched prefix pages are shared, never recomputed
            done = self._acquire_prefix(task, toks_full)
            if done:
                self._chunk_progress[tid] = done
        n = min(n_tokens, L - done)
        async_on = self._async_on()
        fresh = not self.pool.holds(tid)
        pre_len = 0 if fresh else self.pool.length(tid)
        pre_progress = done
        ms = 0.0
        logits = None
        t_all = time.perf_counter()
        for c in _chunk_pieces(n, self.prefill_chunk_size):
            # one compiled step per piece: its bucket is the piece's size
            with span("prefill_chunk/piece", lambda: {
                    "task": tid, "start": done, "n": c, "bucket": c}):
                with span("prefill_chunk/piece/prep"):
                    # incremental allocation: an OutOfPages here propagates
                    # with the pool and progress consistent (progress is
                    # advanced per PIECE, below), so a deferred task
                    # resumes from its cached tokens
                    if self.pool.holds(tid):
                        self._reserve(
                            lambda e=done + c: self.pool.extend(tid, e))
                    else:
                        self._reserve(lambda e=c: self.pool.alloc(tid, e))
                    self._ensure_range_writable(tid, done, done + c)
                    row = self.pool.page_table(tid)
                    pt = np.full((1, self.max_pages_per_seq), -1, np.int32)
                    pt[0, : len(row)] = row
                    piece = self._dev_in(jnp.asarray(
                        toks_full[:, done:done + c], jnp.int32))
                t0 = time.perf_counter()
                with span("prefill_chunk/piece/launch"):
                    logits, self.pages = self._chunk_jit[c](
                        self.params, self.pages, self._dev_in(pt),
                        self._dev_in(jnp.asarray([done], jnp.int32)), piece)
                if not async_on:
                    with span("prefill_chunk/piece/sync"):
                        logits.block_until_ready()
                    ms += (time.perf_counter() - t0) * 1000.0
                done += c
                self._chunk_progress[tid] = done
                self._insert_prefix(task, toks_full, upto=done)
        if done >= L:
            if logits is None:       # fully cached via acquire: the final
                # block is capped at L-1, so at least one token always
                # remains to compute — logits cannot be None here
                raise RuntimeError(f"task {tid}: empty final chunk")
            with span("prefill_chunk/first_token"):
                if async_on:
                    entry = (self._argmax_jit(logits), 0)
                    self._tok_dev[tid] = entry
                    waited = self._push(PendingStep(
                        "prefill", [tid],
                        {"tid": tid, "logits": logits,
                         "tok_dev": {tid: entry}, "fresh": fresh,
                         "pre_len": pre_len, "pre_progress": pre_progress}))
                    ms = max(0.0, (time.perf_counter() - t_all) * 1000.0
                             - waited)
                    self.gap_stats.dispatch_ms += ms
                else:
                    self._last_prefill_logits_src = None
                    self._last_prefill_logits = np.asarray(logits)
                    self._set_first_token(tid, int(jnp.argmax(logits[0])))
                    self.gap_stats.wait_ms += ms
            return ms, True
        if async_on:
            ms = (time.perf_counter() - t_all) * 1000.0
            self.gap_stats.dispatch_ms += ms
        else:
            self.gap_stats.wait_ms += ms
        return ms, False

    def page_budget(self) -> PageBudget:
        """Admission-side view of the pool for SliceScheduler: peak pages per
        task (capped prompt + full output) against the pool, counting pages
        currently held by running tasks. seq_cap/max_tasks mirror this
        engine's hard limits so admission never composes a batch the engine
        would raise on. With the prefix cache enabled, admission sees the
        live free count (plus reclaimable cached pages) and counts each
        shared prompt prefix once (DESIGN.md §6)."""
        free_pages_now = None
        prefix_pages = None
        if self.prefix_cache is not None:
            cache, psz = self.prefix_cache, self.page_size

            def free_pages_now():
                return self.pool.free_pages + cache.reclaimable_pages()

            def prefix_pages(t):
                if getattr(t, "prefix_group", None) is None:
                    return None, 0
                L = self._effective_prompt(t)
                k = min(t.prefix_len or 0, max(L - 1, 0))
                kp = k // psz
                if self.prefill_chunk_size is not None and kp:
                    # chunked prefills interleave, so insert-at-completion
                    # ordering no longer guarantees a within-round
                    # discount is physically realized — discount only
                    # pages resident RIGHT NOW (per-chunk insertion makes
                    # admission catch up at the next reschedule). Atomic
                    # prefills drain serially before any decode, where the
                    # declared count is exact.
                    matched, _ = cache.match(self._group_tokens(
                        t.prefix_group, kp * psz), touch=False)
                    kp = min(kp, matched // psz)
                return ("prefix", t.prefix_group), kp
        kw = dict(
            total_pages=self.n_pages, page_size=self.page_size,
            prompt_cap=self.max_seq // 2, seq_cap=self.max_seq,
            max_tasks=self.max_batch,
            held_pages=lambda t: self.pool.resident_page_count(t.task_id),
            free_pages_now=free_pages_now, prefix_pages=prefix_pages)
        if self.states is None:
            return PageBudget(**kw)
        # SSM/hybrid archs: admission additionally reserves one constant-
        # size recurrent-state slot per task, under the same headroom
        # arithmetic as pages (DESIGN.md §12)
        return StateBudget(
            total_states=self.n_state_slots,
            state_bytes=self.store.state_bytes,
            page_bytes=self.store.page_bytes,
            held_states=lambda t: self.states.resident_slot_count(t.task_id),
            **kw)

    # -- ops --
    def prefill(self, task: Task) -> float:
        with span("prefill", lambda: {"task": task.task_id,
                                      "n": self._effective_prompt(task)}):
            return self._prefill(task)

    def _prefill(self, task: Task) -> float:
        jax, jnp, M = self.jax, self.jnp, self.M
        tid = task.task_id
        L = self._effective_prompt(task)
        if self.pool.holds(tid):
            raise RuntimeError(f"task {tid} already prefilled")
        toks_np = self._task_tokens(task)
        hit = self._acquire_prefix(task, toks_np)    # pool.share on a hit
        if hit > 0:
            try:
                ms = self._prefill_suffix(task, toks_np, hit, L)
            except OutOfPages:
                # roll back the share so a deferred task re-enters prefill
                # cleanly — the OutOfPages contract is 'state unchanged'
                self.pool.free(tid)
                raise
            self._insert_prefix(task, toks_np)
            return ms
        phys = self._reserve(
            lambda: self.pool.alloc(tid, L))         # OutOfPages -> caller
        slot = -1
        if self.states is not None:
            try:
                slot = self.states.alloc(tid)
            except OutOfStates:
                # OutOfStates is state-unchanged; undo the page reservation
                # so the deferred task re-enters prefill cleanly
                self.pool.free(tid)
                raise
        toks = self._dev_in(jnp.asarray(toks_np, jnp.int32))
        key = (L,)
        if key not in self._prefill_jit:
            # AOT-compile so jit tracing never pollutes the measured latency
            # (same rationale as JaxExecutor.prefill).
            self._prefill_jit[key] = self._lower(
                lambda p, t: M.prefill(self.cfg, p, t, buf_len=self.max_seq),
                (self.params, toks))
        async_on = self._async_on()
        t0 = time.perf_counter()
        last, cache1 = self._prefill_jit[key](self.params, toks)
        disp = time.perf_counter() - t0
        if not async_on:
            last.block_until_ready()
            ms = (time.perf_counter() - t0) * 1000.0
        # scatter the contiguous single-row cache into the allocated pages
        # (pure lazy jnp updates — legal to chain un-synced in async mode).
        # The splice's host dispatch time is booked in NEITHER mode's gap:
        # the sync path has always measured compute only, and the async
        # dispatch window must span the same ops or the modes' host-gap
        # numbers stop being comparable.
        n_alloc, psz = len(phys), self.page_size
        span = n_alloc * psz
        idx = jnp.asarray(phys, jnp.int32)
        if self.cfg.has_attention:
            from repro.models.layers import lane_pad
            for name, src in (("k_pages", cache1["k"]),
                              ("v_pages", cache1["v"])):
                # [L,1,Hkv,max_seq,hd] -> [L,n_alloc,Hkv,psz,W]
                view = (src[:, 0, :, :span, :]
                        .reshape(src.shape[0], src.shape[2], n_alloc, psz, -1)
                        .swapaxes(1, 2))
                self.pages[name] = self.pages[name].at[:, idx].set(
                    lane_pad(view, self.pages[name].shape[-1]))
        if self.states is not None:
            # splice the prefill's final recurrent state into the task's
            # slot — the whole per-task state is one fixed-size "page"
            self.pages["ssm_state"] = (
                self.pages["ssm_state"].at[:, slot].set(
                    cache1["ssm"][:, 0].astype(
                        self.pages["ssm_state"].dtype)))
            self.pages["conv_state"] = (
                self.pages["conv_state"].at[:, slot].set(
                    cache1["conv"][:, 0].astype(
                        self.pages["conv_state"].dtype)))
        self._canonicalize_pages()
        if async_on:
            t1 = time.perf_counter()
            entry = (self._argmax_jit(last), 0)
            self._tok_dev[tid] = entry
            waited = self._push(PendingStep(
                "prefill", [tid],
                {"tid": tid, "logits": last, "tok_dev": {tid: entry},
                 "fresh": True}))
            disp += time.perf_counter() - t1
            ms = max(0.0, disp * 1000.0 - waited)
            self.gap_stats.dispatch_ms += ms
        else:
            self._last_prefill_logits_src = None
            self._last_prefill_logits = np.asarray(last)
            self._set_first_token(tid, int(jnp.argmax(last[0])))
            self.gap_stats.wait_ms += ms
        self._insert_prefix(task, toks_np)
        return ms

    def _suffix_step(self, c: int):
        """Compiled prefill_chunk_paged step for a power-of-two piece size
        — the suffix jit cache is bounded at O(log max_seq) entries, same
        economics as the decode/chunk buckets."""
        if c not in self._suffix_jit:
            jnp, M = self.jnp, self.M
            pt0 = self._dev_in(jnp.full((1, self.max_pages_per_seq), -1,
                                        jnp.int32))
            ln0 = self._dev_in(jnp.zeros((1,), jnp.int32))
            tk0 = self._dev_in(jnp.zeros((1, c), jnp.int32))

            def step(params, pages, pt, lengths, toks):
                return M.prefill_chunk_paged(
                    self.cfg, params, pages, pt, lengths, toks,
                    use_kernel=self.use_paged_kernel)

            self._suffix_jit[c] = self._lower(
                step, (self.params, self.pages, pt0, ln0, tk0),
                pages_out=True)
        return self._suffix_jit[c]

    def _prefill_suffix(self, task: Task, toks_np, start: int,
                        L: int) -> float:
        """Cache-hit atomic prefill: only the uncached suffix runs through
        the engine, its queries attending over the shared prefix pages.
        The suffix is decomposed into power-of-two pieces (largest first),
        so arbitrary (prompt, prefix) length pairs reuse one small set of
        compiled steps. The skipped prefix is the TTFT win the prefix
        cache exists for."""
        jnp = self.jnp
        tid = task.task_id
        self._reserve(lambda: self.pool.extend(tid, L))
        self._ensure_range_writable(tid, start, L)
        row = self.pool.page_table(tid)
        pt = np.full((1, self.max_pages_per_seq), -1, np.int32)
        pt[0, : len(row)] = row
        pt = self._dev_in(jnp.asarray(pt))
        n = L - start
        pieces = []                          # binary decomposition of n
        b = 1 << (n.bit_length() - 1)
        while n:
            if n >= b:
                pieces.append(b)
                n -= b
            b >>= 1
        async_on = self._async_on()
        done = start
        ms = 0.0
        logits = None
        t_all = time.perf_counter()
        for c in pieces:
            fn = self._suffix_step(c)
            piece = self._dev_in(jnp.asarray(toks_np[:, done:done + c],
                                             jnp.int32))
            t0 = time.perf_counter()
            logits, self.pages = fn(
                self.params, self.pages, pt,
                self._dev_in(jnp.asarray([done], jnp.int32)), piece)
            if not async_on:
                logits.block_until_ready()
                ms += (time.perf_counter() - t0) * 1000.0
            done += c
        if async_on:
            entry = (self._argmax_jit(logits), 0)
            self._tok_dev[tid] = entry
            waited = self._push(PendingStep(
                "prefill", [tid],
                {"tid": tid, "logits": logits, "tok_dev": {tid: entry},
                 "fresh": True}))
            ms = max(0.0, (time.perf_counter() - t_all) * 1000.0 - waited)
            self.gap_stats.dispatch_ms += ms
        else:
            self._last_prefill_logits_src = None
            self._last_prefill_logits = np.asarray(logits)
            self._set_first_token(tid, int(jnp.argmax(logits[0])))
            self.gap_stats.wait_ms += ms
        return ms

    def decode(self, tasks: Sequence[Task],
               depths: Optional[Sequence[int]] = None) -> float:
        if len(tasks) > self.max_batch:
            raise RuntimeError(f"decode batch {len(tasks)} > max_batch "
                               f"{self.max_batch}")
        if depths is not None and any(depths):
            if self.draft is None:
                raise RuntimeError("executor built without spec_decode=True")
            return self._decode_spec(tasks, [int(d) for d in depths])
        with span("decode") as sp:
            return self._decode(tasks, sp)

    def _decode(self, tasks: Sequence[Task], sp) -> float:
        """One plain decode step, in the phases the profiler's spans name:
        ``prep`` (page reservation, CoW, page table and inputs),
        ``launch`` (the compiled step's call), ``sync`` (the wait on its
        results, or under async dispatch on an older step's commit) and
        ``readback`` (the token read)."""
        jnp = self.jnp
        ids = [t.task_id for t in tasks]
        t_disp = time.perf_counter()
        async_on = self._async_on()
        with span("decode/prep"):
            lengths = [self.pool.length(i) for i in ids]
            for i, ln in zip(ids, lengths):
                if ln + 1 > self.max_seq:
                    raise RuntimeError(
                        f"task {i} exceeds max_seq {self.max_seq}")
                self._reserve(
                    lambda i=i, ln=ln: self.pool.extend(i, ln + 1))
                self._ensure_range_writable(i, ln, ln + 1)  # CoW (§6)
            b = 1
            while b < len(tasks):
                b *= 2
            b = min(b, self.max_batch)
            maxp = self.max_pages_per_seq
            pt = np.full((b, maxp), -1, np.int32)
            for r, i in enumerate(ids):
                row = self.pool.page_table(i)
                pt[r, : len(row)] = row
            ln = np.zeros((b,), np.int32)
            ln[: len(ids)] = lengths
            av = np.zeros((b,), bool)
            av[: len(ids)] = True
            sl = None
            if self.states is not None:
                # per-row recurrent-state slots; pad rows carry -1 (the
                # step's write mask drops them, the clipped read is inert)
                sl = np.full((b,), -1, np.int32)
                sl[: len(ids)] = [self.states.slot_of(i) for i in ids]
            if not async_on:
                tk = np.zeros((b,), np.int32)
                tk[: len(ids)] = [self._last_tok[i] for i in ids]
        sp.set(lambda: {"b": b, "rows": len(ids),
                        "ctx_max": max(lengths) + 1})
        if async_on:
            # dispatch-ahead: the input token vector chains on-device off
            # the in-flight argmax — no host round-trip — and the step's
            # observation rides the queue until commit time. Plain decode
            # always commits exactly one token per task (control flow is
            # length-based), so host accounting can proceed optimistically
            # at dispatch.
            with span("decode/launch"):
                tk_dev = self._chain_vector(ids, b)
                if tk_dev is None:
                    if any(i in self._tok_dev for i in ids):
                        tk_dev = jnp.stack(
                            [self._chain_tok(i) for i in ids]
                            + [np.int32(0)] * (b - len(ids)))
                    else:            # fully committed: plain host vector
                        tk_np = np.zeros((b,), np.int32)
                        tk_np[: len(ids)] = [self._last_tok[i] for i in ids]
                        tk_dev = tk_np
                key = (tuple(ids), b)
                extra = (() if sl is None
                         else (self._cached_in("sl", key, sl),))
                logits, am, ln_next, self.pages = self._step_jit[b](
                    self.params, self.pages,
                    self._cached_in("pt", key, pt),
                    self._cached_in("ln", key, ln),
                    self._dev_in(tk_dev),
                    self._cached_in("av", key, av), *extra)
                # chain next cycle's lengths off the fused output: every
                # active row grew by exactly one token, which is also what
                # pool.length will report when the next decode builds ln
                self._in_cache["ln"] = (key, (ln + av).astype(np.int32),
                                        ln_next)
                tok_dev = {}
                for r, i in enumerate(ids):
                    tok_dev[i] = self._tok_dev[i] = (am, r)
                self._last_am = (tuple(ids), b, am)
            with span("decode/sync"):
                waited = self._push(PendingStep(
                    "decode", ids,
                    {"logits": logits, "argmax": am, "tok_dev": tok_dev,
                     "pre_lengths": dict(zip(ids, lengths))}))
            ms = max(0.0, (time.perf_counter() - t_disp) * 1000.0 - waited)
            self.gap_stats.dispatch_ms += ms
            return ms
        t0 = time.perf_counter()
        with span("decode/launch"):
            extra = () if sl is None else (self._dev_in(sl),)
            logits, am, _, self.pages = self._step_jit[b](
                self.params, self.pages, self._dev_in(pt), self._dev_in(ln),
                self._dev_in(tk), self._dev_in(av), *extra)
        with span("decode/sync"):
            am.block_until_ready()
        ms = (time.perf_counter() - t0) * 1000.0
        with span("decode/readback"):
            # logits stay device-resident until someone reads last_logits —
            # the sync path shares the async commit's lazy materialization
            self._last_logits = None
            self._last_logits_src = (logits, len(ids))
            new_toks = np.asarray(am)[: len(ids)]
            for i, tok in zip(ids, new_toks):
                self._last_tok[i] = int(tok)
                self._tok_dev.pop(i, None)
                # setdefault: latency-model probes decode without a real
                # prefill, so they have no first-token history entry
                self._gen.setdefault(i, []).append(int(tok))
            self._last_commits = [1] * len(ids)
        self.gap_stats.wait_ms += ms
        self.gap_stats.cycles += 1
        return ms

    # -- speculative decoding (DESIGN.md §8) --
    def _decode_spec(self, tasks: Sequence[Task],
                     depths: List[int]) -> float:
        """Draft–verify iteration: per-task windows drafted by the tiny
        model, verified in ONE bucketed ``verify_step_paged`` call, the
        accepted prefix committed and rejected-draft pages rolled back.
        Greedy-equivalent to depth-0 decode by the acceptance rule.

        Async pipelining (DESIGN.md §10): greedy acceptance is data-
        dependent, so drafting the NEXT window needs this window's
        committed history — spec decode is a pipeline commit barrier. The
        realized overlap is the verify flight running while the host
        drafts/replans and swap transfers land; the acceptance/rollback
        work still rides the queue until the loop reads ``last_commits``."""
        from repro.serving.spec_decode import depth_bucket
        jnp = self.jnp
        self._commit_pending()        # drafts replay committed history
        async_on = self._async_on()
        ids = [t.task_id for t in tasks]
        lengths = [self.pool.length(i) for i in ids]
        t0 = time.perf_counter()
        # clamp each row's depth to what the sequence cap, its remaining
        # output (a window past the last needed token is wasted compute),
        # and the compiled buckets allow
        capped = []
        for t, ln, d in zip(tasks, lengths, depths):
            if ln + 1 > self.max_seq:
                raise RuntimeError(f"task {t.task_id} exceeds max_seq "
                                   f"{self.max_seq}")
            capped.append(max(0, min(d, self.spec_depth,
                                     self.max_seq - ln - 1,
                                     t.output_len - t.tokens_done - 1)))
        # draft proposals for every row with depth > 0
        drafts: List[List[int]] = [[] for _ in tasks]
        d_items, d_depths, d_rows = [], [], []
        for r, (t, d) in enumerate(zip(tasks, capped)):
            if d > 0:
                d_items.append((t.task_id, self._committed_tokens(t),
                                self._last_tok[t.task_id]))
                d_depths.append(d)
                d_rows.append(r)
        if d_items:
            for r, dr in zip(d_rows, self.draft.propose(d_items, d_depths)):
                drafts[r] = dr
        # reserve pages for each window (falling back to depth 0 on
        # pressure — plain decode must still be possible) + CoW defense
        for r, (i, ln) in enumerate(zip(ids, lengths)):
            try:
                self._reserve(
                    lambda i=i, e=ln + 1 + capped[r]: self.pool.extend(i, e))
            except OutOfPages:
                if capped[r] == 0:
                    raise
                capped[r] = 0
                drafts[r] = []
                self._reserve(lambda i=i, e=ln + 1: self.pool.extend(i, e))
            self._ensure_range_writable(i, ln, ln + 1 + capped[r])
        b = depth_bucket(len(tasks), self.max_batch)
        K = depth_bucket(max(max(capped), 1), self.spec_depth)
        maxp = self.max_pages_per_seq
        pt = np.full((b, maxp), -1, np.int32)
        for r, i in enumerate(ids):
            row = self.pool.page_table(i)
            pt[r, : len(row)] = row
        ln_arr = np.zeros((b,), np.int32)
        ln_arr[: len(ids)] = lengths
        toks = np.zeros((b, K + 1), np.int32)
        for r, i in enumerate(ids):
            toks[r, 0] = self._last_tok[i]
            toks[r, 1: 1 + len(drafts[r])] = drafts[r]
        logits, self.pages = self._verify_jit[(b, K)](
            self.params, self.pages, self._dev_in(pt), self._dev_in(ln_arr),
            self._dev_in(toks))
        if async_on:
            waited = self._push(PendingStep(
                "verify", ids,
                {"logits": logits, "tasks": list(tasks), "lengths": lengths,
                 "capped": capped, "drafts": drafts, "tok_dev": {},
                 "pre_lengths": dict(zip(ids, lengths))}))
            ms = max(0.0, (time.perf_counter() - t0) * 1000.0 - waited)
            self.gap_stats.dispatch_ms += ms
            return ms
        logits.block_until_ready()
        self._apply_verify(tasks, lengths, capped, drafts,
                           np.asarray(logits)[: len(ids)])
        ms = (time.perf_counter() - t0) * 1000.0
        self.gap_stats.wait_ms += ms
        self.gap_stats.cycles += 1
        return ms

    def _commit_verify(self, step: PendingStep) -> None:
        p = step.payload
        self._apply_verify(p["tasks"], p["lengths"], p["capped"],
                           p["drafts"],
                           np.asarray(p["logits"])[: len(step.task_ids)])

    def _apply_verify(self, tasks, lengths, capped, drafts,
                      logits_np) -> None:
        """Host side of a verify window: greedy acceptance, page rollback,
        committed-history updates. Shared verbatim by the sync path and
        the async commit so both modes produce identical streams."""
        from repro.serving.spec_decode import greedy_accept
        commits: List[int] = []
        last_rows = []
        for r, (t, ln) in enumerate(zip(tasks, lengths)):
            i = t.task_id
            d = capped[r]
            target_ids = np.argmax(logits_np[r, : d + 1], -1)
            n_acc = greedy_accept(drafts[r][:d], target_ids)
            bonus = int(target_ids[n_acc])
            new_len = ln + n_acc + 1
            if new_len < ln + d + 1:     # roll back rejected-draft pages
                self.pool.truncate(i, new_len)
            self._last_tok[i] = bonus
            self._tok_dev.pop(i, None)
            self._gen[i].extend(drafts[r][:n_acc] + [bonus])
            self.draft.note_commit(i, new_len)
            self._accepted_tokens += n_acc
            commits.append(n_acc + 1)
            last_rows.append(logits_np[r, n_acc])
        self.spec_steps += 1
        self._last_logits_src = None
        self._last_logits = np.stack(last_rows)
        self._last_commits = commits

    @property
    def drafted_tokens(self) -> int:
        return self.draft.drafted_tokens if self.draft is not None else 0

    # -- host-offload KV swap (DESIGN.md §7) --
    @property
    def suspend_count(self) -> int:
        return self.arena.swap_outs

    @property
    def resume_count(self) -> int:
        return self.arena.swap_ins

    @property
    def swapped_bytes(self) -> float:
        return float(self.arena.bytes_out + self.arena.bytes_in)

    def _restore_pages(self, positions, entries) -> None:
        """Scatter host page blobs back into freshly allocated device pages.
        positions: [(logical_idx, phys)] from pool.swap_in; entries: the
        arena's [(logical_idx, {"k","v"})] — both ascending by logical."""
        if not positions:
            return
        jnp = self.jnp
        assert [li for li, _ in positions] == [li for li, _ in entries], (
            positions, [li for li, _ in entries])
        idx = jnp.asarray([p for _, p in positions], jnp.int32)
        k_host = np.stack([blob["k"] for _, blob in entries], axis=1)
        v_host = np.stack([blob["v"] for _, blob in entries], axis=1)
        self.pages["k_pages"] = self.pages["k_pages"].at[:, idx].set(
            self._dev_in(k_host))
        self.pages["v_pages"] = self.pages["v_pages"].at[:, idx].set(
            self._dev_in(v_host))
        self._canonicalize_pages()

    def suspend(self, task: Task) -> float:
        """Swap the task's private pages to the host arena: gather their
        device contents (jax.device_get), release them to the pool's free
        list, keep shared prefix pages resident (their contents were never
        copied and other owners / the radix index still read them). On
        HostArenaFull the swap is rolled back — contents restored into
        re-allocated pages — and the error propagates with the task still
        resident."""
        jax, jnp = self.jax, self.jnp
        tid = task.task_id
        # ordering contract (DESIGN.md §10): a suspend issued while steps
        # are in flight lands AFTER their commit — the swapped contents
        # must include every committed token's KV
        self._commit_pending()
        async_on = self._async_on()
        t0 = time.perf_counter()
        released = self.pool.swap_out(tid)
        entries = []
        if released:
            # snapshot IMMEDIATELY after swap_out: the pages are back on
            # the free list, but jax arrays are functional — these slices
            # capture the arena version of this instant, so later reuse of
            # the physical pages can never corrupt the blobs, even while
            # the async gather is still in flight
            idx = jnp.asarray([p for _, p in released], jnp.int32)
            k_slab = self.pages["k_pages"][:, idx]
            v_slab = self.pages["v_pages"][:, idx]
            if async_on:
                # lazy per-page blobs: .nbytes is shape-derived, so the
                # arena's capacity check stays synchronous; the actual
                # device->host copy runs on the background worker
                entries = [(li, {"k": k_slab[:, i], "v": v_slab[:, i]})
                           for i, (li, _) in enumerate(released)]
            else:
                k_host = jax.device_get(k_slab)
                v_host = jax.device_get(v_slab)
                entries = [(li, {"k": k_host[:, i], "v": v_host[:, i]})
                           for i, (li, _) in enumerate(released)]
        # the recurrent-state kind swaps as ONE fixed-size blob, stashed at
        # the sentinel logical index -1 — always below real page indices,
        # so the arena's ascending-index audit holds unchanged
        s_slab = c_slab = None
        stashed = entries
        if (self.states is not None and self.states.holds(tid)
                and not self.states.is_swapped(tid)):
            slot = self.states.slot_of(tid)
            # functional snapshots, same reasoning as the page slabs above
            s_slab = self.pages["ssm_state"][:, slot]
            c_slab = self.pages["conv_state"][:, slot]
            self.states.swap_out(tid)
            if async_on:
                blob = {"ssm": s_slab, "conv": c_slab}
            else:
                blob = {"ssm": jax.device_get(s_slab),
                        "conv": jax.device_get(c_slab)}
            stashed = [(-1, blob)] + entries
        try:
            self.arena.put(tid, stashed)
        except HostArenaFull:
            # the released pages are still free (nothing allocated since),
            # so swap_in cannot fail here; np.stack on the lazy blobs
            # simply forces the transfer inline
            if s_slab is not None:
                back = self.states.swap_in(tid)
                self.pages["ssm_state"] = (
                    self.pages["ssm_state"].at[:, back].set(s_slab))
                self.pages["conv_state"] = (
                    self.pages["conv_state"].at[:, back].set(c_slab))
            self._restore_pages(self.pool.swap_in(tid), entries)
            raise
        if async_on and stashed:
            handle = self.ledger.begin(tid, [p for _, p in released])
            self._transfer_worker().submit(
                self._materialize_entries, handle, stashed)
        if self.draft is not None:
            # a suspended task's draft state is simply dropped (DESIGN.md
            # §8): its committed history survives in _gen, so the first
            # propose after resume re-prefills the draft cache
            self.draft.drop(tid)
        ms = (time.perf_counter() - t0) * 1000.0
        if async_on:
            self.gap_stats.dispatch_ms += ms
        else:
            self.gap_stats.wait_ms += ms
        return ms

    def _transfer_worker(self):
        if self._swap_pool is None:
            from concurrent.futures import ThreadPoolExecutor
            self._swap_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="kv-swap")
        return self._swap_pool

    def _materialize_entries(self, handle: int, entries) -> None:
        """Background half of an async suspend: pull each lazy page blob
        to host memory in place, then retire the ledger entry. Runs on
        the single transfer worker, overlapped with device compute."""
        t0 = time.perf_counter()
        try:
            for _, blob in entries:
                for key in blob:          # {"k","v"} pages or {"ssm","conv"}
                    blob[key] = np.asarray(blob[key])
        finally:
            self.gap_stats.add_swap_overlap(
                (time.perf_counter() - t0) * 1000.0)
            self.ledger.complete(handle)

    def resume(self, task: Task) -> float:
        """Re-allocate device pages for the swapped-out positions (evicting
        idle prefix-cache pages under pressure, like any reservation) and
        restore the host contents. OutOfPages propagates with pool and
        arena unchanged — the task simply stays suspended."""
        tid = task.task_id
        # the blobs may still be materializing on the transfer worker —
        # the ledger is what makes "no page read before its transfer
        # landed" a waited-on invariant rather than a hope
        self.ledger.wait(tid)
        async_on = self._async_on()
        t0 = time.perf_counter()
        slot = -1
        if self.states is not None and self.states.is_swapped(tid):
            slot = self.states.swap_in(tid)   # OutOfStates: nothing changed
        try:
            restored = self._reserve(lambda: self.pool.swap_in(tid))
        except OutOfPages:
            if slot >= 0:
                self.states.swap_out(tid)     # give the fresh slot back
            raise
        entries = self.arena.take(tid)
        state = [blob for li, blob in entries if li < 0]
        self._restore_pages(restored, [e for e in entries if e[0] >= 0])
        if state:
            self.pages["ssm_state"] = self.pages["ssm_state"].at[:, slot].set(
                self._dev_in(np.asarray(state[0]["ssm"])))
            self.pages["conv_state"] = (
                self.pages["conv_state"].at[:, slot].set(
                    self._dev_in(np.asarray(state[0]["conv"]))))
            self._canonicalize_pages()
        ms = (time.perf_counter() - t0) * 1000.0
        if async_on:
            self.gap_stats.dispatch_ms += ms
        else:
            self.gap_stats.wait_ms += ms
        return ms

    def release(self, task: Task) -> None:
        tid = task.task_id
        # a finished task can still have steps in flight (the loop learns
        # "finished" from host-side token counts, not device results):
        # commit through them so their observation lands before teardown
        while self._queue.pending_for(tid):
            self._queue.commit_oldest()
        self.ledger.wait(tid)
        self.pool.free(tid)
        if self.states is not None:
            self.states.free(tid)
        self.arena.drop(tid)
        self._last_tok.pop(tid, None)
        self._tok_dev.pop(tid, None)
        self._chunk_progress.pop(tid, None)
        self._toks_memo.pop(tid, None)
        self._gen.pop(tid, None)
        if self.draft is not None:
            self.draft.drop(tid)

    def latency_model(self) -> LatencyModel:
        """Measure l(b) on the live engine (warm jit) — MeasuredLatencyModel.
        Probes run under _sync_mode(): dispatch-only timings would look
        like a ~0ms decode and poison every Eq. 7 feasibility estimate."""
        from repro.core.task import qa_task
        # each warm task may grow ~32 tokens across the probe decodes;
        # reserve that many pages so probing never exhausts the pool
        nmax = min(self.max_batch,
                   max(1, self.n_pages // max(1, self.pool.pages_for(32))))
        if self.states is not None:
            nmax = min(nmax, max(1, self.states.free_slots))
        probes = sorted({b for b in (1, 2, 4, 8, nmax) if b <= nmax})
        warm = [qa_task() for _ in range(nmax)]
        with self._sync_mode():
            for t in warm:
                self.pool.alloc(t.task_id, 1)
                if self.states is not None:
                    self.states.alloc(t.task_id)
                self._last_tok[t.task_id] = 0
            lat = _probe_latency_curve(self, warm, probes)
            for t in warm:
                self.release(t)
        return lat
