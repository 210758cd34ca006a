"""Serving engine: continuous batching over jitted prefill/decode steps
with paged caches, shared-prefix reuse, and chunked prefill.

Shape discipline — the decode step compiles exactly once per engine:
``(max_slots, 1)`` tokens against the shared pools, with block tables
and per-slot fill levels as data. A mixed stream of request lengths
never retriggers decode compilation. Prompt processing depends on
family:

  * attention families (dense/moe) prefill through the paged
    chunk path — ``models/decode.py:prefill_chunk_lm_paged`` writes KV
    straight into the sequence's pages from a logical offset, so a
    prompt whose prefix is already cached (shared system prompt) only
    computes its tail, and with ``chunked_prefill`` the tail is split
    into budget-sized chunks interleaved with decode steps (a long
    prompt no longer stalls every active slot for its full length).
    One executable per distinct chunk length.
  * recurrent families (hybrid mamba, xlstm) opt out of prefix sharing
    and chunking (models/decode.py:PREFIX_SHARING_FAMILIES): their
    prompts prefill in one shot at exact length through a temporary
    static cache that is scattered into pages / slot state.

The loop each engine step: expire deadlines -> admit waiting requests
into free slots (FIFO, shared prefixes mapped from the index) -> run
prefill chunks under the step budget -> one batched decode step for
every *decoding* slot (mid-prefill slots are invisible to it) ->
record tokens, drain finished/cancelled sequences to the caller.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Deque, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.model_config import ModelConfig
from repro.models.decode import (
    ATTN_STATE_KEYS,
    recurrent_slot_axes,
    supports_prefix_sharing,
)
from repro.models.model import (
    decode_step_paged,
    init_decode_state,
    init_paged_state,
    prefill,
    prefill_chunk_paged,
)
from repro.serving.paged_cache import PagedCacheConfig, paged_write_pages, slot_write
from repro.serving.scheduler import (
    ContinuousBatchingScheduler,
    Request,
    SeqState,
    SLOScheduler,
)
from repro.serving.streaming import StreamingConfig

# inter-token latency samples kept for percentile stats; bounded so a
# long-lived engine under continuous traffic cannot leak host memory
LATENCY_WINDOW = 4096


def params_from_checkpoint(ckpt_dir: str, *, rank: Optional[int] = None,
                           step: Optional[int] = None):
    """(step, params) from a training checkpoint directory, optionally
    resized to ``rank`` via the manager's resize-on-restore path. The
    one serving-side loader — the engine classmethod and the serve CLI
    both route through here, so checkpoint-layout or resize-semantics
    changes have a single call site. Full TrainStates are stripped to
    their ``params``; a bare params tree passes through."""
    from repro.checkpoint.manager import CheckpointManager

    mgr = CheckpointManager(ckpt_dir)
    if step is None:
        step, state = mgr.restore_latest(target_rank=rank)
        if state is None:
            raise FileNotFoundError(f"no checkpoints under {ckpt_dir!r}")
    else:
        state = mgr.restore(step, target_rank=rank)
    params = state["params"] if isinstance(state, dict) and "params" in state \
        else state
    return step, params


class ServingEngine:
    """Continuous-batching serving runtime over one model + one paged
    cache pool. Construct with live ``params`` (optionally
    ``quantize="int8"``, ``prefix_cache=True``, ``chunked_prefill=True``)
    or via :meth:`from_checkpoint` (optionally at a different spectral
    rank), submit ``Request`` traces through :meth:`run`, cancel
    in-flight requests with :meth:`cancel`, read throughput/memory/
    prefix-cache/latency numbers from :meth:`stats`. The decode step
    compiles once per engine — ``(max_slots, 1)`` tokens against the
    shared pools with block tables as data — so mixed-length request
    streams never retrigger compilation."""

    def __init__(self, cfg: ModelConfig, params, pcfg: PagedCacheConfig, *,
                 prefill_token_budget: Optional[int] = None,
                 quantize: Optional[str] = None,
                 prefix_cache: bool = False,
                 chunked_prefill: bool = False,
                 scheduler: str = "fifo",
                 shed: bool = True,
                 streaming: Optional[StreamingConfig] = None,
                 mesh=None):
        if cfg.family == "encdec":
            raise NotImplementedError("paged serving targets decoder-only families")
        self.cfg = cfg
        from repro.serving.quantize import param_bytes, quantize_tree

        self.weight_bytes_fp = param_bytes(params)
        # one host-to-device copy now: host arrays (a checkpoint restore)
        # would otherwise be re-sent on every jitted step
        params = jax.device_put(params)
        if quantize == "int8":
            params = quantize_tree(params)
        elif quantize is not None:
            raise ValueError(f"unknown quantization {quantize!r}; options: int8")
        self.quantize = quantize
        self.weight_bytes = param_bytes(params)
        self.params = params
        self.pcfg = pcfg
        self.prefill_token_budget = prefill_token_budget
        # chunk size for chunked prefill: the step budget when set, else
        # a few pages' worth — chunked_prefill=True must never silently
        # degrade to whole-tail prefill just because no budget was given
        self.prefill_chunk = prefill_token_budget or 4 * pcfg.page_size
        # family policy: recurrent families silently opt out (explicit
        # in models/decode.py:PREFIX_SHARING_FAMILIES and docs/serving.md)
        self._offset_prefill = supports_prefix_sharing(cfg)
        self.prefix_cache = bool(prefix_cache) and self._offset_prefill
        self.chunked_prefill = bool(chunked_prefill) and self._offset_prefill
        # streaming KV policy (serving/streaming.py): attention sinks +
        # sliding-window eviction + optional int8 cold tier. Eviction
        # rewrites cache-resident history, which only the offset-prefill
        # families can express (positions are cache-slot-relative).
        self.streaming = streaming
        if streaming is not None and not self._offset_prefill:
            raise NotImplementedError(
                "streaming KV needs the offset-prefill paged path; family "
                f"{cfg.family!r} carries recurrent state that cannot drop "
                "evicted history")
        if streaming is not None and mesh is not None:
            raise NotImplementedError(
                "streaming KV is not supported under tensor-parallel "
                "serving (per-shard shadow pools are not wired)")
        self._cold = streaming is not None and streaming.cold_kv == "int8"
        self.state = init_paged_state(cfg, pcfg,
                                      "int8" if self._cold else "none")
        if scheduler == "slo":
            self.sched: ContinuousBatchingScheduler = SLOScheduler(
                pcfg, prefill_token_budget, prefix_sharing=self.prefix_cache,
                streaming=streaming, shed=shed)
        elif scheduler == "fifo":
            self.sched = ContinuousBatchingScheduler(
                pcfg, prefill_token_budget, prefix_sharing=self.prefix_cache,
                streaming=streaming)
        else:
            raise ValueError(f"unknown scheduler {scheduler!r}; options: "
                             f"fifo, slo")
        self.scheduler = scheduler
        self._next_input = np.zeros((pcfg.max_slots,), dtype=np.int32)

        # cold-tier bookkeeping: a host flag per physical page (1 = the
        # int8 shadow copy is authoritative for attention) mirrored to
        # device lazily, cleared whenever the pool frees a page (evict,
        # finish, cancel, prefix-cache eviction — one hook covers all)
        self.stream_demotions = 0
        self.cold_page_bytes = 0
        self._cold_np = np.zeros((pcfg.num_pages + 1,), dtype=np.int32)
        self._cold_dev = None
        if self._cold:
            self.sched.pool.on_free = self._on_pages_freed
            from repro.serving.quantize import quantize_kv_pages

            def _demote(state, page):
                for key in ATTN_STATE_KEYS:
                    if key not in state:
                        continue
                    cache = dict(state[key])
                    for name in [n for n in cache if n + "_q8" in cache]:
                        qt = quantize_kv_pages(cache[name][:, page],
                                               token_axis=1)
                        cache[name + "_q8"] = \
                            cache[name + "_q8"].at[:, page].set(qt["q8"])
                        cache[name + "_scale"] = \
                            cache[name + "_scale"].at[:, page].set(qt["scale"])
                    state = dict(state, **{key: cache})
                return state

            self._demote_fn = jax.jit(_demote, donate_argnums=(0,))
            # int8 shadow bytes one demoted page occupies across every
            # layer of every q8 leaf — the deterministic cost metric
            self._cold_bytes_per_page = sum(
                int(leaf.shape[0]) * int(np.prod(leaf.shape[2:]))
                for key in ATTN_STATE_KEYS if key in self.state
                for name, leaf in self.state[key].items()
                if name.endswith("_q8"))

        # tensor-parallel serving: under a serve mesh the decode and
        # chunk-prefill steps run inside shard_map — GQA KV pools live
        # as per-shard kv-head slices, MLA latent pools and everything
        # else (params: tiny spectral factors — replication is the
        # cheap placement the paper's compression buys) replicate, and
        # the per-shard attention all-gathers head outputs before wo,
        # so greedy outputs stay token-identical to single-device.
        self.mesh = mesh
        self.tp = 1
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from repro.sharding.partition import (
                TP_AXIS,
                named_shardings,
                paged_state_pspecs,
                serve_tp_valid,
            )

            self.tp = int(mesh.shape[TP_AXIS])
            if not self._offset_prefill:
                raise NotImplementedError(
                    "tensor-parallel paged decode needs pure paged-attention "
                    f"state; family {cfg.family!r} carries recurrent state")
            if not serve_tp_valid(cfg, self.tp):
                dim = "n_heads" if cfg.attention == "mla" else "n_kv_heads"
                raise ValueError(
                    f"tp={self.tp} does not divide this config's {dim}")
        if mesh is not None and self.tp > 1:
            tp = self.tp
            state_specs = paged_state_pspecs(cfg, self.state, tp)
            # replication check off: logits and replicated pools are
            # replicated by construction (every shard computes them
            # from all-gathered head outputs)
            self._decode_fn = jax.jit(jax.shard_map(
                lambda p, t, st, bt, sl: decode_step_paged(
                    p, t, st, bt, sl, cfg, tp_axis=TP_AXIS, tp_size=tp),
                mesh=mesh, in_specs=(P(), P(), state_specs, P(), P()),
                out_specs=(P(), state_specs), check_vma=False),
                donate_argnums=(2,))
            self._chunk_fn = jax.jit(jax.shard_map(
                lambda p, t, st, bt, s0: prefill_chunk_paged(
                    p, t, st, bt, s0, cfg, tp_axis=TP_AXIS, tp_size=tp),
                mesh=mesh, in_specs=(P(), P(), state_specs, P(), P()),
                out_specs=(P(), state_specs), check_vma=False),
                donate_argnums=(2,))
            self.state = jax.device_put(self.state,
                                        named_shardings(state_specs, mesh))
            rep = NamedSharding(mesh, P())
            self.params = jax.device_put(
                self.params, jax.tree.map(lambda _: rep, self.params))
        elif self._cold:
            # cold-tier variants thread the page flag vector; attention
            # substitutes dequantized shadow rows for flagged pages
            self._decode_fn = jax.jit(
                lambda p, t, st, bt, sl, cf: decode_step_paged(
                    p, t, st, bt, sl, cfg, cold_flags=cf),
                donate_argnums=(2,),
            )
            self._chunk_fn = jax.jit(
                lambda p, t, st, bt, s0, cf: prefill_chunk_paged(
                    p, t, st, bt, s0, cfg, cold_flags=cf),
                donate_argnums=(2,),
            )
        else:
            self._decode_fn = jax.jit(
                lambda p, t, st, bt, sl: decode_step_paged(p, t, st, bt, sl, cfg),
                donate_argnums=(2,),
            )
            self._chunk_fn = jax.jit(
                lambda p, t, st, bt, s0: prefill_chunk_paged(p, t, st, bt, s0, cfg),
                donate_argnums=(2,),
            )
        self._prefill_fn = jax.jit(lambda p, t, st: prefill(p, t, cfg, st))
        self._write_pages = jax.jit(
            lambda pool, ids, v: paged_write_pages(pool, ids, jnp.squeeze(v, 1), n_stack=1),
            donate_argnums=(0,),
        )
        # COW fork: pools are layer-stacked (L, P, page, *f) -> copy one
        # physical page across every layer of every leaf
        self._copy_page_fn = jax.jit(
            lambda pool, src, dst: jax.tree.map(
                lambda leaf: leaf.at[:, dst].set(leaf[:, src]), pool),
            donate_argnums=(0,),
        )
        self._scatter = {}
        for key, ax in recurrent_slot_axes(cfg).items():
            axes_tree = jax.tree.map(lambda _: ax, self.state[key])
            self._scatter[key] = jax.jit(
                lambda full, vals, slot, _axes=axes_tree: slot_write(full, _axes, slot, vals),
                static_argnums=(2,), donate_argnums=(0,),
            )

        # stats (bounded: counters + a fixed-width latency window)
        self.prefill_tokens = 0          # prompt tokens actually computed
        self.prompt_tokens = 0           # prompt tokens admitted
        self.prefix_shared_tokens = 0    # prompt tokens served from the index
        self.decoded_tokens = 0
        self.decode_steps = 0
        self.requests_done = 0
        self.generated_total = 0
        self.cancelled = 0
        self.timed_out = 0
        self.shed = 0
        self.wall_s = 0.0
        self.step_times: Deque[float] = deque(maxlen=LATENCY_WINDOW)
        # per-request completion records (rid, tenant, TTFT, SLO-met, ...)
        # — what bench/runner.py aggregates into goodput/TTFT percentiles.
        # Bounded like the latency window: a long-lived engine keeps the
        # most recent LATENCY_WINDOW completions
        self.request_log: Deque[Dict] = deque(maxlen=LATENCY_WINDOW)
        self._arrive_wall: Dict[int, float] = {}   # rid -> submit wall time
        self._first_tok_wall: Dict[int, float] = {}
        self.last_statuses: Dict[int, str] = {}
        # completions drained from the scheduler but not yet handed to a
        # consumer — survives an abandoned serve() generator (several
        # requests can finish in one step; closing the generator between
        # their yields must not lose the rest)
        self._undelivered: List[tuple] = []
        # requests handed to serve() but not yet submitted to the
        # scheduler (future arrivals) — engine state, not generator
        # state, for the same reason: an abandoned or never-iterated
        # generator must not lose them
        self._backlog: List[Request] = []
        # the engine-step clock arrivals and deadlines are measured
        # against; persists across an abandoned generator (a recovery
        # must not restart deadlines) and resets per fresh trace
        self._clock = 0

    # -------------------------------------------------------------- load --
    @classmethod
    def from_checkpoint(cls, cfg: ModelConfig, ckpt_dir: str,
                        pcfg: PagedCacheConfig, *,
                        rank: Optional[int] = None,
                        step: Optional[int] = None,
                        **kw) -> "ServingEngine":
        """Build an engine straight from a training checkpoint directory.

        ``rank`` shrinks (or grows) every spectral group to that rank at
        load time via the checkpoint manager's resize-on-restore path —
        the cheap-serving story: a run trained at rank 128 serves from
        the same snapshot at rank 64 with ~2x smaller spectral factors,
        keeping the top-64 singular directions (Eckart–Young optimal for
        the represented weights). The Adam moments in the checkpoint are
        dropped; only ``params`` board the engine. Composes with
        ``quantize="int8"`` (shrink first, then quantize).
        """
        _, params = params_from_checkpoint(ckpt_dir, rank=rank, step=step)
        return cls(cfg, params, pcfg, **kw)

    # --------------------------------------------------------------- run --
    def serve(self, requests: Sequence[Request]):
        """Generator form of the serving loop: drives the trace one
        engine step per iteration and yields ``(rid, tokens, status)``
        as each request finishes (status ``finished`` / ``cancelled`` /
        ``timeout``; tokens are the int32 generated ids, partial for
        evicted requests). ``Request.arrival`` staggers enqueueing in
        engine-step time (a request is invisible to the scheduler before
        its arrival step). Results are drained from the scheduler every
        step, so neither side accumulates state across requests; per-rid
        outcomes also land in :attr:`last_statuses`. :meth:`run` is the
        collect-everything wrapper; ``api.Server.stream`` is the
        incremental consumer.

        Abandonment-safe: the request backlog and drained-but-unyielded
        completions live on the engine, so a generator dropped mid-trace
        (or never iterated) strands nothing — a later ``serve(())`` /
        :meth:`run` picks up exactly where it left off (see
        :attr:`has_pending_work`)."""
        # registration happens eagerly, NOT inside the generator body: a
        # never-iterated generator must still have handed its requests
        # to the engine. Checked before the merge: a fresh trace restarts
        # engine-step time, while any leftover work — backlog included —
        # keeps the clock so arrivals/deadlines retain their meaning.
        if not self.has_pending_work:
            self._clock = 0
        self._backlog = sorted(self._backlog + list(requests),
                               key=lambda r: r.arrival)
        return self._serve_loop()

    def _deliver(self):
        """Yield undelivered completions, popping before the yield (a
        consumer that bails mid-delivery never sees one twice) and
        re-recording the per-rid outcome (a stranded completion's
        status must survive the reset a recovery run starts with)."""
        while self._undelivered:
            rid, tokens, status = self._undelivered.pop(0)
            self.last_statuses[rid] = status
            yield (rid, tokens, status)

    def _serve_loop(self):
        self.last_statuses = {}
        t0 = time.time()
        last_decode_t = None
        try:
            # completions stranded by a previously abandoned generator
            # are delivered first
            yield from self._deliver()
            while self._backlog or self.sched.has_work:
                while self._backlog and self._backlog[0].arrival <= self._clock:
                    req = self._backlog.pop(0)
                    self._arrive_wall[req.rid] = time.time()
                    # submit-time clock anchors the relative deadline: a
                    # reused engine's clock never reset, and the request
                    # must not inherit steps it was never alive for
                    self.sched.submit(req, now=self._clock)
                self.sched.expire_deadlines(self._clock)
                for seq in self.sched.admit():
                    self.prompt_tokens += seq.request.prompt_len
                    self.prefix_shared_tokens += seq.shared_len
                self._prefill_step()
                if any(s.status == "decoding" for s in self.sched.active.values()):
                    self._decode_once()
                    # inter-token latency = gap between consecutive decode
                    # completions, so prefill stalls *between* decode steps
                    # (what chunked prefill exists to bound) count against
                    # the tail; the first decode of a run is TTFT, not ITL
                    now = time.time()
                    if last_decode_t is not None:
                        self.step_times.append(now - last_decode_t)
                    last_decode_t = now
                self._undelivered.extend(
                    (seq.request.rid,
                     np.asarray(seq.generated, dtype=np.int32),
                     seq.status)
                    for seq in self._drain())
                yield from self._deliver()
                self._clock += 1
            jax.block_until_ready(jax.tree.leaves(self.state)[0])
        finally:
            # wall clock closes even when the consumer abandons the
            # generator mid-trace (stats stay meaningful either way)
            self.wall_s += time.time() - t0

    def run(self, requests: Sequence[Request]) -> Dict[int, np.ndarray]:
        """Serve a trace to completion: rid -> generated token ids
        (first token from prefill, rest from decode). The batch wrapper
        over :meth:`serve`."""
        return {rid: tokens for rid, tokens, _ in self.serve(requests)}

    @property
    def peak_pages(self) -> int:
        """Max pool pages ever resident at once. Read from the pool's
        own allocation-site high-water mark, so pages allocated and
        released *within* one engine step (COW forks, a decode-time
        boundary page on a sequence that finishes the same step) count —
        a per-step poll of ``allocated_count`` missed those."""
        return self.sched.pool.peak_allocated

    @property
    def has_pending_work(self) -> bool:
        """True while a fresh :meth:`serve` call with no new requests
        can still produce completions: a future-arrival backlog,
        in-flight scheduler work, or results drained but not yet
        delivered (an abandoned generator)."""
        return (bool(self._undelivered) or bool(self._backlog)
                or self.sched.has_work)

    def known_rids(self) -> set:
        """Every rid the runtime currently owns — backlog, queued,
        active, or finished-but-undelivered. Results key on rid, so
        admitting a duplicate would silently cross-wire two requests;
        submitters check here."""
        rids = {r.rid for r in self._backlog}
        rids.update(r.rid for r in self.sched.waiting)
        rids.update(seq.request.rid for seq in self.sched.active.values())
        rids.update(rid for rid, _, _ in self._undelivered)
        return rids

    def cancel(self, rid: int) -> bool:
        """Cancel a request mid-flight (queue or active). Partial
        results surface on the next drain with status ``cancelled``."""
        return self.sched.cancel(rid)

    def _drain(self) -> List[SeqState]:
        drained = self.sched.drain_finished()
        for seq in drained:
            self.last_statuses[seq.request.rid] = seq.status
            self.requests_done += 1
            self.generated_total += len(seq.generated)
            if seq.status == "cancelled":
                self.cancelled += 1
            elif seq.status == "timeout":
                self.timed_out += 1
            elif seq.status == "shed":
                self.shed += 1
            self.request_log.append(self._record(seq))
        return drained

    def _record(self, seq: SeqState) -> Dict:
        """One completion record for :attr:`request_log`: identity,
        outcome, clock-domain latencies (deterministic: engine steps),
        wall-clock TTFT, and the SLO verdict. ``slo_met`` is True only
        for requests that finished inside their deadline — deadline
        eviction makes finishing imply that, but the record states it
        explicitly so consumers needn't know the eviction contract."""
        req = seq.request
        arrive_wall = self._arrive_wall.pop(req.rid, None)
        first_wall = self._first_tok_wall.pop(req.rid, None)
        finish = self._clock
        # clock-domain latencies measure from the deadline anchor
        # (submit-time clock, == arrival on any fresh trace) so engine
        # reuse cannot charge a request for steps before it existed
        anchor = req.deadline_anchor
        return {
            "rid": req.rid,
            "tenant": req.tenant,
            "priority": req.priority,
            "status": seq.status,
            "arrival": req.arrival,
            "deadline": req.deadline,
            "admit_clock": seq.admit_clock,
            "first_token_clock": seq.first_token_clock,
            "finish_clock": finish,
            "ttft_steps": (seq.first_token_clock - anchor
                           if seq.first_token_clock is not None else None),
            "ttft_s": (first_wall - arrive_wall
                       if first_wall is not None and arrive_wall is not None
                       else None),
            "prompt_tokens": req.prompt_len,
            "new_tokens": len(seq.generated),
            "slo_met": (seq.status == "finished"
                        and (req.deadline is None
                             or finish - anchor <= req.deadline)),
        }

    # ------------------------------------------------------------- steps --
    def _prefill_step(self) -> None:
        """Advance every prefilling sequence, FIFO, under the per-step
        chunk budget (when chunking; otherwise each tail runs whole).
        The first chunk of a step always runs — progress guarantee."""
        budget = self.prefill_chunk if self.chunked_prefill else None
        # streaming caps every chunk at a window of tokens: eviction can
        # then always make room, and each chunk advances by at least a
        # page (termination under arbitrarily long prompts)
        cap = (self.streaming.window_pages * self.pcfg.page_size
               if self.streaming is not None else None)
        spent = 0
        for seq in self.sched.prefilling():
            if not self._offset_prefill:
                self._prefill_full(seq)
                continue
            plen = seq.request.prompt_len
            logits = None
            while seq.prefill_pos < plen:
                remaining = plen - seq.prefill_pos
                c = remaining if budget is None else min(remaining, max(1, budget - spent))
                if cap is not None:
                    c = min(c, cap)
                if budget is not None and spent > 0 and spent + c > budget:
                    return                       # budget exhausted; resume next step
                if self.streaming is not None:
                    self.sched.stream_prepare_chunk(seq.slot, c)
                    self._stream_demote(seq.slot)
                logits = self._run_chunk(seq, c)
                spent += c
            self._complete_prefill(seq, logits)
            if budget is not None and spent >= budget:
                return

    def _run_chunk(self, seq: SeqState, c: int):
        req = seq.request
        toks = jnp.asarray(req.prompt[seq.prefill_pos:seq.prefill_pos + c],
                           dtype=jnp.int32)[None]
        bt = jnp.asarray(self.sched.block_table[seq.slot:seq.slot + 1])
        # cache-slot-relative start: evicted history no longer occupies
        # cache positions, so the chunk writes (and RoPE-rotates) at its
        # resident offset — the StreamingLLM position contract
        start = jnp.int32(seq.prefill_pos - seq.evicted_tokens)
        if self._cold:
            logits, self.state = self._chunk_fn(self.params, toks, self.state,
                                                bt, start, self._cold_flags())
        else:
            logits, self.state = self._chunk_fn(self.params, toks, self.state,
                                                bt, start)
        seq.prefill_pos += c
        self.prefill_tokens += c
        return logits

    # --------------------------------------------------------- streaming --
    def _cold_flags(self):
        """Device copy of the per-page cold flags, rebuilt only when the
        host mirror changed (demotion or page free)."""
        if self._cold_dev is None:
            self._cold_dev = jnp.asarray(self._cold_np)
        return self._cold_dev

    def _on_pages_freed(self, pages) -> None:
        """PagePool.on_free hook: a freed page's shadow copy is stale —
        whatever sequence reuses the page starts hot."""
        if pages and self._cold_np[np.asarray(pages)].any():
            self._cold_np[np.asarray(pages)] = 0
            self._cold_dev = None

    def _stream_demote(self, slot: int) -> None:
        """Demote this slot's newly cold pages (resident, outside the
        window, unshared) into the int8 shadow pools."""
        if not self._cold:
            return
        for p in self.sched.stream_cold_pages(slot):
            if self._cold_np[p]:
                continue
            self.state = self._demote_fn(self.state, jnp.int32(p))
            self._cold_np[p] = 1
            self._cold_dev = None
            self.stream_demotions += 1
            self.cold_page_bytes += self._cold_bytes_per_page

    def score_nll(self, tokens) -> float:
        """Teacher-forced mean NLL of ``tokens`` under this engine's
        exact cache policy: the sequence prefills through the paged
        chunk path — evicting and demoting just as serving would — and
        each chunk's logits score its next-token targets. The
        perplexity-vs-eviction-policy bench sweep is built on this."""
        if not self._offset_prefill:
            raise NotImplementedError("score_nll needs the offset-prefill "
                                      "paged path")
        toks = np.asarray(tokens, dtype=np.int32)
        rid = max(self.known_rids(), default=-1) + 1
        self.sched.submit(Request(rid=rid, prompt=toks, max_new_tokens=1))
        seq = next((s for s in self.sched.admit() if s.request.rid == rid),
                   None)
        if seq is None:
            raise RuntimeError("score_nll: request was not admitted "
                               "(no free slot or pages)")
        cap = (self.streaming.window_pages * self.pcfg.page_size
               if self.streaming is not None else self.prefill_chunk)
        plen = seq.request.prompt_len
        total, count = 0.0, 0
        while seq.prefill_pos < plen:
            c = min(plen - seq.prefill_pos, cap)
            if self.streaming is not None:
                self.sched.stream_prepare_chunk(seq.slot, c)
                self._stream_demote(seq.slot)
            pos0 = seq.prefill_pos
            logits = self._run_chunk(seq, c)
            upto = min(c, plen - 1 - pos0)       # last token has no target
            if upto > 0:
                lg = jax.nn.log_softmax(
                    logits[0, :upto].astype(jnp.float32), axis=-1)
                tgt = jnp.asarray(toks[pos0 + 1:pos0 + 1 + upto],
                                  dtype=jnp.int32)
                total += float(-jnp.sum(
                    jnp.take_along_axis(lg, tgt[:, None], axis=1)))
                count += upto
        self.sched.cancel(rid)
        self.sched.drain_finished()
        return total / max(count, 1)

    def _complete_prefill(self, seq: SeqState, logits) -> None:
        tok = int(np.asarray(jnp.argmax(logits[0, -1])))
        self._next_input[seq.slot] = tok
        self._first_tok_wall.setdefault(seq.request.rid, time.time())
        self.sched.finish_prefill(seq.slot)
        self.sched.on_prefill_token(seq.slot, tok)

    def _prefill_full(self, seq: SeqState) -> None:
        """Recurrent-family prompt path: full-length prefill through a
        temporary static cache, scattered into pages / slot state."""
        req = seq.request
        tokens = jnp.asarray(req.prompt, dtype=jnp.int32)[None]
        tmp = init_decode_state(self.cfg, 1, req.prompt_len)
        logits, filled = self._prefill_fn(self.params, tokens, tmp)
        page_ids = jnp.asarray(np.asarray(seq.pages, dtype=np.int32))
        for key in ATTN_STATE_KEYS:
            if key in self.state:
                self.state[key] = jax.tree.map(
                    lambda pool, v: self._write_pages(pool, page_ids, v),
                    self.state[key], filled[key])
        for key, scatter in self._scatter.items():
            self.state[key] = scatter(self.state[key], filled[key], seq.slot)
        seq.prefill_pos = req.prompt_len
        self.prefill_tokens += req.prompt_len
        self._complete_prefill(seq, logits)

    def _decode_once(self) -> None:
        if self.streaming is not None:
            # window maintenance first: eviction may shrink seq_len, so
            # it must precede the append-capacity check that reasons
            # about the next token's page
            for slot, seq in list(self.sched.active.items()):
                if seq.status == "decoding":
                    self.sched.stream_maintain(slot, 1)
                    self._stream_demote(slot)
        for _, src, dst in self.sched.ensure_append_capacity():
            # copy-on-write fork: duplicate the shared page before the
            # batched append may write it (unreachable under full-page
            # sharing, but the semantics are complete and fuzz-tested)
            for key in ATTN_STATE_KEYS:
                if key in self.state:
                    self.state[key] = self._copy_page_fn(
                        self.state[key], jnp.int32(src), jnp.int32(dst))
        bt_np, sl_np = self.sched.decode_view()
        bt = jnp.asarray(bt_np)
        sl = jnp.asarray(sl_np)
        toks = jnp.asarray(self._next_input)[:, None]
        if self._cold:
            logits, self.state = self._decode_fn(self.params, toks, self.state,
                                                 bt, sl, self._cold_flags())
        else:
            logits, self.state = self._decode_fn(self.params, toks, self.state,
                                                 bt, sl)
        nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1)).astype(np.int32)
        decoding = [s for s, seq in self.sched.active.items()
                    if seq.status == "decoding"]
        for slot in decoding:
            tok = int(nxt[slot])
            self._next_input[slot] = tok
            self.sched.on_token(slot, tok)
        self.decode_steps += 1
        self.decoded_tokens += len(decoding)

    # ------------------------------------------------------------- stats --
    def attn_cache_bytes(self) -> int:
        """Bytes held by the paged attention pools (the memory the
        static (batch, max_seq) layout pins at worst case instead)."""
        total = 0
        for key in ATTN_STATE_KEYS:
            if key in self.state:
                total += sum(leaf.size * leaf.dtype.itemsize
                             for leaf in jax.tree.leaves(self.state[key]))
        return total

    def latency_percentiles(self) -> Dict[str, float]:
        """p50/p99 inter-token latency (seconds) over the sliding window
        of gaps between consecutive decode-step completions — prefill
        work scheduled between decode steps shows up in the tail."""
        if not self.step_times:
            return {"itl_p50_s": 0.0, "itl_p99_s": 0.0}
        arr = np.asarray(self.step_times)
        return {"itl_p50_s": float(np.percentile(arr, 50)),
                "itl_p99_s": float(np.percentile(arr, 99))}

    def stats(self) -> Dict[str, float]:
        gen = self.generated_total
        out = {
            "requests": float(self.requests_done),
            "cancelled": float(self.cancelled),
            "timed_out": float(self.timed_out),
            "shed": float(self.shed),
            "peak_pages": float(self.peak_pages),
            "prefill_tokens": float(self.prefill_tokens),
            "prompt_tokens": float(self.prompt_tokens),
            "prefix_shared_tokens": float(self.prefix_shared_tokens),
            "generated_tokens": float(gen),
            "decode_steps": float(self.decode_steps),
            "cow_forks": float(self.sched.cow_forks),
            "wall_s": self.wall_s,
            "tokens_per_s": (self.prefill_tokens + gen) / self.wall_s if self.wall_s else 0.0,
            "attn_cache_bytes": float(self.attn_cache_bytes()),
            "weight_bytes": float(self.weight_bytes),
            "weight_bytes_fp": float(self.weight_bytes_fp),
        }
        out.update(self.latency_percentiles())
        if self.streaming is not None:
            out["stream_evictions"] = float(self.sched.stream_evictions)
            out["stream_demotions"] = float(self.stream_demotions)
            out["cold_page_bytes"] = float(self.cold_page_bytes)
        if self.sched.prefix_cache is not None:
            out.update({k: float(v)
                        for k, v in self.sched.prefix_cache.stats().items()})
        if isinstance(self.sched, SLOScheduler):
            out.update({k: float(v) for k, v in self.sched.stats().items()})
        return out
