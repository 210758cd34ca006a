"""Disaggregated serving: a prefill worker feeding a decode engine
through an explicit KV-transfer seam.

Colocated continuous batching runs prompt chunks and decode steps on
one pool: a long prompt's chunks and the decode batch contend for the
same step loop. Production serving stacks (DistServe, Mooncake,
vLLM-disagg) split the phases — prefill workers with their own KV pool
process prompts, then ship the filled pages to the decode worker's
pool. This module is that split, single-process: the workers are real
(separate ``PagePool`` + paged state + executables), the wire is a
device-to-device page copy (:func:`~repro.serving.paged_cache.
paged_copy_pages`), and the whole arrangement stays token-for-token
identical to the colocated engine because the chunk math is the same
function against the same page geometry.

Three pieces:

  * :class:`KVTransfer` — ships filled pages between pools. ``raw``
    copies at pool dtype (lossless, the default); ``int8`` quantizes
    page payloads symmetric-per-channel on the wire (8x smaller than
    fp32 pools, reusing the scale scheme of ``serving/quantize.py`` /
    ``runtime/compression.py``) and dequantizes into the destination —
    an opt-in accuracy/bandwidth trade, surfaced in stats as raw vs
    wire bytes.
  * :class:`PrefillWorker` — owns a private pool and paged state,
    allocates pages per prompt, runs the same chunked offset-prefill
    executable the colocated engine uses.
  * :class:`DisaggregatedEngine` — a :class:`ServingEngine` whose
    prefill step runs on the worker: prompt chunks never touch the
    decode pool until the finished pages arrive in one transfer, so a
    long prompt never stalls the decode batch mid-write.

Both sides share one process and (under a serve mesh) one mesh with
identically sharded pools, so the transfer is a shard-local gather/
scatter under jit — the seam where a multi-host implementation would
put the actual interconnect.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.model_config import ModelConfig
from repro.models.decode import ATTN_STATE_KEYS
from repro.models.model import init_paged_state, prefill_chunk_paged
from repro.serving.engine import ServingEngine
from repro.serving.paged_cache import PagedCacheConfig, PagePool, paged_copy_pages
from repro.serving.scheduler import SeqState

KV_TRANSFER_MODES = ("raw", "int8")


class KVTransfer:
    """Page shipment between two pools plus the bandwidth ledger.

    ``ship`` copies pages ``src_ids`` of every leaf in ``src_tree``
    into pages ``dst_ids`` of ``dst_tree`` (layer-stacked pools:
    leading axis is layers) and returns the new destination tree.
    ``raw`` copies at pool dtype. ``int8`` quantizes each page's
    payload to symmetric int8 with one fp32 scale per (layer, page,
    channel) — amax over the token-in-page axis, the same per-channel
    scheme ``serving/quantize.py`` applies to weights — then
    dequantizes into the destination pool, so the pools always hold
    pool-dtype values and downstream attention is unchanged.

    The ledger counts ``pages_shipped`` (page-copies, summed over
    stacked pool groups), ``bytes_raw`` (payload at pool dtype — what
    a lossless wire carries) and ``bytes_wire`` (what this mode's wire
    carries: int8 payload + fp32 scales under ``int8``)."""

    def __init__(self, mode: str = "raw"):
        if mode not in KV_TRANSFER_MODES:
            raise ValueError(f"unknown kv transfer mode {mode!r}; "
                             f"options: {', '.join(KV_TRANSFER_MODES)}")
        self.mode = mode
        self.pages_shipped = 0
        self.bytes_raw = 0
        self.bytes_wire = 0
        fn = self._copy_raw if mode == "raw" else self._copy_int8
        # one executable per (tree structure, page count); page counts
        # are small integers so the cache stays bounded in practice
        self._fn = jax.jit(fn, donate_argnums=(0,))

    @staticmethod
    def _copy_raw(dst_tree, dst_ids, src_tree, src_ids):
        return jax.tree.map(
            lambda d, s: paged_copy_pages(d, dst_ids, s, src_ids, n_stack=1),
            dst_tree, src_tree)

    @staticmethod
    def _copy_int8(dst_tree, dst_ids, src_tree, src_ids):
        def one(d, s):
            vals = jnp.take(s, src_ids, axis=1).astype(jnp.float32)
            # (L, n, page, *channels): scale per channel over the page
            amax = jnp.max(jnp.abs(vals), axis=2, keepdims=True)
            scale = jnp.maximum(amax, 1e-12) / 127.0
            q = jnp.clip(jnp.round(vals / scale), -127, 127).astype(jnp.int8)
            deq = q.astype(jnp.float32) * scale
            return d.at[:, dst_ids].set(deq.astype(d.dtype))
        return jax.tree.map(one, dst_tree, src_tree)

    def ship(self, dst_tree, dst_ids: jax.Array, src_tree, src_ids: jax.Array):
        n = int(src_ids.shape[0])
        for leaf in jax.tree.leaves(dst_tree):
            # payload elements of one page across all layers of a leaf
            per_page = leaf.size // leaf.shape[1]
            self.bytes_raw += n * per_page * leaf.dtype.itemsize
            if self.mode == "int8":
                page = leaf.shape[2]
                self.bytes_wire += n * per_page       # int8 payload
                self.bytes_wire += (n * per_page // page) * 4  # fp32 scales
            else:
                self.bytes_wire += n * per_page * leaf.dtype.itemsize
        self.pages_shipped += n
        return self._fn(dst_tree, dst_ids, src_tree, src_ids)


class PrefillWorker:
    """Prompt-side worker: private page pool, private paged state, and
    the same chunked offset-prefill executable the colocated engine
    runs — so its logits and page contents are bit-identical to an
    in-place prefill at the same positions.

    Per prompt: :meth:`begin` allocates ``pages_for(prompt_len)`` pages
    from the worker pool, :meth:`run_chunk` advances ``seq.prefill_pos``
    writing KV into those pages, and when the prompt is done the engine
    ships the pages out and calls :meth:`finish` (ownership passes to
    the transfer; the worker releases after the ship). :meth:`abort`
    reclaims pages for sequences evicted mid-prefill."""

    def __init__(self, cfg: ModelConfig, params, pcfg: PagedCacheConfig, *,
                 mesh=None):
        self.cfg = cfg
        self.params = params
        self.pcfg = pcfg
        self.pool = PagePool(pcfg.num_pages)
        self.state = init_paged_state(cfg, pcfg)
        self.prefilled_tokens = 0
        self._pages: Dict[int, List[int]] = {}   # rid -> worker pages
        if mesh is not None and any(n > 1 for n in mesh.shape.values()):
            from jax.sharding import PartitionSpec as P

            from repro.sharding.partition import (
                TP_AXIS,
                named_shardings,
                paged_state_pspecs,
            )

            tp = int(mesh.shape[TP_AXIS])
            specs = paged_state_pspecs(cfg, self.state, tp)
            self._chunk_fn = jax.jit(jax.shard_map(
                lambda p, t, st, bt, s0: prefill_chunk_paged(
                    p, t, st, bt, s0, cfg, tp_axis=TP_AXIS, tp_size=tp),
                mesh=mesh, in_specs=(P(), P(), specs, P(), P()),
                out_specs=(P(), specs), check_vma=False),
                donate_argnums=(2,))
            self.state = jax.device_put(self.state,
                                        named_shardings(specs, mesh))
        else:
            self._chunk_fn = jax.jit(
                lambda p, t, st, bt, s0: prefill_chunk_paged(p, t, st, bt, s0, cfg),
                donate_argnums=(2,),
            )

    def begin(self, seq: SeqState) -> None:
        rid = seq.request.rid
        if rid not in self._pages:
            self._pages[rid] = self.pool.alloc(
                self.pcfg.pages_for(seq.request.prompt_len))

    def _block_row(self, rid: int) -> np.ndarray:
        bt = np.full((1, self.pcfg.max_pages_per_seq), self.pcfg.null_page,
                     dtype=np.int32)
        pages = self._pages[rid]
        bt[0, :len(pages)] = pages
        return bt

    def run_chunk(self, seq: SeqState, c: int):
        """Advance one prompt by ``c`` tokens against the worker pool;
        returns the chunk logits (the last chunk's tail logit seeds the
        first generated token, exactly as colocated)."""
        req = seq.request
        toks = jnp.asarray(req.prompt[seq.prefill_pos:seq.prefill_pos + c],
                           dtype=jnp.int32)[None]
        bt = jnp.asarray(self._block_row(req.rid))
        logits, self.state = self._chunk_fn(self.params, toks, self.state, bt,
                                            jnp.int32(seq.prefill_pos))
        seq.prefill_pos += c
        self.prefilled_tokens += c
        return logits

    def finish(self, rid: int) -> List[int]:
        """Hand the prompt's filled pages to the transfer; caller
        releases them (via :meth:`release`) once the ship is issued."""
        return self._pages.pop(rid)

    def release(self, pages: List[int]) -> None:
        self.pool.release(pages)

    def abort(self, rid: int) -> None:
        """Reclaim pages of a sequence evicted mid-prefill (cancel,
        deadline, shed). No-op for prompts already shipped."""
        pages = self._pages.pop(rid, None)
        if pages is not None:
            self.pool.release(pages)


class DisaggregatedEngine(ServingEngine):
    """Continuous-batching engine with disaggregated prefill: prompt
    chunks run on a :class:`PrefillWorker` against its private pool;
    on completion the filled pages ship through :class:`KVTransfer`
    into the pages the scheduler already allocated in the decode pool,
    and the sequence joins the decode batch exactly as if it had
    prefilled in place.

    Scheduling semantics are inherited unchanged — admission still
    allocates/reserves decode-pool pages, chunk budgets still meter
    prompt work per step — so colocated and disaggregated runs admit,
    chunk, and decode in the same order and emit identical tokens.
    Incompatible with ``prefix_cache`` (shared prefix pages live in the
    decode pool, invisible to the worker) and limited to the
    offset-prefill families (recurrent state has no page transfer).

    ``prefill_pcfg`` sizes the worker pool separately (same page size
    and block-table width — the chunk executable's geometry); default
    mirrors the decode pool."""

    def __init__(self, cfg: ModelConfig, params, pcfg: PagedCacheConfig, *,
                 kv_transfer: str = "raw",
                 prefill_pcfg: Optional[PagedCacheConfig] = None,
                 **kw):
        super().__init__(cfg, params, pcfg, **kw)
        if not self._offset_prefill:
            raise NotImplementedError(
                "disaggregated prefill needs the offset-prefill path; "
                f"family {cfg.family!r} carries recurrent state with no "
                "page transfer")
        if self.prefix_cache:
            raise ValueError(
                "disaggregated prefill is incompatible with prefix_cache: "
                "shared prefix pages live in the decode pool, which the "
                "prefill worker cannot see")
        wcfg = prefill_pcfg or pcfg
        if (wcfg.page_size != pcfg.page_size
                or wcfg.max_pages_per_seq != pcfg.max_pages_per_seq):
            raise ValueError(
                "prefill pool must match the decode pool's page_size and "
                f"max_pages_per_seq (got {wcfg.page_size}x"
                f"{wcfg.max_pages_per_seq} vs {pcfg.page_size}x"
                f"{pcfg.max_pages_per_seq}) — the chunk executable's "
                "geometry")
        self.transfer = KVTransfer(kv_transfer)
        # self.params: post-quantize, post-placement — the worker runs
        # the same weights the decode side serves
        self.worker = PrefillWorker(cfg, self.params, wcfg, mesh=self.mesh)

    # ------------------------------------------------------------- steps --
    def _prefill_step(self) -> None:
        """Same budget loop as the colocated engine, but chunks execute
        on the worker; a finished prompt's pages ship before the
        sequence turns visible to decode."""
        budget = self.prefill_chunk if self.chunked_prefill else None
        spent = 0
        for seq in self.sched.prefilling():
            self.worker.begin(seq)
            plen = seq.request.prompt_len
            logits = None
            while seq.prefill_pos < plen:
                remaining = plen - seq.prefill_pos
                c = remaining if budget is None else min(remaining, max(1, budget - spent))
                if budget is not None and spent > 0 and spent + c > budget:
                    return                   # budget exhausted; resume next step
                logits = self.worker.run_chunk(seq, c)
                self.prefill_tokens += c
                spent += c
            self._receive(seq)
            self._complete_prefill(seq, logits)
            if budget is not None and spent >= budget:
                return

    def _receive(self, seq: SeqState) -> None:
        """Ship the worker's filled pages into the sequence's decode-
        pool pages (allocated at admission, one per prompt page) and
        release the worker side."""
        rid = seq.request.rid
        src_pages = self.worker.finish(rid)
        dst_pages = seq.pages[:len(src_pages)]
        src_ids = jnp.asarray(np.asarray(src_pages, dtype=np.int32))
        dst_ids = jnp.asarray(np.asarray(dst_pages, dtype=np.int32))
        for key in ATTN_STATE_KEYS:
            if key in self.state:
                self.state[key] = self.transfer.ship(
                    self.state[key], dst_ids, self.worker.state[key], src_ids)
        self.worker.release(src_pages)

    def _drain(self) -> List[SeqState]:
        drained = super()._drain()
        for seq in drained:
            self.worker.abort(seq.request.rid)
        return drained

    # ------------------------------------------------------------- stats --
    def stats(self) -> Dict[str, float]:
        out = super().stats()
        out.update({
            "kv_transfer_pages": float(self.transfer.pages_shipped),
            "kv_transfer_bytes": float(self.transfer.bytes_raw),
            "kv_transfer_wire_bytes": float(self.transfer.bytes_wire),
            "prefill_pool_peak_pages": float(self.worker.pool.peak_allocated),
        })
        return out
