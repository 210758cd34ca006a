"""Serving cells: a closed loop of ``clients`` callers over
``Server.stream`` (``api/server.py`` -> ``ServingEngine._serve_loop``).
Each caller waits for its reply and then sends its next request, so the
engine always holds ``clients`` requests.

Set-up makes the weights on the device in one jitted call from the seed,
in the type they are served in, builds the Server, submits one request
per client and drives the engine until every slot decodes: prefill and
decode programs are then warm, and the window opens at the end of that
decode step. It closes at the end of the first decode step that ends
``--seconds`` later.

The benchmark times the engine from outside: it wraps the engine's
decode step, prompt-chunk call and first-token completion (instance
attributes) to record when each ended and what it carried. From those:

* ``serve_tokens_per_s``: output tokens produced in the window (one per
  sequence advanced by each decode step, plus each first token from a
  completed prefill), over the window;
* ``serve_itl_p95_ms``: the 95th percentile of every inter-token gap in
  the window, where each decode step's gap since the previous one counts
  once for every sequence it advanced.
"""
from __future__ import annotations

import gc
import sys
import time

import numpy as np

from perfbench import check as check_mod
from perfbench import reference, traffic
from perfbench.device import memory_peak
from perfbench.trace import WINDOW, capture

TRACE_S = 3.0            # the traced stretch: from the window's first completion on


class Recorder:
    """Host-side spans around the engine's own calls."""

    def __init__(self, engine, seconds: float, trace_dir=None):
        import jax

        self.seconds = seconds
        self.decode = []          # (end time, sequences advanced, [context lengths])
        self.chunks = []          # (end time, start position, length)
        self.firsts = []          # end time of each completed prefill
        self.t0 = self.t_close = None
        self.completed = 0        # requests finished in the window
        self.trace_dir = trace_dir
        self.trace_span = None    # (start, end) of the traced stretch
        self._trace = self._ann = None
        self._ta = jax.profiler.TraceAnnotation
        self.on_open = None
        slots = engine.pcfg.max_slots
        decode_once, run_chunk = engine._decode_once, engine._run_chunk
        complete, prefill_step = engine._complete_prefill, engine._prefill_step

        def timed_decode():
            lens = [s.seq_len + 1 for s in engine.sched.active.values()
                    if s.status == "decoding"]
            with self._ta("perfbench.decode"):
                decode_once()
            t = time.perf_counter()
            self.decode.append((t, len(lens), lens))
            if self.t0 is None and len(lens) == slots:
                self.t0 = t
                if self.on_open is not None:
                    self.on_open()
            elif self.t0 is not None and self.t_close is None:
                self._maybe_trace(t)
                if t - self.t0 >= self.seconds and self._ann is None \
                        and (self.trace_dir is None or self.trace_span is not None):
                    self.t_close = t

        def timed_chunk(seq, c):
            start = seq.prefill_pos
            out = run_chunk(seq, c)
            self.chunks.append((time.perf_counter(), start, c))
            return out

        def timed_complete(seq, logits):
            complete(seq, logits)
            self.firsts.append(time.perf_counter())

        def timed_prefill():
            with self._ta("perfbench.prefill"):
                prefill_step()

        engine._decode_once = timed_decode
        engine._run_chunk = timed_chunk
        engine._complete_prefill = timed_complete
        engine._prefill_step = timed_prefill

    def _maybe_trace(self, t):
        if self.trace_dir is None or self.trace_span is not None and self._ann is None:
            return
        if self._trace is None and self.completed:
            self._trace = capture(self.trace_dir)
            self._trace.__enter__()
            self._ann = self._ta(WINDOW)
            self._ann.__enter__()
            self.trace_span = (time.perf_counter(), None)
        elif self._ann is not None and t - self.trace_span[0] >= min(TRACE_S, self.seconds / 2):
            self._ann.__exit__(None, None, None)
            self.trace_span = (self.trace_span[0], time.perf_counter())
            self._trace.__exit__(None, None, None)
            self._ann = None

    def in_window(self, t) -> bool:
        return (self.t0 is not None and self.t0 < t
                and (self.t_close is None or t <= self.t_close))

    def in_trace(self, t) -> bool:
        return self.trace_span[0] < t <= self.trace_span[1]


def program_spec(cfg: dict, mix: dict, seed: int):
    from repro.api import ModelSpec, RunSpec, ServeSpec, TrainSpec

    prog = cfg["program"]
    return RunSpec(
        model=ModelSpec(arch=prog["arch"], reduced=prog["reduced"], rank=cfg["sct"]["rank"],
                        spectral_mlp=cfg["sct"]["spectral_mlp"],
                        dtype=cfg["precision"]["serve_compute"]),
        train=TrainSpec(seed=seed & 0x7FFFFFFF),
        serve=ServeSpec(slots=mix["slots"], page_size=mix["page_size"],
                        num_pages=mix["num_pages"], pages_per_seq=mix["pages_per_seq"],
                        prefill_budget=mix["prefill_budget"],
                        chunked_prefill=mix["chunked_prefill"]),
    )


def itl_p95_ms(rec: Recorder) -> float:
    gaps, weights = [], []
    prev = None
    for t, n, _ in rec.decode:
        if prev is not None and rec.in_window(t) and n:
            gaps.append(t - prev)
            weights.append(n)
        prev = t
    return float(np.percentile(np.repeat(gaps, weights), 95) * 1e3)


def sample_for_check(done: list, mix: dict, seed: int) -> list:
    """The longest finished request, then others drawn from the seed,
    until ``check_tokens`` served tokens or ``check_max_requests``."""
    done = sorted(done, key=lambda r: (-(len(r[1]) + len(r[2])), r[0]))
    picked, rest = done[:1], done[1:]
    order = np.random.default_rng([seed, 5]).permutation(len(rest))
    for i in order:
        if (sum(len(r[2]) for r in picked) >= mix["check_tokens"]
                or len(picked) >= mix["check_max_requests"]):
            break
        picked.append(rest[int(i)])
    return picked


def run(cell, seed, seconds, trace, *, counter, devices, peaks, t_start):
    import jax
    import jax.numpy as jnp

    from repro.api import Server
    from repro.models.model import init_model
    from perfbench.jobs.train import verify_model

    cfg, mix = cell.cfg, cell.mix
    spec = program_spec(cfg, mix, seed)
    key = reference.seed_key(seed)
    wdt = jnp.dtype(cfg["precision"]["serve_weights"])
    model_cfg = spec.model.config()
    # the key is an argument, not a constant: one program serves every seed
    params = jax.jit(lambda k: jax.tree.map(lambda x: x.astype(wdt),
                                            init_model(k, model_cfg)))(key)
    server = Server(spec, params)
    del params
    verify_model(server.cfg, cfg)
    engine = server.engine
    rec = Recorder(engine, seconds, cell.trace_dir if trace else None)
    stream = traffic.RequestStream(mix, cfg, seed)
    prompts, done, failed = {}, [], 0

    def submit():
        prompt, n = stream.next()
        prompts[server.submit(prompt, n)] = prompt

    def arm():
        counter.armed = True

    rec.on_open = arm
    for _ in range(mix["clients"]):
        submit()
    events = server.stream()
    setup_s = None
    for rid, tokens, status in events:
        t = time.perf_counter()
        if rec.t0 is not None and setup_s is None:
            setup_s = rec.t0 - t_start
        if rec.t_close is not None:
            break
        prompt = prompts.pop(rid)
        if rec.in_window(t):
            done.append((rid, prompt, np.asarray(tokens), status))
            failed += status != "finished"
            rec.completed += 1
        submit()
        server.stream()            # registers the new request with the running loop
    counter.armed = False
    if rec.t_close is None:
        raise SystemExit("perfbench: the closed loop ran dry before the window closed")
    if setup_s is None:
        setup_s = rec.t0 - t_start
    window_s = rec.t_close - rec.t0
    produced = sum(n for t, n, _ in rec.decode if rec.in_window(t)) \
        + sum(1 for t in rec.firsts if rec.in_window(t))
    win_steps = [(n, lens) for t, n, lens in rec.decode if rec.in_window(t)]
    occupancy = sum(n for n, _ in win_steps) / max(len(win_steps), 1)
    itl_p95 = itl_p95_ms(rec)
    mem = memory_peak(devices)

    trace_summary, traced = None, {}
    if trace:
        from perfbench.trace import find_xplane, reduce

        trace_summary = reduce(find_xplane(cell.trace_dir), len(devices))
        traced = {
            "decode_ctx": [lens for t, _, lens in rec.decode if rec.in_trace(t)],
            "chunks": [(s, c) for t, s, c in rec.chunks if rec.in_trace(t)],
        }

    # ---- the check ----
    finished = [(rid, p, toks) for rid, p, toks, st in done if st == "finished"]
    sample = sample_for_check(finished, mix, seed)
    # the engine (and its KV pool) goes before the reference comes
    events = server = engine = None
    gc.collect()
    t_ref = time.perf_counter()
    scorer = reference.Scorer(cfg, seed, mix["page_size"] * mix["pages_per_seq"])
    gap = max(float(np.max(scorer.gaps(p, toks))) for _, p, toks in sample) \
        if sample else float("inf")
    print(f"perfbench: reference {time.perf_counter() - t_ref:.1f} s over "
          f"{len(sample)} requests, {sum(len(t) for _, _, t in sample)} served tokens",
          file=sys.stderr, flush=True)
    correct, check = check_mod.judge({"logit_gap": gap}, cell.limits)
    return {
        "correct": correct and failed == 0, "attempted": len(done), "failed": failed,
        "end_to_end": {"serve_tokens_per_s": produced / window_s,
                       "serve_itl_p95_ms": itl_p95, "setup_s": setup_s},
        "memory_peak_bytes": mem, "check": check,
        "compiles_in_window": counter.count, "trace": trace_summary,
        "context": dict(traced, occupancy=occupancy, slots=mix["slots"]),
        "sample": sample,
    }
