"""Declarative experiment specs: the serializable description of a run.

A :class:`RunSpec` is the single object that describes everything the
repo knows how to do — which architecture to build (``model``), how to
train it (``train``, ``precision``, ``rank``, ``sharding``,
``checkpoint``) and how to serve it (``serve``). It is:

  * **frozen** — specs are values; deriving a variant goes through
    :meth:`RunSpec.replace`, never mutation;
  * **JSON-round-trippable** — ``to_json``/``from_json`` are bit-exact
    inverses (sorted keys, no float surprises: every field is an int,
    str, bool or None except learning rates, which JSON represents
    exactly via repr round-trip);
  * **self-validating** — unknown keys are rejected on ``from_dict``,
    and enum-ish fields (precision mode, serve mode, quantize, rank
    schedule grammar) are checked at construction time, so a spec that
    exists is a spec that can run.

The facades (api/trainer.py, api/server.py) consume RunSpecs; the CLIs
(launch/train.py, launch/serve.py, ``python -m repro``) are thin
argparse -> RunSpec adapters; CheckpointManager embeds the serialized
spec in every checkpoint sidecar so a snapshot is self-describing —
``Server.from_checkpoint(path)`` and ``Trainer.resume(path)`` need zero
re-specified flags.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, Optional

__all__ = [
    "ModelSpec",
    "TrainSpec",
    "PrecisionSpec",
    "RankScheduleSpec",
    "ShardingSpec",
    "StreamingSpec",
    "ServeSpec",
    "CheckpointSpec",
    "RunSpec",
    "WorkloadSpec",
    "SLOSpec",
    "BenchSpec",
]


# ----------------------------------------------------------------------
# shared (de)serialization machinery
# ----------------------------------------------------------------------

class _Spec:
    """Base for all spec dataclasses: dict/JSON round-trip with
    unknown-key rejection, and field-validated ``replace``."""

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, data: Dict[str, Any]) -> "_Spec":
        if not isinstance(data, dict):
            raise TypeError(f"{cls.__name__}.from_dict wants a dict, "
                            f"got {type(data).__name__}")
        fields = {f.name: f for f in dataclasses.fields(cls)}
        unknown = sorted(set(data) - set(fields))
        if unknown:
            raise ValueError(f"{cls.__name__}: unknown key(s) {unknown} "
                             f"(known: {sorted(fields)})")
        kw = {}
        for name, value in data.items():
            sub = _subspec_type(fields[name])
            kw[name] = sub.from_dict(value) if sub is not None else value
        return cls(**kw)

    def replace(self, **overrides) -> "_Spec":
        """A new spec with ``overrides`` applied. Keys are validated, so
        a typo raises instead of silently minting a field."""
        fields = {f.name: f for f in dataclasses.fields(self)}
        unknown = sorted(set(overrides) - set(fields))
        if unknown:
            raise ValueError(f"{type(self).__name__}.replace: unknown "
                             f"field(s) {unknown} (known: {sorted(fields)})")
        return dataclasses.replace(self, **overrides)

    def to_json(self, indent: Optional[int] = None) -> str:
        return json.dumps(self.to_dict(), indent=indent, sort_keys=True)

    @classmethod
    def from_json(cls, text: str) -> "_Spec":
        return cls.from_dict(json.loads(text))


def _subspec_type(field: dataclasses.Field):
    """Nested-spec detection: a field whose default is itself a spec
    instance (RunSpec's sub-specs) recurses through that class's
    ``from_dict``; everything else is a plain JSON scalar."""
    return type(field.default) if isinstance(field.default, _Spec) else None


def _spec(cls):
    return dataclasses.dataclass(frozen=True)(cls)


# ----------------------------------------------------------------------
# sub-specs
# ----------------------------------------------------------------------

@_spec
class ModelSpec(_Spec):
    """Reference into the config registry (config/registry.py) plus the
    declarative SCT overrides a sweep needs: ``rank`` overrides
    ``cfg.sct.rank``; ``spectral_mlp=False`` is the dense baseline.
    ``dtype`` overrides the compute dtype: ``"float32"`` is the regime
    the token-identity oracles hold in, since two programs that round
    differently in bf16 can split a near-tie of the greedy argmax."""
    arch: str = "smollm2-1.7b"
    reduced: bool = False
    rank: Optional[int] = None
    spectral_mlp: Optional[bool] = None
    dtype: Optional[str] = None

    def __post_init__(self):
        if self.dtype not in (None, "float32", "bfloat16"):
            raise ValueError(f"model dtype {self.dtype!r}; options "
                             f"float32|bfloat16")

    def config(self):
        from repro.config import get_config

        cfg = get_config(self.arch, reduced=self.reduced)
        if self.dtype is not None:
            cfg = cfg.replace(dtype=self.dtype)
        sct_kw = {}
        if self.rank is not None:
            sct_kw["rank"] = int(self.rank)
        if self.spectral_mlp is not None:
            sct_kw["spectral_mlp"] = bool(self.spectral_mlp)
        return cfg.replace_sct(**sct_kw) if sct_kw else cfg


@_spec
class TrainSpec(_Spec):
    """The optimization run: step budget, batch geometry, LR schedule
    inputs, microbatching, and the data/init seed. ``warmup=None`` is
    the CLI's historical auto rule ``min(100, steps // 10 + 1)``."""
    steps: int = 100
    batch: int = 8
    seq: int = 64
    lr: float = 1e-3
    warmup: Optional[int] = None
    microbatches: int = 1
    seed: int = 0
    telemetry: bool = False

    @property
    def warmup_steps(self) -> int:
        return self.warmup if self.warmup is not None \
            else min(100, self.steps // 10 + 1)


@_spec
class PrecisionSpec(_Spec):
    """The precision contract, with the legacy path an explicit mode
    rather than a sentinel ``None``:

      legacy  compute in ``ModelConfig.dtype``, fp32 accumulation, no
              loss scaling — what every run did before --precision grew
              presets.
      fp32 / bf16 / mixed — the core/precision.py presets.
    """
    mode: str = "legacy"

    def __post_init__(self):
        from repro.core.precision import LEGACY, POLICIES

        allowed = [LEGACY, *POLICIES]
        if self.mode not in allowed:
            raise ValueError(f"precision mode {self.mode!r}; options {allowed}")

    def policy(self):
        """The optimizer-facing PrecisionPolicy — None for legacy (the
        optimizer's no-cast, no-scaling path; steps.py resolves the
        effective dtypes via core/precision.effective_policy)."""
        from repro.core.precision import precision_policy

        return precision_policy(self.mode)


@_spec
class RankScheduleSpec(_Spec):
    """Adaptive-rank policy as its CLI grammar string (rank/schedule.py:
    ``static:K`` | ``step:S=K,...`` | ``energy:T[,kv...]``), or None for
    fixed-rank training. The string is the serialization format — it is
    validated at construction by actually parsing it."""
    schedule: Optional[str] = None

    def __post_init__(self):
        self.parsed()     # grammar errors surface at spec build time

    def parsed(self):
        from repro.rank import parse_rank_schedule

        return parse_rank_schedule(self.schedule)


@_spec
class ShardingSpec(_Spec):
    """Mesh geometry. ``data``/``model`` of None means the launcher
    heuristic: all visible devices, with the model axis the largest of
    (16, 8, 4, 2, 1) dividing both the device count and ``cfg.d_ff``;
    single-device runs get no mesh (plain jit). Explicit values pin the
    axes (their product must equal the device count)."""
    data: Optional[int] = None
    model: Optional[int] = None
    # decode-path tensor parallelism: the serve mesh is 1-D ('model',)
    # over this many devices (sharding/partition.py:serve_mesh); None/1
    # keeps single-device serving. Orthogonal to the training axes —
    # serving never builds the 2-D training mesh.
    decode_mesh: Optional[int] = None

    def __post_init__(self):
        if self.decode_mesh is not None and self.decode_mesh < 1:
            raise ValueError(f"decode_mesh {self.decode_mesh} must be >= 1")

    def serve_mesh(self):
        """The tensor-parallel serve mesh, or None for single-device
        serving (tp unset or 1)."""
        if self.decode_mesh is None or self.decode_mesh == 1:
            return None
        from repro.sharding.partition import serve_mesh

        return serve_mesh(self.decode_mesh)

    def mesh(self, cfg):
        import jax

        from repro.launch.mesh import make_mesh

        n_dev = jax.device_count()
        if self.data is None and self.model is None:
            if n_dev <= 1:
                return None
            n_model = 1
            for cand in (16, 8, 4, 2, 1):
                if n_dev % cand == 0 and cfg.d_ff % cand == 0:
                    n_model = cand
                    break
            return make_mesh((n_dev // n_model, n_model), ("data", "model"))
        n_model = self.model or 1
        n_data = self.data or n_dev // n_model
        if n_data * n_model != n_dev:
            raise ValueError(f"sharding {n_data}x{n_model} wants "
                             f"{n_data * n_model} devices, have {n_dev}")
        if n_data == n_model == 1:
            return None
        return make_mesh((n_data, n_model), ("data", "model"))


@_spec
class StreamingSpec(_Spec):
    """Long-context streaming KV policy (serving/streaming.py):
    ``window_pages=None`` disables streaming entirely (the default —
    every existing spec round-trips unchanged); setting it turns on
    attention sinks + sliding-window page eviction, with ``sink_pages``
    pages pinned forever at the head of every sequence. ``cold_kv``
    picks the tier for resident pages older than the window: ``"none"``
    keeps them at pool precision, ``"int8"`` demotes them to the
    page-granular int8 shadow pools (transparent dequant-on-attend)."""
    sink_pages: int = 1
    window_pages: Optional[int] = None
    cold_kv: str = "none"

    def __post_init__(self):
        if self.sink_pages < 1:
            raise ValueError(f"sink_pages {self.sink_pages} must be >= 1")
        if self.window_pages is not None and self.window_pages < 1:
            raise ValueError(f"window_pages {self.window_pages} must be >= 1")
        if self.cold_kv not in ("none", "int8"):
            raise ValueError(f"cold_kv {self.cold_kv!r}; options none|int8")
        if self.cold_kv != "none" and self.window_pages is None:
            raise ValueError("cold_kv needs streaming on (set window_pages)")

    @property
    def enabled(self) -> bool:
        return self.window_pages is not None

    def config(self):
        """The runtime StreamingConfig, or None when disabled."""
        if not self.enabled:
            return None
        from repro.serving.streaming import StreamingConfig

        return StreamingConfig(sink_pages=self.sink_pages,
                               window_pages=self.window_pages,
                               cold_kv=self.cold_kv)


@_spec
class ServeSpec(_Spec):
    """The serving side. ``mode="paged"`` is the continuous-batching
    engine (serving/engine.py) — page geometry, slots, prefill budget,
    prefix cache, chunked prefill, deadlines, int8 quantization.
    ``mode="static"`` is the dense (batch, max_seq)-cache path; it only
    reads ``batch``/``prompt_len``/``gen``/``quantize``. ``rank``
    resizes spectral groups at checkpoint-load time (cheap serving of a
    shrunk snapshot); ``gen`` doubles as the default ``max_new_tokens``
    for ``Server.submit``.

    Multi-tenant scheduling: ``scheduler`` picks the admission policy —
    ``"fifo"`` (strict arrival order, the original scheduler) or
    ``"slo"`` (per-tenant fair-share token accounting, priority
    classes, deadline-aware shedding — serving/scheduler.py:
    SLOScheduler; ``shed=False`` keeps the fair-share ordering but
    never rejects a doomed request, for apples-to-apples ordering
    studies). ``tenant``/``priority``/``default_deadline`` are the
    per-request defaults :meth:`Server.submit` stamps onto requests
    that don't say otherwise (priority 0 is the most urgent class;
    ``default_deadline`` falls back to ``request_timeout`` when None,
    keeping the pre-SLO flag meaningful).

    Self-speculative decoding: ``speculative_rank`` names the rank
    ladder as a grammar string — ``"32"`` drafts at rank 32 and
    verifies at full rank; ``"32,128"`` adds a rank-128 intermediate
    verification stage (comma-separated, non-decreasing, drafter
    first; the full-rank target is always implicit). The drafters are
    rank-truncations of the *same* checkpoint (the paper's rank-sweep
    result is what makes them usable for free); ``draft_tokens`` is
    the burst length the drafter proposes per engine step. Requires
    ``mode="paged"`` and is mutually exclusive with ``prefix_cache``
    (serving/speculative.py explains both)."""
    mode: str = "paged"
    slots: int = 4
    page_size: int = 16
    num_pages: int = 64
    pages_per_seq: int = 8
    prefill_budget: Optional[int] = 64
    prefix_cache: bool = False
    chunked_prefill: bool = False
    request_timeout: Optional[int] = None
    quantize: Optional[str] = None
    rank: Optional[int] = None
    batch: int = 4
    prompt_len: int = 16
    gen: int = 32
    scheduler: str = "fifo"
    shed: bool = True
    tenant: str = "default"
    priority: int = 0
    default_deadline: Optional[int] = None
    speculative_rank: Optional[str] = None
    draft_tokens: int = 4
    # disaggregated serving: prompt prefill runs on a separate worker
    # with its own page pool; finished pages ship to the decode pool
    # through serving/distributed.py:KVTransfer. ``kv_transfer`` picks
    # the wire format: "raw" (lossless page copy at pool dtype) or
    # "int8" (symmetric per-channel quantization on the wire, opt-in).
    disaggregate: bool = False
    kv_transfer: str = "raw"
    # long-context streaming KV policy; off by default (window unset)
    streaming: StreamingSpec = StreamingSpec()

    def __post_init__(self):
        if self.mode not in ("paged", "static"):
            raise ValueError(f"serve mode {self.mode!r}; options paged|static")
        if self.quantize not in (None, "int8"):
            raise ValueError(f"quantize {self.quantize!r}; options int8")
        if self.scheduler not in ("fifo", "slo"):
            raise ValueError(f"serve scheduler {self.scheduler!r}; "
                             f"options fifo|slo")
        if self.priority < 0:
            raise ValueError(f"priority {self.priority} must be >= 0 "
                             f"(0 is the most urgent class)")
        if not self.tenant:
            raise ValueError("tenant must be a non-empty string")
        if self.draft_tokens < 1:
            raise ValueError(f"draft_tokens {self.draft_tokens} must be >= 1")
        if self.speculative_rank is not None:
            if self.mode != "paged":
                raise ValueError("speculative decoding needs mode='paged'")
            if self.prefix_cache:
                raise ValueError(
                    "speculative_rank and prefix_cache are mutually "
                    "exclusive (an index page holds one ladder level's KV; "
                    "a speculative sequence needs every level's)")
            self.speculative_ladder()   # grammar errors at build time
        if self.kv_transfer not in ("raw", "int8"):
            raise ValueError(f"kv_transfer {self.kv_transfer!r}; "
                             f"options raw|int8")
        if self.disaggregate:
            if self.mode != "paged":
                raise ValueError("disaggregated prefill needs mode='paged'")
            if self.prefix_cache:
                raise ValueError(
                    "disaggregate and prefix_cache are mutually exclusive "
                    "(shared prefix pages live in the decode pool, which "
                    "the prefill worker cannot see)")
            if self.speculative_rank is not None:
                raise ValueError(
                    "disaggregate and speculative_rank are mutually "
                    "exclusive (the speculative engine owns its own "
                    "prefill/verify interleaving)")
        if self.streaming.enabled:
            if self.mode != "paged":
                raise ValueError("streaming KV needs mode='paged'")
            if self.speculative_rank is not None:
                raise ValueError(
                    "streaming and speculative_rank are mutually exclusive "
                    "(a drafted burst can cross an eviction boundary the "
                    "verifier no longer sees)")
            if self.disaggregate:
                raise ValueError(
                    "streaming and disaggregate are mutually exclusive "
                    "(the prefill worker's pool has no eviction policy)")
            cap = self.streaming.sink_pages + self.streaming.window_pages + 1
            if cap > self.pages_per_seq:
                raise ValueError(
                    f"streaming resident cap {cap} pages (sink + window + "
                    f"growth) exceeds pages_per_seq={self.pages_per_seq}")

    def speculative_ladder(self) -> list:
        """The parsed rank ladder (drafter first), or ``[]`` when
        speculation is off — serving/speculative.py owns the grammar."""
        if self.speculative_rank is None:
            return []
        from repro.serving.speculative import parse_ladder

        return parse_ladder(self.speculative_rank)

    @property
    def effective_deadline(self) -> Optional[int]:
        """The submit-time deadline default: ``default_deadline`` when
        set, else the engine-level ``request_timeout``."""
        return (self.default_deadline if self.default_deadline is not None
                else self.request_timeout)

    def paged_config(self):
        from repro.serving import PagedCacheConfig

        return PagedCacheConfig(
            page_size=self.page_size,
            num_pages=self.num_pages,
            max_slots=self.slots,
            max_pages_per_seq=self.pages_per_seq,
        )


@_spec
class CheckpointSpec(_Spec):
    """Where and how often the run checkpoints. ``directory=None`` means
    no checkpointing — :meth:`Trainer.fit` requires a directory (the
    fault-tolerant loop restarts from disk); step-at-a-time
    ``Trainer.step`` runs fine without one."""
    directory: Optional[str] = None
    every: int = 50
    keep: int = 3


# ----------------------------------------------------------------------
# the top-level spec
# ----------------------------------------------------------------------

@_spec
class RunSpec(_Spec):
    """One experiment, fully described. Sub-specs compose orthogonally;
    derive variants with :meth:`replace` (sub-spec instances, dicts
    merged into a sub-spec, or dotted leaf paths):

        spec.replace(precision=PrecisionSpec("mixed"))
        spec.replace(serve={"quantize": "int8", "slots": 8})
        spec.replace(**{"train.steps": 500, "serve.rank": 64})
    """
    model: ModelSpec = ModelSpec()
    train: TrainSpec = TrainSpec()
    precision: PrecisionSpec = PrecisionSpec()
    rank: RankScheduleSpec = RankScheduleSpec()
    sharding: ShardingSpec = ShardingSpec()
    serve: ServeSpec = ServeSpec()
    checkpoint: CheckpointSpec = CheckpointSpec()

    def replace(self, **overrides) -> "RunSpec":
        return _composite_replace(self, overrides)


def _composite_replace(spec, overrides: Dict[str, Any]):
    """``replace`` for specs composed of sub-specs (RunSpec, BenchSpec):
    accepts sub-spec instances, dicts merged into the existing sub-spec,
    and dotted leaf paths — every key validated, typos raise."""
    cls_name = type(spec).__name__
    fields = {f.name: f for f in dataclasses.fields(spec)}
    merged: Dict[str, Dict[str, Any]] = {}
    flat: Dict[str, Any] = {}
    for key, value in overrides.items():
        name, dot, leaf = key.partition(".")
        if name not in fields:
            raise ValueError(f"{cls_name}.replace: unknown field {name!r} "
                             f"(known: {sorted(fields)})")
        if dot:
            merged.setdefault(name, {})[leaf] = value
        elif isinstance(value, dict) and _subspec_type(fields[name]) is not None:
            merged.setdefault(name, {}).update(value)
        else:
            expected = type(fields[name].default)
            if not isinstance(value, expected):
                raise TypeError(f"{cls_name}.replace: {name} wants "
                                f"{expected.__name__} (or a dict / "
                                f"dotted '{name}.<field>' override), "
                                f"got {type(value).__name__}")
            flat[name] = value
    for name, sub_overrides in merged.items():
        base = flat.get(name, getattr(spec, name))
        flat[name] = base.replace(**sub_overrides)
    return dataclasses.replace(spec, **flat)


# ----------------------------------------------------------------------
# benchmark specs: declarative workloads, SLOs, and bench runs
# ----------------------------------------------------------------------

def _parse_weights(text: str, what: str) -> list:
    """Comma-separated positive weights (``"1"``, ``"3,1"``)."""
    try:
        weights = [float(w) for w in text.split(",") if w.strip()]
    except ValueError:
        raise ValueError(f"{what} {text!r}: want comma-separated numbers")
    if not weights or any(w <= 0 for w in weights):
        raise ValueError(f"{what} {text!r}: want positive weights")
    return weights


@_spec
class WorkloadSpec(_Spec):
    """A synthetic production traffic trace, fully determined by its
    fields (seeded — the same spec always generates the same requests;
    bench/workload.py is the generator).

      * **arrival process** (engine-step time): ``poisson`` draws the
        per-step arrival count from Poisson(``rate``); ``onoff`` is the
        bursty variant — Poisson(``rate``) for ``on_steps`` steps, then
        silent for ``off_steps``; ``fixed`` spaces arrivals evenly at
        ``rate`` per step (deterministic smoke traces).
      * **multi-tenant shared-prefix mix** — requests draw a tenant
        from ``tenants`` weights (ids ``t0``, ``t1``, ...); each tenant
        has its own ``shared_prefix``-token system prompt opening every
        one of its requests (the prefix-cache workload, per tenant).
      * **long-tail lengths** — prompt tails and output budgets are
        lognormal with the given mean and coefficient of variation
        (``cv=0`` pins the length exactly); the generator clips to the
        serving geometry so every request is admissible.
      * **priority classes** — each request draws a class from
        ``priority_mix`` weights (class 0 first, most urgent).
    """
    arrival: str = "poisson"
    rate: float = 0.5                # mean arrivals per engine step
    requests: int = 32
    seed: int = 0
    tenants: str = "1"               # per-tenant arrival weights
    shared_prefix: int = 0           # system-prompt tokens per tenant
    prompt_mean: int = 16
    prompt_cv: float = 0.5
    gen_mean: int = 16
    gen_cv: float = 0.5
    priority_mix: str = "1"          # per-class weights, class 0 first
    on_steps: int = 8                # onoff: burst length
    off_steps: int = 8               # onoff: silence length

    def __post_init__(self):
        if self.arrival not in ("poisson", "onoff", "fixed"):
            raise ValueError(f"arrival process {self.arrival!r}; "
                             f"options poisson|onoff|fixed")
        if self.rate <= 0:
            raise ValueError(f"arrival rate {self.rate} must be > 0")
        if self.requests < 1:
            raise ValueError(f"requests {self.requests} must be >= 1")
        if self.prompt_mean < 1 or self.gen_mean < 1:
            raise ValueError("prompt_mean and gen_mean must be >= 1")
        if self.prompt_cv < 0 or self.gen_cv < 0:
            raise ValueError("length cv must be >= 0")
        if self.shared_prefix < 0:
            raise ValueError("shared_prefix must be >= 0")
        if self.arrival == "onoff" and (self.on_steps < 1 or self.off_steps < 1):
            raise ValueError("onoff arrivals need on_steps/off_steps >= 1")
        self.tenant_weights()
        self.priority_weights()

    def tenant_weights(self) -> list:
        return _parse_weights(self.tenants, "tenants")

    def priority_weights(self) -> list:
        return _parse_weights(self.priority_mix, "priority_mix")


@_spec
class SLOSpec(_Spec):
    """Service-level objectives the bench scores against (and the SLO
    scheduler enforces). ``deadlines`` maps priority classes to
    end-to-end deadlines in engine steps, as a grammar string (the
    serialization format, validated by parsing): ``"64"`` gives every
    class the same deadline, ``"0=32,1=96"`` is per-class, ``None``
    means no deadline (every completion counts as SLO-met). ``ttft`` is
    the time-to-first-token target in engine steps — reported against,
    never enforced by eviction. ``shed`` lets the SLO scheduler refuse
    admission to requests that provably cannot finish inside their
    deadline (status ``"shed"``) instead of letting them burn decode
    slots and time out."""
    deadlines: Optional[str] = None
    ttft: Optional[int] = None
    shed: bool = True

    def __post_init__(self):
        self.deadline_map()
        if self.ttft is not None and self.ttft < 1:
            raise ValueError(f"ttft target {self.ttft} must be >= 1")

    def deadline_map(self) -> Dict[int, int]:
        """{priority class -> deadline steps}; empty when no SLO."""
        if self.deadlines is None:
            return {}
        text = self.deadlines.strip()
        try:
            if "=" not in text:
                return {0: int(text)}
            out = {}
            for part in text.split(","):
                cls_s, _, dl_s = part.partition("=")
                out[int(cls_s)] = int(dl_s)
            return out
        except ValueError:
            raise ValueError(
                f"SLO deadlines {self.deadlines!r}: want 'N' or "
                f"'CLS=N,CLS=N,...' (engine steps per priority class)")

    def deadline_for(self, priority: int) -> Optional[int]:
        """The deadline for a priority class: its own entry, else the
        highest class's entry (a single ``"64"`` covers everyone),
        else None."""
        dmap = self.deadline_map()
        if not dmap:
            return None
        if priority in dmap:
            return dmap[priority]
        return dmap[max(dmap)]


@_spec
class BenchSpec(_Spec):
    """One benchmark run, fully described: the model and serving
    geometry under test, the workload driven at it, the SLOs scored,
    and the sweep axes — ``overloads`` (arrival-rate multipliers; 1 is
    the nominal rate, 2 doubles it), ``schedulers`` (admission policies
    compared arm-by-arm), ``precisions``/``ranks`` (throughput-per-
    variant axes). ``python -m repro bench`` resolves every benchmark
    CLI to one of these first (``--dump-spec`` prints it), and
    bench/runner.py turns it into a schema-valid ``BENCH_<area>.json``
    (docs/benchmarks.md)."""
    name: str = "serving"
    model: ModelSpec = ModelSpec("llama3.2-1b", reduced=True)
    serve: ServeSpec = ServeSpec()
    workload: WorkloadSpec = WorkloadSpec()
    slo: SLOSpec = SLOSpec()
    overloads: str = "1,2"
    schedulers: str = "fifo,slo"
    precisions: str = "fp32"
    ranks: str = ""
    # serving-topology axis: "colocated" is the single-engine baseline,
    # "disaggregated" runs the same workload through the prefill/decode
    # worker split (serving/distributed.py) — arm-by-arm comparable
    # because both emit identical tokens
    serving_modes: str = "colocated"

    def __post_init__(self):
        if not self.name:
            raise ValueError("bench name must be non-empty")
        self.overload_factors()
        for s in self.scheduler_arms():
            if s not in ("fifo", "slo"):
                raise ValueError(f"scheduler {s!r}; options fifo|slo")
        for p in self.precision_arms():
            if p not in ("fp32", "int8"):
                raise ValueError(f"precision {p!r}; options fp32|int8")
        for m in self.serving_mode_arms():
            if m not in ("colocated", "disaggregated"):
                raise ValueError(f"serving mode {m!r}; options "
                                 f"colocated|disaggregated")
        self.rank_arms()

    def overload_factors(self) -> list:
        return _parse_weights(self.overloads, "overloads")

    def scheduler_arms(self) -> list:
        arms = [s.strip() for s in self.schedulers.split(",") if s.strip()]
        if not arms:
            raise ValueError("schedulers must name at least one arm")
        return arms

    def precision_arms(self) -> list:
        return [p.strip() for p in self.precisions.split(",") if p.strip()]

    def rank_arms(self) -> list:
        try:
            return [int(r) for r in self.ranks.split(",") if r.strip()]
        except ValueError:
            raise ValueError(f"ranks {self.ranks!r}: want comma-separated ints")

    def serving_mode_arms(self) -> list:
        arms = [m.strip() for m in self.serving_modes.split(",") if m.strip()]
        if not arms:
            raise ValueError("serving_modes must name at least one arm")
        return arms

    def replace(self, **overrides) -> "BenchSpec":
        return _composite_replace(self, overrides)
