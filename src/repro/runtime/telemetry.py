"""Step-time telemetry + straggler detection + serving telemetry.

Feeds the scheduling-assistant runtime (paper §3): on real hardware the
per-device utilization counters come from the profiler; here step-time
outliers flag stragglers, and ``to_utilization`` converts plan-modeled loads
+ measured skew into the per-resource utilization dict the assistants
consume.

``ServeTelemetry`` is the serving-side counterpart: the continuous-batching
engine records slot occupancy, KV-cache block pressure and step latency each
decode step, and ``assistant_callback`` turns that record into the
``telemetry=`` feed of ``core.assistants.run_adaptation`` — live serving
interference (instead of the analytical simulator alone) driving the §3
out-box protocol.  ``StepSpans`` is what the engine fills while a step
runs: one profiler span per phase of the step, and the phase's host
seconds and work for the step's ``ServeStep``.
"""

from __future__ import annotations

import statistics
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable

from jax.profiler import TraceAnnotation


@dataclass
class Telemetry:
    window: int = 50
    straggler_factor: float = 1.5
    steps: list = field(default_factory=list)      # (step, seconds, loss)
    stragglers: list = field(default_factory=list)

    def record(self, step: int, seconds: float, loss: float) -> None:
        self.steps.append((step, seconds, loss))
        recent = [s for _, s, _ in self.steps[-self.window:]]
        if len(recent) >= 10:
            med = statistics.median(recent)
            if seconds > self.straggler_factor * med:
                self.stragglers.append((step, seconds, med))

    def median_ms(self) -> float:
        if not self.steps:
            return 0.0
        return statistics.median(s for _, s, _ in self.steps) * 1e3

    def n_stragglers(self) -> int:
        return len(self.stragglers)

    def losses(self) -> list:
        return [l for _, _, l in self.steps]


@dataclass
class ServeStep:
    """One continuous-batching engine step's counters."""

    step: int
    seconds: float
    active_slots: tuple          # slot indices that decoded this step
    n_slots: int
    blocks_in_use: int
    n_blocks: int
    prefills: int = 0            # prefills *completed* (1 emitted token each)
    prefill_chunks: int = 0      # chunked-prefill work units this step
    new_tokens: int = 0
    # physical paged-cache residency (0 when the engine runs the dense
    # accounting-only regime — see serve.cache.PagedKVStore)
    resident_bytes: int = 0
    capacity_bytes: int = 0
    # per-layer-group residency split (paged regime): {"global": bytes,
    # "window": bytes, "recurrent": bytes, "cross": bytes} — window rings
    # stay O(window), recurrent slots O(1), and enc-dec cross block sets
    # flat (static, written once at admission) regardless of generated
    # length, which this field lets the assistants (and the invariant
    # tests) observe
    resident_by_group: dict = field(default_factory=dict)
    # lazy-pricing safety net: slots evicted and requeued this step
    preemptions: int = 0
    # prefix cache (sharable layouts): tokens looked up / served from the
    # cache at admissions this step, plus an instantaneous view of the
    # pool's sharing state
    prefix_hit_tokens: int = 0
    prefix_lookup_tokens: int = 0
    shared_saved_bytes: int = 0       # bytes deduplicated right now
    cached_blocks: int = 0            # refcount-0 committed blocks resident
    # self-speculative decoding: draft tokens proposed / accepted this
    # step, and cache rows written then rewound after rejection
    drafted: int = 0
    accepted: int = 0
    rewound_tokens: int = 0
    # paged decode: the pages the step's decode had to read, sum of
    # ceil(len / block_size) over the decoding lanes' resident lengths
    # (a decode that gathers the full width reads every lane's whole
    # table, lanes x table width); 0 without a paged decode this step
    decode_pages: int = 0
    # where the step's host time went and what it did (see StepSpans):
    # perf_counter at its start and end, host seconds per phase span,
    # the rids admitted, the prompt chunks run ((rid, start, valid,
    # total) rows), and the lanes decoded ((rid, position) of the row
    # each wrote)
    t0: float = 0.0
    t1: float = 0.0
    phases: dict = field(default_factory=dict)
    admitted: tuple = ()
    chunks: tuple = ()
    decoded: tuple = ()
    # sparse-expert models: the (token, slot) pairs the programs whose
    # results this step brought to the host routed to each held expert,
    # summed over the MoE layers, then those routed to experts held
    # elsewhere; None for a model without MoE layers
    experts: tuple | None = None


class _Phase:
    """A profiler span that adds its host seconds to ``phases[name]``."""

    __slots__ = ("_phases", "_name", "_span", "_t0")

    def __init__(self, phases: dict, name: str, span: TraceAnnotation):
        self._phases, self._name, self._span = phases, name, span

    def __enter__(self) -> None:
        self._span.__enter__()
        self._t0 = time.perf_counter()

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t0
        self._span.__exit__(*exc)
        self._phases[self._name] = self._phases.get(self._name, 0.0) + dt


class StepSpans:
    """The engine step in progress: its phase spans and what it did.

    ``span(name, **meta)`` opens a ``jax.profiler.TraceAnnotation`` named
    ``name`` whose stats are ``step`` and ``meta``; a running profiler
    writes it on its host plane, on the device trace's clock (about a
    microsecond when none runs).  On exit the span's ``perf_counter``
    seconds are added to ``phases[name]``.  The engine opens phases one
    after another inside one ``engine.step`` span, never one inside
    another, so a phase's seconds are its self time and ``phases`` sums
    to at most the step's.
    """

    __slots__ = ("step", "t0", "phases", "admitted", "chunks", "decoded")

    def __init__(self, step: int):
        self.step = step
        self.t0 = time.perf_counter()
        self.phases: dict = {}
        self.admitted: list = []
        self.chunks: list = []
        self.decoded: list = []

    def span(self, name: str, **meta) -> _Phase:
        return _Phase(self.phases, name,
                      TraceAnnotation(name, step=self.step, **meta))


@dataclass
class ServeTelemetry:
    """Per-step serving counters + the bridge to the §3 assistants.

    ``device_interference`` maps slot occupancy onto the device mesh (slot s
    is served by device ``s % k``, the engine's round-robin lane placement)
    and cache pressure onto memory, producing the per-device busy-time
    multipliers ``core.assistants.simulate_utilization`` consumes.
    """

    window: int = 50
    alpha: float = 0.75          # compute inflation per unit slot occupancy
    beta: float = 0.5            # memory inflation per unit cache pressure
    history: int = 10_000        # retained ServeStep records (memory bound
                                 # for long-lived serving loops); whole-run
                                 # totals below survive eviction
    steps: deque = field(default_factory=deque)

    def __post_init__(self):
        self.steps = deque(self.steps, maxlen=self.history)
        self._total_tokens = 0
        self._peak_pressure = 0.0
        self._max_concurrency = 0
        self._peak_resident_bytes = 0
        self._peak_group_bytes: dict = {}
        self._total_preemptions = 0
        self._prefix_hit_tokens = 0
        self._prefix_lookup_tokens = 0
        self._peak_shared_saved_bytes = 0
        self._total_drafted = 0
        self._total_accepted = 0
        self._total_rewound = 0
        self._starved_decode_steps = 0
        self._expert_load = None

    def reset(self) -> None:
        """Drop all recorded steps and whole-run aggregates."""
        self.steps.clear()
        self._total_tokens = 0
        self._peak_pressure = 0.0
        self._max_concurrency = 0
        self._peak_resident_bytes = 0
        self._peak_group_bytes = {}
        self._total_preemptions = 0
        self._prefix_hit_tokens = 0
        self._prefix_lookup_tokens = 0
        self._peak_shared_saved_bytes = 0
        self._total_drafted = 0
        self._total_accepted = 0
        self._total_rewound = 0
        self._starved_decode_steps = 0
        self._expert_load = None

    def record_step(self, step: int, seconds: float, active_slots,
                    n_slots: int, blocks_in_use: int, n_blocks: int,
                    prefills: int = 0, prefill_chunks: int = 0,
                    new_tokens: int = 0,
                    resident_bytes: int = 0, capacity_bytes: int = 0,
                    resident_by_group: dict = None, preemptions: int = 0,
                    prefix_hit_tokens: int = 0,
                    prefix_lookup_tokens: int = 0,
                    shared_saved_bytes: int = 0,
                    cached_blocks: int = 0, drafted: int = 0,
                    accepted: int = 0, rewound_tokens: int = 0,
                    decode_pages: int = 0,
                    t0: float = 0.0, t1: float = 0.0, phases: dict = None,
                    admitted=(), chunks=(), decoded=(),
                    experts=None) -> None:
        self.steps.append(ServeStep(
            step=step, seconds=seconds, active_slots=tuple(active_slots),
            n_slots=n_slots, blocks_in_use=blocks_in_use, n_blocks=n_blocks,
            prefills=prefills, prefill_chunks=prefill_chunks,
            new_tokens=new_tokens,
            resident_bytes=resident_bytes, capacity_bytes=capacity_bytes,
            resident_by_group=dict(resident_by_group or {}),
            preemptions=preemptions,
            prefix_hit_tokens=prefix_hit_tokens,
            prefix_lookup_tokens=prefix_lookup_tokens,
            shared_saved_bytes=shared_saved_bytes,
            cached_blocks=cached_blocks, drafted=drafted,
            accepted=accepted, rewound_tokens=rewound_tokens,
            decode_pages=decode_pages,
            t0=t0, t1=t1, phases=dict(phases or {}),
            admitted=tuple(admitted), chunks=tuple(chunks),
            decoded=tuple(decoded), experts=experts))
        if experts is not None:
            self._expert_load = (list(experts) if self._expert_load is None
                                 else [a + b for a, b in
                                       zip(self._expert_load, experts)])
        # chunk work units are not emitted tokens — only completed prefills
        # (one greedy token each) and decode tokens count
        self._total_tokens += new_tokens + prefills
        if n_blocks:
            self._peak_pressure = max(self._peak_pressure,
                                      blocks_in_use / n_blocks)
        self._max_concurrency = max(self._max_concurrency, len(active_slots))
        self._peak_resident_bytes = max(self._peak_resident_bytes,
                                        resident_bytes)
        for group, nbytes in (resident_by_group or {}).items():
            self._peak_group_bytes[group] = max(
                self._peak_group_bytes.get(group, 0), nbytes)
        self._total_preemptions += preemptions
        self._prefix_hit_tokens += prefix_hit_tokens
        self._prefix_lookup_tokens += prefix_lookup_tokens
        self._peak_shared_saved_bytes = max(self._peak_shared_saved_bytes,
                                            shared_saved_bytes)
        self._total_drafted += drafted
        self._total_accepted += accepted
        self._total_rewound += rewound_tokens
        # decode-step starvation: every decode lane that shared this engine
        # step with prefill work had its token delayed by that prefill's
        # compute — the displacement disaggregated prefill/decode removes.
        # A running total (not derived from `steps`) so history eviction
        # cannot lose it.
        if (prefills or prefill_chunks) and active_slots:
            self._starved_decode_steps += len(tuple(active_slots))

    # -- aggregates -----------------------------------------------------------
    def _recent(self) -> list:
        recent = list(self.steps)
        return recent[-self.window:]

    def occupancy(self) -> float:
        """Mean fraction of slots decoding over the recent window (0 when
        no recent step had any slots — e.g. a replica that has only run
        admission-less bookkeeping steps — not ``StatisticsError``)."""
        vals = [len(s.active_slots) / s.n_slots for s in self._recent()
                if s.n_slots]
        return statistics.mean(vals) if vals else 0.0

    def cache_pressure(self) -> float:
        """Mean fraction of KV-cache blocks allocated over the recent
        window (0 when no step had a block pool — e.g. a pure-recurrent
        arch whose paged layout holds only state slots)."""
        vals = [s.blocks_in_use / s.n_blocks for s in self._recent()
                if s.n_blocks]
        return statistics.mean(vals) if vals else 0.0

    def peak_cache_pressure(self) -> float:
        return self._peak_pressure

    def peak_resident_bytes(self) -> int:
        """Peak physical paged-cache residency (0 in the dense regime)."""
        return self._peak_resident_bytes

    def peak_resident_bytes_by_group(self) -> dict:
        """Peak residency per layer group
        ({"global"/"window"/"recurrent"/"cross"} -> bytes; empty in the
        dense regime).  The window entry is bounded by O(window), the
        recurrent entry by O(n_slots), and the cross entry by
        O(n_slots x frontend_tokens) — flat per lane for a request's whole
        lifetime — regardless of generated length; these are the
        invariants the window-ring and static-cross tests assert."""
        return dict(self._peak_group_bytes)

    def max_concurrency(self) -> int:
        return self._max_concurrency

    def mean_step_ms(self) -> float:
        recent = self._recent()
        if not recent:
            return 0.0
        return statistics.mean(s.seconds for s in recent) * 1e3

    def total_tokens(self) -> int:
        return self._total_tokens

    def total_preemptions(self) -> int:
        """Whole-run count of lazy-pricing preempt-and-requeue evictions."""
        return self._total_preemptions

    def prefix_hit_rate(self) -> float:
        """Fraction of looked-up prompt tokens served from the prefix
        cache over the whole run (0 when the cache is off or no admission
        carried a hash chain)."""
        if not self._prefix_lookup_tokens:
            return 0.0
        return self._prefix_hit_tokens / self._prefix_lookup_tokens

    def peak_shared_saved_bytes(self) -> int:
        """Peak physical bytes deduplicated by prefix-block sharing."""
        return self._peak_shared_saved_bytes

    def accept_rate(self) -> float:
        """Fraction of drafted speculative tokens the verify pass accepted
        over the whole run (0 when speculation is off) — the §3 assistant
        loop's signal for tuning the draft depth."""
        if not self._total_drafted:
            return 0.0
        return self._total_accepted / self._total_drafted

    def total_drafted(self) -> int:
        return self._total_drafted

    def total_rewound_tokens(self) -> int:
        """Whole-run count of cache rows written by a draft/verify pass and
        then rewound after rejection (block-tail truncation + window-ring
        rollback + recurrent-state restore)."""
        return self._total_rewound

    def decode_starvation(self) -> int:
        """Whole-run count of decode-lane-steps displaced by prefill work:
        each active decode lane in an engine step that also ran a prefill
        (whole or chunk) counts one unit.  Deterministic under greedy —
        the quantity the router benchmark gates when comparing co-located
        against disaggregated prefill/decode."""
        return self._starved_decode_steps

    def expert_load(self) -> list | None:
        """Whole-run (token, slot) pairs per held expert, summed over the
        MoE layers, then the pairs routed to experts held elsewhere; None
        for a model without MoE layers."""
        return None if self._expert_load is None else list(self._expert_load)

    def expert_imbalance(self) -> float:
        """Max over mean of the held experts' whole-run load (1 when
        balanced, 0 before any load): what a rebalancing of expert
        placement (paper §3) would act on."""
        held = (self._expert_load or [])[:-1]
        mean = sum(held) / len(held) if held else 0
        return max(held) / mean if mean else 0.0

    def absent_share(self) -> float:
        """Share of the whole run's routed pairs sent to experts held
        elsewhere (0 before any load)."""
        load = self._expert_load or []
        total = sum(load)
        return load[-1] / total if total else 0.0

    # -- assistant bridge (paper §3) -------------------------------------------
    def device_interference(self, k: int) -> list:
        """Per-device busy-time multipliers from serving load.

        Slot s maps to device ``s % k``; a device whose lanes are saturated
        gets its compute busy time inflated by ``1 + alpha``, and cache
        pressure inflates every device's memory busy time.
        """
        recent = self._recent()
        press = self.cache_pressure()
        per_dev = [0.0] * k
        if recent:
            for s in recent:
                slots_per_dev = max(1, -(-s.n_slots // k))
                hits = [0] * k
                for slot in s.active_slots:
                    hits[slot % k] += 1
                for d in range(k):
                    per_dev[d] += min(1.0, hits[d] / slots_per_dev)
            per_dev = [x / len(recent) for x in per_dev]
        return [{"compute": 1.0 + self.alpha * per_dev[d],
                 "memory": 1.0 + self.beta * press,
                 "network": 1.0} for d in range(k)]

    def assistant_callback(self, graph, cost_model) -> Callable:
        """A ``telemetry=`` callback for ``core.assistants.run_adaptation``:
        utilization under the measured serving interference, re-evaluated
        against each candidate assignment as the assistants migrate nodes."""
        from repro.core.assistants import simulate_utilization

        interference = self.device_interference(cost_model.k)

        def callback(assignment):
            return simulate_utilization(graph, assignment, cost_model,
                                        interference=interference)
        return callback


class FleetTelemetry:
    """Aggregated view over the per-replica ``ServeTelemetry`` feeds of a
    multi-replica ``serve.Router``.

    Each replica records its own steps; the fleet object never copies
    them — it holds ``(name, ServeTelemetry)`` references and reduces on
    demand.  Counters (tokens, starvation, preemptions) sum across
    replicas; ratios (occupancy, cache pressure, prefix hit rate)
    average over the replicas that have recorded anything, so an idle
    prefill replica does not dilute the fleet picture.  The §3 bridge is
    ``device_interference``: the element-wise mean of every replica's
    per-device multipliers, which the router feeds into one
    ``core.assistants.run_adaptation`` loop for the whole fleet.
    """

    def __init__(self):
        self.replicas: list[tuple[str, ServeTelemetry]] = []

    def attach(self, name: str, telemetry: ServeTelemetry) -> None:
        self.replicas.append((name, telemetry))

    def _live(self) -> list:
        return [t for _, t in self.replicas if t.steps]

    def total_tokens(self) -> int:
        return sum(t.total_tokens() for _, t in self.replicas)

    def total_preemptions(self) -> int:
        return sum(t.total_preemptions() for _, t in self.replicas)

    def decode_starvation(self) -> int:
        """Fleet-wide decode-lane-steps displaced by co-scheduled prefill
        work (prefill-only replicas contribute 0 by construction — their
        steps never carry decode lanes)."""
        return sum(t.decode_starvation() for _, t in self.replicas)

    def occupancy(self) -> float:
        live = self._live()
        return statistics.mean(t.occupancy() for t in live) if live else 0.0

    def cache_pressure(self) -> float:
        live = self._live()
        return statistics.mean(t.cache_pressure() for t in live) \
            if live else 0.0

    def prefix_hit_rate(self) -> float:
        looked = sum(t._prefix_lookup_tokens for _, t in self.replicas)
        hit = sum(t._prefix_hit_tokens for _, t in self.replicas)
        return hit / looked if looked else 0.0

    def max_concurrency(self) -> int:
        return sum(t.max_concurrency() for _, t in self.replicas)

    def summary(self) -> dict:
        """Per-replica snapshot keyed by replica name."""
        return {name: {"tokens": t.total_tokens(),
                       "occupancy": t.occupancy(),
                       "cache_pressure": t.cache_pressure(),
                       "decode_starvation": t.decode_starvation(),
                       "steps": len(t.steps)}
                for name, t in self.replicas}

    # -- assistant bridge (paper §3, fleet level) ------------------------------
    def device_interference(self, k: int) -> list:
        """Element-wise mean of every replica's per-device interference:
        the fleet's measured serving load on a shared k-device mesh."""
        live = self._live()
        if not live:
            return [{"compute": 1.0, "memory": 1.0, "network": 1.0}
                    for _ in range(k)]
        per = [t.device_interference(k) for t in live]
        out = []
        for d in range(k):
            out.append({res: statistics.mean(p[d][res] for p in per)
                        for res in ("compute", "memory", "network")})
        return out

    def assistant_callback(self, graph, cost_model) -> Callable:
        """``telemetry=`` feed for one fleet-level ``run_adaptation``."""
        from repro.core.assistants import simulate_utilization

        interference = self.device_interference(cost_model.k)

        def callback(assignment):
            return simulate_utilization(graph, assignment, cost_model,
                                        interference=interference)
        return callback
