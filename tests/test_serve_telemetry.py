"""Serve telemetry and its feed into the §3 scheduling assistants:
occupancy/pressure accounting, the per-device interference mapping, and
adaptation convergence (no oscillation, relocatable-only migrations)."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get
from repro.core import (AssistantConfig, CostModel, Graph, Node,
                        homogeneous_devices, run_adaptation)
from repro.models import lm
from repro.runtime import ServeTelemetry
from repro.serve import ContinuousEngine


def _record(tel, step, active, n_slots=4, used=8, total=16):
    tel.record_step(step=step, seconds=1e-3, active_slots=active,
                    n_slots=n_slots, blocks_in_use=used, n_blocks=total,
                    new_tokens=len(active))


def test_occupancy_and_pressure_aggregates():
    tel = ServeTelemetry(window=10)
    assert tel.occupancy() == 0.0 and tel.cache_pressure() == 0.0
    for i in range(10):
        _record(tel, i, active=(0, 1), used=4, total=16)
    assert tel.occupancy() == pytest.approx(0.5)
    assert tel.cache_pressure() == pytest.approx(0.25)
    assert tel.max_concurrency() == 2
    assert tel.total_tokens() == 20


def test_occupancy_guarded_when_every_step_has_zero_slots():
    """Regression: ``occupancy()`` fed ``statistics.mean`` an empty list
    (``StatisticsError``) when every recent step recorded ``n_slots ==
    0`` — the filter ran per-step but nothing guarded the empty result,
    unlike ``cache_pressure``'s same-shaped guard."""
    tel = ServeTelemetry(window=4)
    for i in range(6):
        _record(tel, i, active=(), n_slots=0)
    assert tel.occupancy() == 0.0
    # a mixed window still averages only the slot-bearing steps
    _record(tel, 6, active=(0,), n_slots=2)
    assert tel.occupancy() == pytest.approx(0.5)


def test_decode_starvation_counts_lanes_sharing_prefill_steps():
    """The router benchmark's gated quantity: a running total of decode
    lanes resident on steps that also carried prefill work — it must
    survive history-window eviction and reset with ``reset()``."""
    tel = ServeTelemetry(window=2)
    tel.record_step(step=0, seconds=1e-3, active_slots=(0, 1), n_slots=4,
                    blocks_in_use=1, n_blocks=16, prefills=1)
    tel.record_step(step=1, seconds=1e-3, active_slots=(0, 1, 2), n_slots=4,
                    blocks_in_use=1, n_blocks=16, prefill_chunks=2)
    tel.record_step(step=2, seconds=1e-3, active_slots=(0,), n_slots=4,
                    blocks_in_use=1, n_blocks=16)       # pure decode: free
    tel.record_step(step=3, seconds=1e-3, active_slots=(), n_slots=4,
                    blocks_in_use=1, n_blocks=16, prefills=1)  # no lanes
    assert tel.decode_starvation() == 5        # 2 + 3, despite window=2
    tel.reset()
    assert tel.decode_starvation() == 0


def test_device_interference_maps_slots_round_robin():
    tel = ServeTelemetry(window=10, alpha=1.0, beta=1.0)
    # slots 0 and 2 always active -> devices 0 and 2 loaded (k=4, 1 slot/dev)
    for i in range(10):
        _record(tel, i, active=(0, 2), used=16, total=16)
    inter = tel.device_interference(4)
    assert len(inter) == 4
    assert inter[0]["compute"] == pytest.approx(2.0)
    assert inter[1]["compute"] == pytest.approx(1.0)
    assert inter[2]["compute"] == pytest.approx(2.0)
    assert inter[3]["compute"] == pytest.approx(1.0)
    for d in range(4):
        assert inter[d]["memory"] == pytest.approx(2.0)   # pressure = 1.0
        assert inter[d]["network"] == 1.0


def _graph(n=24, pinned=("n0", "n5", "n10")):
    g = Graph()
    for i in range(n):
        g.add_node(Node(id=f"n{i}", kind="op", flops=1e12, bytes_accessed=1e3,
                        relocatable=f"n{i}" not in pinned))
    for i in range(n - 1):
        g.add_edge(f"n{i}", f"n{i+1}", bytes=1.0)
    return g


def _skewed_telemetry(k=4, n_slots=4):
    """Device 0's lane saturated for the whole window -> compute hotspot."""
    tel = ServeTelemetry(alpha=1.0, beta=0.5)
    for i in range(50):
        _record(tel, i, active=(0,), n_slots=n_slots, used=12, total=16)
    return tel


def test_adaptation_with_serve_callback_converges_without_oscillation():
    g = _graph()
    cm = CostModel(homogeneous_devices(4))
    cm.tag_nodes(g)
    a = {f"n{i}": i % 4 for i in range(24)}            # balanced plan
    tel = _skewed_telemetry()
    cb = tel.assistant_callback(g, cm)
    trace = run_adaptation(
        g, dict(a), cm, telemetry=cb,
        interference=tel.device_interference(cm.k),
        config=AssistantConfig(theta=0.9, gamma=0.8), max_steps=50)
    # serving interference on device 0 must trigger at least one migration
    n_migs = sum(len(m) for m in trace.migrations)
    assert n_migs >= 1
    # convergence: the protocol settles — no migrations in the last 10 cycles
    assert all(len(m) == 0 for m in trace.migrations[-10:])
    # no oscillation: no node bounces back and forth more than the hysteresis
    # allows (<= 2 moves per node over 50 cycles)
    per_node: dict = {}
    for migs in trace.migrations:
        for m in migs:
            per_node[m.node] = per_node.get(m.node, 0) + 1
    assert all(c <= 2 for c in per_node.values()), per_node
    # adapted placement is no slower than the starting one
    assert trace.step_times[-1] <= trace.step_times[0] * 1.001


def test_adaptation_never_migrates_non_relocatable_nodes():
    pinned = ("n0", "n5", "n10")
    g = _graph(pinned=pinned)
    cm = CostModel(homogeneous_devices(4))
    cm.tag_nodes(g)
    # pathological start: everything (pinned included) on device 0
    a = {f"n{i}": 0 for i in range(24)}
    tel = _skewed_telemetry()
    trace = run_adaptation(
        g, dict(a), cm, telemetry=tel.assistant_callback(g, cm),
        config=AssistantConfig(theta=0.9, gamma=0.8), max_steps=50)
    moved = {m.node for migs in trace.migrations for m in migs}
    assert moved, "expected migrations off the overloaded device"
    assert moved.isdisjoint(pinned)


def test_engine_telemetry_feeds_assistants_end_to_end():
    """The full loop: serve a trace with the continuous engine, then hand its
    measured telemetry to the assistants on a compiler plan of the same
    model."""
    from repro.core import plan_model
    from repro.models.config import SHAPES

    cfg = get("paper-mlp").reduced()
    key = jax.random.PRNGKey(0)
    params = lm.init_params(cfg, key, jnp.float32)
    eng = ContinuousEngine(cfg, params, kv_len=32, n_slots=2)
    for i in range(3):
        prompt = jax.random.randint(jax.random.fold_in(key, i), (6,), 0,
                                    cfg.vocab_size)
        eng.submit(prompt, max_new_tokens=4, rid=i, arrival=i)
    eng.run()
    assert eng.telemetry.steps, "engine recorded no telemetry"

    plan = plan_model(cfg, SHAPES["decode_32k"], k=4)
    cb = eng.telemetry.assistant_callback(plan.graph, plan.cost_model)
    utils = cb(plan.assignment)
    assert len(utils) == 4
    assert all(set(u) == {"compute", "memory", "network"} for u in utils)
    assert all(0.0 <= v <= 1.0 for u in utils for v in u.values())
    trace = run_adaptation(plan.graph, dict(plan.assignment), plan.cost_model,
                           telemetry=cb, max_steps=20)
    assert trace.step_times[-1] <= trace.step_times[0] * 1.001
