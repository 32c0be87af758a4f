"""``correct`` holds for the program and fails for its control and for
each fault the cells can have, at sizes a test run holds.

The tiny configurations' limit (0.02) sits between the readings these
sizes give on the CPU: the program's widest gap was at most 0.0065 over
seeds 1-4, the float8 control's at least 0.059."""

import jax.numpy as jnp
import pytest

from bench import calibrate
from bench import engine_adapter as ad
from bench_helpers import run, tiny_cell

FAMILIES = ["tiny-dense"]


@pytest.mark.parametrize("config", FAMILIES)
def test_program_is_correct_open_and_traced(config):
    res = run(tiny_cell(config, "tiny-open"), trace=True)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["failed"] == 0 and res["attempted"] > 0


@pytest.mark.parametrize("config", FAMILIES)
def test_control_fails_the_limit(config):
    cell = tiny_cell(config, "tiny-closed")
    limit = cell.config["correct"]["max_logit_gap"]
    rows = calibrate.readings(cell, [1, 2, 3], {1, 2, 3}, 2.0)
    for r in rows:
        assert r["tokens"] > 0 and r["in_vocab"]
        assert r["program_gap"] <= limit
        assert r["control_gap"] > limit


def _break(fault: str, vocab: int):
    def hook(eng):
        step = eng._decode_p

        def broken(params, caches, toks, pos, tables, active,
                   sample_args=None):
            new_toks, new_caches = step(params, caches, toks, pos, tables,
                                        active, sample_args)
            if fault == "token altered":
                new_toks = (new_toks + 1) % vocab
            elif fault == "state unchanged":
                new_caches = caches
            elif fault == "half the lanes left out":
                half = new_toks.shape[0] // 2
                new_toks = new_toks.at[half:].set(
                    jnp.broadcast_to(new_toks[0], new_toks[half:].shape))
            return new_toks, new_caches

        eng._decode_p = broken
        return eng
    return hook


@pytest.mark.parametrize("config", FAMILIES)
@pytest.mark.parametrize("fault", ["token altered", "state unchanged",
                                   "half the lanes left out"])
def test_faults_make_correct_false(monkeypatch, config, fault):
    cell = tiny_cell(config, "tiny-closed")
    hook = _break(fault, cell.config["vocab_size"])
    build = ad.build_engine
    monkeypatch.setattr(ad, "build_engine",
                        lambda *a, **k: hook(build(*a, **k)))
    res = run(cell)
    assert not res["correct"], res["checks"]
    assert res["checks"]["max_logit_gap"]["value"] > \
        cell.config["correct"]["max_logit_gap"]
