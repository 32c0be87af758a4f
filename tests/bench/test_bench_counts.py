"""The FLOP and byte counts against the hand numbers of the cells'
configurations, and the configurations against the program's own."""

import dataclasses

import pytest

from bench import spec, window
from bench import engine_adapter as ad

from repro import configs


def _conf(name):
    return spec.load_json(spec.BENCH_DIR / "configs" / f"{name}.json")


def test_minicpm_sizes():
    conf, m = _conf("minicpm-2b"), spec.load_model("dense")
    assert m.param_count(conf) == 2_724_880_896          # 2.725 B
    assert m.kv_bytes_per_token(conf) == 368_640
    # one decode token at position 0: 2 N FLOPs plus attention over itself
    assert m.token_flops(conf, 0, True) == pytest.approx(
        2 * (m.param_count(conf) - 2304) + 4 * 40 * 36 * 64)


def test_decode_cost_counts_weights_once_and_each_lane():
    conf, m = _conf("minicpm-2b"), spec.load_model("dense")
    w = m.param_count(conf) * 2
    f1, b1 = m.decode_cost(conf, [1023])
    f2, b2 = m.decode_cost(conf, [1023, 99])
    assert b1 == w + 1024 * 368_640
    assert b2 - b1 == 100 * 368_640
    assert f2 - f1 == pytest.approx(m.token_flops(conf, 99, True))


@pytest.mark.parametrize("name,model", [("minicpm-2b", "dense")])
def test_chunk_flops_is_the_sum_over_its_positions(name, model):
    conf, m = _conf(name), spec.load_model(model)
    L = 700
    for start, valid in [(0, 256), (512, 188)]:
        want = sum(m.token_flops(conf, p, p == L - 1)
                   for p in range(start, start + valid))
        assert window.chunk_flops(m, conf, start, valid, L) == \
            pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("name,model", [("minicpm-2b", "dense")])
def test_config_file_is_the_program_config(name, model):
    """The program's own config, with the published norm epsilon (1e-5)
    in place of its default."""
    conf = _conf(name)
    assert conf["rms_norm_eps"] == 1e-5 and conf["reduced"] == []
    assert ad.program_config(spec.load_model(model), conf) == \
        dataclasses.replace(configs.get(name), norm_eps=1e-5)
    assert conf["name"] == name
