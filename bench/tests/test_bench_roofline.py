"""The rooflines count the work a step requires, whatever implements
it: padding (the capacity factor) and the cache buffer's length
(``max_len``) do not change them; live tokens and live context do. A
device kind missing from the peaks table is an error."""
import copy

import pytest

from bench import model as bmodel
from bench.roofline import decode_step, experts_reached, gmm, least_time
from bench.roofline import peaks


@pytest.fixture(scope="module")
def moe():
    return bmodel.load("deepseek-moe-16b")


@pytest.fixture(scope="module")
def dense():
    return bmodel.load("internlm2-1.8b")


def _vary(conf, **engine):
    c = copy.deepcopy(conf)
    c["engine"].update(engine)
    return c


@pytest.mark.parametrize("cf", [1.0, 1.5, 11.0])
def test_gmm_work_ignores_capacity(moe, cf):
    c = copy.deepcopy(moe)
    c["program"]["set"]["moe.capacity_factor"] = cf
    assert gmm.work(c, 32) == gmm.work(moe, 32)


def test_gmm_work_follows_live_tokens(moe):
    f1, b1 = gmm.work(moe, 1)
    f32, b32 = gmm.work(moe, 32)
    assert f32 == pytest.approx(32 * f1)
    d, f = moe["hidden_size"], moe["moe_intermediate_size"]
    # one token reaches its 6 experts' weights; many reach all 64
    assert b1 == pytest.approx(6 * 3 * d * f * 2 + 6 * d * 6)
    assert gmm.work(moe, 10_000)[1] == pytest.approx(
        64 * 3 * d * f * 2 + 60_000 * d * 6, rel=1e-6)
    assert experts_reached(0, 64, 6) == 0.0


@pytest.mark.parametrize("max_len", [1024, 2048, 8192])
def test_decode_work_ignores_the_cache_buffer(dense, max_len):
    c = _vary(dense, max_len=max_len, max_batch=64)
    assert decode_step.work(c, 9, 9 * 700) == \
        decode_step.work(dense, 9, 9 * 700)


def test_decode_work_follows_live_context(dense):
    f0, b0 = decode_step.work(dense, 8, 0)
    f1, b1 = decode_step.work(dense, 8, 8000)
    kv = decode_step.kv_bytes_per_token(dense)
    assert b1 - b0 == pytest.approx(8000 * kv)
    assert kv == 24 * 2 * 8 * 128 * 2
    assert f1 > f0
    assert decode_step.work(dense, 0, 0) == (0.0, 0.0)


def test_decode_bytes_of_a_dense_model_are_its_weights(dense):
    # internlm2-1.8b: 1.89 B params, of which the 0.19 B of the
    # embedding table are looked up a row at a time: 1.70 B read in bf16
    _, b = decode_step.work(dense, 1, 0)
    assert 3.3e9 < b < 3.5e9


def test_least_time_takes_the_binding_bound():
    peak = peaks("TPU v5 lite")
    assert least_time(197e12, 0, peak) == pytest.approx(1.0)
    assert least_time(0, 819e9, peak) == pytest.approx(1.0)


def test_a_device_missing_from_the_peaks_table_is_an_error():
    with pytest.raises(KeyError):
        peaks("TPU v99")


def test_no_tpu_means_no_run():
    from bench import run

    with pytest.raises(run.NoDevice):
        run.devices_or_raise(1)
