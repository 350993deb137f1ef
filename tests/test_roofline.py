"""Unit tests for the roofline computation (deliverable g math)."""

import pytest

from repro import configs
from repro.core import roofline
from repro.distributed.hlo import HloCost
from repro.hardware import CHIPS_BY_KIND, SINGLE_POD, TPU_V5E


def _mk(cost_kw, shape_kind="train", arch="glm4-9b", gb=256, seq=4096):
    cfg = configs.get_config(arch)
    cost = HloCost(**cost_kw)
    return roofline.compute(
        cfg=cfg, arch=arch, shape_name="x", shape_kind=shape_kind,
        seq_len=seq, global_batch=gb, system=SINGLE_POD, strategy="tp_dp",
        cost=cost, hbm_required=8e9, state_bytes=0.0,
    )


@pytest.mark.parametrize("kind,chip", [("TPU v5 lite", TPU_V5E), ("cpu", None),
                                       ("TPU v4", None)])
def test_peaks_are_looked_up_by_device_kind(kind, chip):
    assert CHIPS_BY_KIND.get(kind) is chip


def test_terms_and_dominant():
    r = _mk({"flops": 197e12, "bytes": 819e9 * 2, "collective_bytes": 50e9 / 2})
    assert r.t_compute == pytest.approx(1.0)
    assert r.t_memory == pytest.approx(2.0)
    assert r.t_collective == pytest.approx(0.5)
    assert r.dominant == "memory"
    assert r.step_time == pytest.approx(2.0)
    assert r.fits  # 8 GB < 16 GB


def test_model_flops_train_vs_decode():
    n_tok_train = 256 * 4096
    mf_train = roofline.model_flops(configs.get_config("glm4-9b"), "train", n_tok_train)
    mf_dec = roofline.model_flops(configs.get_config("glm4-9b"), "decode", 128)
    # train = 6ND, decode = 2ND with D=tokens.
    assert mf_train / n_tok_train == pytest.approx(3 * mf_dec / 128)


def test_moe_active_params_reduce_model_flops():
    dense_like = roofline.model_flops(configs.get_config("deepseek-v3-671b"), "train", 1000)
    from repro.models import params as P

    n_act = P.non_embedding_param_count(configs.get_config("deepseek-v3-671b"), active_only=True)
    n_tot = P.non_embedding_param_count(configs.get_config("deepseek-v3-671b"))
    assert dense_like == pytest.approx(6 * n_act * 1000)
    assert n_act < 0.1 * n_tot  # top-8 of 256 experts


def test_roofline_fraction_bounds():
    # Perfectly balanced, all-useful cell: fraction near its definition cap.
    cfg = configs.get_config("glm4-9b")
    from repro.models import params as P

    n = P.non_embedding_param_count(cfg, active_only=True)
    ntok = 256 * 4096
    useful_flops_per_dev = 6.0 * n * ntok / 256
    r = _mk({"flops": useful_flops_per_dev, "bytes": 1e9, "collective_bytes": 0.0})
    assert r.useful_ratio == pytest.approx(1.0, rel=1e-6)
    assert 0 < r.roofline_fraction <= 1.000001


def test_metrics_keys_cover_readiness_contract():
    from repro.core.readiness import INSTRUMENTED_METRICS

    r = _mk({"flops": 1e12, "bytes": 1e12, "collective_bytes": 1e9})
    m = r.metrics()
    for k in INSTRUMENTED_METRICS:
        assert k in m, k
