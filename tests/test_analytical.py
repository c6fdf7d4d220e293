"""Tests for the paper's analytical model (Eqs. 5-6, 17-19, 22-31) against
both measured op counts (jaxpr instrumentation) and the paper's reported
numbers (Tables 1-2 resource columns)."""
import jax.numpy as jnp
import jax
import pytest

from repro.core import analytical as an
from repro.core import fip, workloads


def test_eq5_eq6_counts_match_instrumented_jaxpr():
    """Eq. (5)/(6) multiplication counts == multiplies actually present in the
    lowered FIP computation (measured, not assumed)."""
    m, k, n = 8, 16, 4
    a = jnp.zeros((m, k))
    b = jnp.zeros((k, n))
    measured = fip.count_multiplies_in_jaxpr(lambda a, b: fip.fip_matmul(a, b), a, b)
    assert measured == an.fip_mults(m, k, n)
    measured_base = fip.count_multiplies_in_jaxpr(lambda a, b: a @ b, a, b)
    assert measured_base == an.baseline_mults(m, k, n)


def test_mult_halving_ratio():
    """The headline claim: FIP mults -> ~half of baseline for large MNK."""
    m = k = n = 512
    ratio = an.fip_mults(m, k, n) / an.baseline_mults(m, k, n)
    assert 0.5 <= ratio < 0.51


def test_register_model_fig2():
    rows = an.fig2_table(x=64, d=1)
    by_w = {r["w"]: r for r in rows}
    # Eq. 17/18/19 spot values at w=8, X=64 (clog2=6):
    assert by_w[8]["fip"] == 6 * 8 + 6 + 1
    assert by_w[8]["fip_extra"] == 8 * 8 + 2 + 6 + 1
    assert by_w[8]["ffip"] == 6 * 8 + 2 + 6 + 3
    # FFIP < FIP+extra for all w >= 2 (Fig. 2's message)
    for r in rows:
        assert r["ffip"] < r["fip_extra"]


def test_mxu_resources_match_table1():
    """FFIP 64x64 on Arria 10: 1072 DSPs (Table 1) — our resource model."""
    cfg = an.MxuConfig(x=64, y=64, algo="ffip", w_bits=8)
    assert an.mxu_dsps(cfg) == 1072
    base = an.MxuConfig(x=64, y=64, algo="baseline", w_bits=8)
    assert an.mxu_dsps(base) == (64 * 64 + 64 + 1) // 2  # 2080
    # near-2x DSP reduction (the Fig. 9 claim)
    assert an.mxu_dsps(base) / an.mxu_dsps(cfg) > 1.9


def test_roofs():
    ffip = an.MxuConfig(x=64, y=64, algo="ffip", w_bits=8)
    base = an.MxuConfig(x=64, y=64, algo="baseline", w_bits=8)
    assert an.ops_per_mult_per_cycle_roof(ffip) == 4.0   # Eq. (30)
    assert an.ops_per_mult_per_cycle_roof(base) == 2.0   # Eq. (26)


def test_fmax_table_values():
    """Frequency constants reproduce Table 1/2 'Ours' rows at 64x64."""
    assert an.mxu_fmax_mhz(an.MxuConfig(64, 64, "ffip", 8)) == pytest.approx(388, abs=2)
    assert an.mxu_fmax_mhz(an.MxuConfig(64, 64, "ffip", 16)) == pytest.approx(346, abs=2)


def test_fip_fmax_30pct_below_baseline():
    f_fip = an.mxu_fmax_mhz(an.MxuConfig(64, 64, "fip", 8))
    f_base = an.mxu_fmax_mhz(an.MxuConfig(64, 64, "baseline", 8))
    assert 0.62 <= f_fip / f_base <= 0.78


def test_workload_gops_sane():
    """Model op counts match literature (AlexNet ~1.45 GOP, ResNet-50 ~7.7,
    VGG16 ~30.9, ResNet-152 ~22.6)."""
    assert workloads.model_gops("alexnet") == pytest.approx(1.45, rel=0.15)
    assert workloads.model_gops("resnet50") == pytest.approx(7.7, rel=0.15)
    assert workloads.model_gops("vgg16") == pytest.approx(30.9, rel=0.05)
    assert workloads.model_gops("resnet152") == pytest.approx(22.6, rel=0.15)


def test_cycle_model_utilization_bounds():
    cfg = an.MxuConfig(x=64, y=64, algo="ffip", w_bits=8)
    perf = an.model_performance(workloads.resnet50(batch=8), cfg)
    assert 0.3 < perf["utilization"] <= 1.0
    assert perf["gops"] <= perf["roof_gops"] * 1.001


def test_ffip_table1_gops_reproduction():
    """Reproduce Table 1 'Ours FFIP 64x64' GOPS within 15%.

    The paper's own estimator claims 1% vs silicon; ours re-derives the cycle
    model from the architecture description alone (their exact layer-IO
    pipelining depth is not published), so we accept a wider band. Operating
    points: streaming batch=2 for ResNets, batch=32 for AlexNet (fc weight
    loads amortize over a batch; the paper's AlexNet number implies the same).
    """
    cfg = an.MxuConfig(x=64, y=64, algo="ffip", w_bits=8)
    for model, batch, paper in [("resnet50", 2, 2529), ("resnet101", 2, 2752),
                                ("resnet152", 2, 2838), ("alexnet", 32, 2277)]:
        perf = an.model_performance(workloads.MODELS[model](batch), cfg)
        assert perf["gops"] == pytest.approx(paper, rel=0.15), (model, perf["gops"])


def test_ffip_table2_gops_reproduction_16bit():
    """Table 2 (16-bit FFIP 64x64): GOPS scale by fmax ratio, util unchanged —
    exactly the paper's behaviour (2258/2529 == 346/388)."""
    cfg = an.MxuConfig(x=64, y=64, algo="ffip", w_bits=16)
    for model, batch, paper in [("resnet50", 2, 2258), ("resnet152", 2, 2534),
                                ("alexnet", 32, 1974)]:
        perf = an.model_performance(workloads.MODELS[model](batch), cfg)
        assert perf["gops"] == pytest.approx(paper, rel=0.15), (model, perf["gops"])


def test_ops_per_mult_per_cycle_beats_baseline_2x():
    """The paper's Table 1 headline: FFIP reaches ~3.0-3.4 ops/mult/cycle,
    above the baseline theoretical max of 2 (Eq. 26)."""
    cfg = an.MxuConfig(x=64, y=64, algo="ffip", w_bits=8)
    perf = an.model_performance(workloads.resnet152(batch=2), cfg)
    assert perf["ops_per_mult_per_cycle"] > 2.0
    assert perf["ops_per_mult_per_cycle"] == pytest.approx(3.414, rel=0.15)


def test_tpu_roofline_terms():
    t = an.tpu_roofline_terms(1e15, 1e12, 1e11, 256)
    assert t["bottleneck"] == "compute_s"
    assert t["compute_s"] == pytest.approx(1e15 / (256 * 197e12))


def test_chip_peaks_keyed_by_device_kind():
    peaks = an.chip_peaks("TPU v5 lite")
    assert (peaks.bf16_flops, peaks.int8_ops, peaks.hbm_bw) == (197e12, 393e12, 819e9)
    with pytest.raises(ValueError, match="no published peaks"):
        an.chip_peaks("cpu")
    with pytest.raises(ValueError):
        an.tpu_roofline_terms(1e15, 1e12, 1e11, 4, device_kind="TPU v9")


def test_collective_term_uses_per_chip_interconnect():
    """The collective term divides by one chip's whole interconnect, 1,600
    Gbit/s = 200 GB/s on v5e (not one 50 GB/s link): 4e11 bytes over 4
    chips take 0.5 s."""
    from repro.launch import roofline
    t = an.tpu_roofline_terms(0.0, 0.0, 4e11, 4)
    assert t["collective_s"] == pytest.approx(0.5)
    stats = roofline.CollectiveStats(counts={"all-reduce": 1},
                                     bytes_by_kind={"all-reduce": 4e11})
    r = roofline.roofline_report(0.0, 0.0, stats, 4)
    assert r["collective_s"] == pytest.approx(0.5)
