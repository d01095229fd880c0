"""The work arithmetic that every roofline share and `step_mfu` read."""
import pytest

from bench_helpers import ROOT, ctx_of, hand_trace, recorded

from bench.lib import registry, work


def test_bytes_per_site_is_f_and_g_read_and_written_once():
    assert work.BYTES_PER_SITE == 304 == 2 * 19 * 4 * 2


def test_flops_per_site_is_counted_from_the_reference():
    assert work.count_reference_flops() == work.FLOPS_PER_SITE


def test_bytes_bound_the_step_on_v5e():
    t, bound = work.least_step_seconds(128 ** 3, "TPU v5 lite")
    assert bound == "bytes"
    assert t == pytest.approx(128 ** 3 * 304 / 819e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        work.least_step_seconds(128 ** 3, "TPU v99")


@pytest.mark.parametrize("metric", ["step_mfu", "kernel_roofline_share"])
def test_share_reaches_100_only_at_the_floor(metric):
    """A window (or kernel time) exactly as long as the least time of its
    steps reads 100%; any longer one reads less."""
    t = hand_trace()
    steps = 20
    least, _ = work.least_step_seconds(128 ** 3, "TPU v5 lite")
    ns = int(round(least * steps * 1e9))
    t.spans = [("bench.window", 0, ns)]
    t.devices = {"/device:TPU:0": [("tdp_windowed_fused_p1_soa", 0, ns)]}
    read = registry.load_metric(ROOT, metric).read
    at_floor = read(ctx_of(t, steps=steps, kernel_steps=steps))
    assert at_floor == pytest.approx(100.0, rel=1e-6)
    t.spans = [("bench.window", 0, 2 * ns)]
    t.devices = {"/device:TPU:0": [("tdp_windowed_fused_p1_soa", 0, 2 * ns)]}
    assert read(ctx_of(t, steps=steps, kernel_steps=steps)) == pytest.approx(50.0, rel=1e-6)


def test_shares_at_the_measured_rate_stay_under_100():
    """At the chip's measured rate (the recorded trace) both shares are far
    below 100%."""
    ctx = recorded("spinodal128.stats100")
    for metric in ("step_mfu", "kernel_roofline_share"):
        v = registry.load_metric(ROOT, metric).read(ctx)
        assert 0 < v < 100, (metric, v)
