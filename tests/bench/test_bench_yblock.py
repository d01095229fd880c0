"""The 256^2 cross-section cell: its configuration is found by name, and
its y-blocked kernel's roofline share reads only kernels named for a
y-blocked window."""
from bench_helpers import MS, ROOT, ctx_of, hand_trace, recorded

from bench.lib import registry, work

CONFIG = "ludwig_spinodal_128x256x256"
METRIC = "yblock_kernel_roofline_share"


def test_config_loads_by_name():
    cfg = registry.load_config(ROOT, CONFIG)
    assert cfg["name"] == CONFIG and cfg["grid"] == [128, 256, 256]
    assert (cfg["executor"], cfg["fused"], cfg["mesh"]) == ("pallas_windowed", "one_launch", None)
    assert cfg["reduced"] == ["dtype", "grid"] and set(cfg["reduced"]) <= set(cfg["assumed"])
    ref = registry.load_reference(cfg["reference"])
    assert all(hasattr(ref, a) for a in ("WEIGHTS", "run", "observables"))


def test_reader_reads_only_y_blocked_kernels():
    read = registry.load_metric(ROOT, METRIC).read
    assert read(ctx_of(hand_trace())) is None
    assert read(recorded("spinodal128.stats100")) is None
    t = hand_trace()
    for dev, ops in t.devices.items():
        t.devices[dev] = [(n.replace("_p1_", "_p1_yb128_"), s, d) for n, s, d in ops]
    sites = 128 * 256 * 256
    least, _ = work.least_step_seconds(sites, "TPU v5 lite")
    # kernels of 50 and 40 ms on the two devices, 9 kernel steps
    want = 100.0 * least * 9 / (45 * MS / 1e9)
    got = read(ctx_of(t, sites=sites))
    assert abs(got - want) < 1e-9 * want
