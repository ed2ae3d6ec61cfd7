"""The four module kill switches through one off/on cycle on a live
cluster: while a switch is off its module does no work and its status
document stays readable; switched back on, the same cluster's module
works again; and the module is left enabled, its default.
"""

import pytest

from foundationdb_tpu.core import deterministic
from foundationdb_tpu.server import consistencyscan, health
from foundationdb_tpu.server.cluster import Cluster
from foundationdb_tpu.utils import heatmap, timeseries
from tests.conftest import TEST_KNOBS


def _write(c):
    n = c.hot_ranges_status()["totals"]["write"]["charges"]
    c.database()[b"k%06d" % n] = b"v"


# module → (its driver's pump, the count of work it has done)
SWITCHES = {
    "health": (health, lambda c: c.prober.maybe_probe(),
               lambda c: c.prober.status()["probes"]),
    "timeseries": (timeseries, lambda c: c.history.maybe_collect(),
                   lambda c: c.history_status()["windows"]),
    "consistencyscan": (
        consistencyscan, lambda c: c.scanner.maybe_scan(),
        lambda c: c.consistency_scan_status()["batches"]),
    "heatmap": (heatmap, _write,
                lambda c: c.hot_ranges_status()["totals"]["write"]["charges"]),
}


@pytest.mark.parametrize("name", sorted(SWITCHES))
def test_kill_switch_off_then_on_again(name):
    module, pump, work = SWITCHES[name]
    c = Cluster(**dict(TEST_KNOBS, resolver_backend="cpu",
                       storage_sample_every=1))
    t = [1000.0]
    deterministic.set_clock(lambda: t[0])

    def drive():
        for _ in range(3):  # the first pump of an arm may only re-arm
            t[0] += 100.0  # past every cadence and its jitter
            pump(c)
        return work(c)

    try:
        c.database()[b"seed"] = b"0"
        assert module.enabled()
        on = drive()
        assert on > 0
        module.set_enabled(False)
        assert drive() == on  # no work, and the document still reads
        module.set_enabled(True)
        assert drive() > on
    finally:
        module.set_enabled(True)
        deterministic.registry().reset_clock()
        c.close()
    assert module.enabled()
