"""The readers of the program's span log, on a hand-made run and log."""

import pytest

from bench import run as harness
from bench import span_stages

RUN = {"ops": [], "t0": 100.0, "t1": 130.0, "window_s": 30.0,
       "counters": {}, "trace": {"busy_s": 4.0, "window_s": 5.0},
       "traced_ops": 4, "set_up_seconds": 1.0,
       "peaks": {"hbm_bytes_per_s": 819e9}}


def _rec(name, self_s, end=110.0):
    return {"name": name, "start": end - self_s, "dur": self_s,
            "self": self_s, "trace_id": None, "nbytes": None}


LOG = [
    _rec("msgr.seal", 0.008), _rec("msgr.open", 0.004),
    _rec("osd.queue", 1.6), _rec("osd.pg_lock.wait", 0.4),
    _rec("ecbackend.write.stripe", 0.010), _rec("ecbackend.write.stage", 0.002),
    _rec("ecbackend.write.launch", 0.004), _rec("ecbackend.write.txns", 0.008),
    _rec("ecbackend.read.gather", 0.012),
    _rec("ecbackend.read.verify.stage", 0.002),
    _rec("ecbackend.read.verify.launch", 0.001),
    _rec("ecbackend.read.decode", 0.0005), _rec("ecbackend.read.unstripe", 0.0005),
    _rec("ecbackend.read.verify", 0.5),          # its self: nobody's metric
    _rec("ecbackend.write.fetch", 0.3), _rec("ecbackend.read.verify.fetch", 0.1),
    _rec("ecbackend.write.fanout", 0.06),
    _rec("osd.store_lock.wait", 0.01), _rec("store.apply", 0.02),
    _rec("store.commit", 0.05), _rec("store.read", 0.04),
    _rec("xla.compile", 2.0, end=50.0),          # set-up: before the window
    _rec("xla.compile", 0.7, end=101.0), _rec("xla.compile", 0.2, end=129.0),
    _rec("xla.compile", 0.1, end=131.0),         # after the close
]

WANT = {"msgr.crypto_ms_per_op": 3.0, "osd.queue_wait_ms_per_op": 500.0,
        "ec.host_ms_per_op": 10.0, "ec.device_wait_ms_per_op": 100.0,
        "ec.fanout_wait_ms_per_op": 15.0, "store.apply_ms_per_op": 30.0,
        "xla.compiles_in_window": 2}


@pytest.fixture
def log(monkeypatch):
    from ceph_tpu.utils import tracing
    monkeypatch.setattr(tracing, "_LOG", list(LOG))


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_a_hand_made_log(name, log):
    reader = harness.load_module("layer_metrics", name)
    assert reader.compute(dict(RUN)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
@pytest.mark.parametrize("lacks", ["trace", "traced_ops", "log", "program"])
def test_reader_with_nothing_to_read_returns_nothing(name, lacks, log,
                                                     monkeypatch):
    run = dict(RUN)
    if lacks == "trace":
        run["trace"] = None
    elif lacks == "traced_ops":
        run["traced_ops"] = 0
    elif lacks == "log":
        from ceph_tpu.utils import tracing
        monkeypatch.setattr(tracing, "_LOG", [])
    else:                   # a program that keeps no span log: the parent
        monkeypatch.setattr(span_stages, "tracing", lambda: None)
    got = harness.load_module("layer_metrics", name).compute(run)
    if name == "xla.compiles_in_window" and lacks in ("traced_ops", "log"):
        assert got == (2 if lacks == "traced_ops" else 0)   # a count is a count
    else:
        assert got is None


def test_every_reader_of_the_log_is_in_the_manifest():
    manifest = harness.load_json(harness.ROOT, "BENCHMARK.json")
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert set(WANT) <= set(entries)
    write_only = entries["ec.fanout_wait_ms_per_op"]["workloads"]
    assert write_only == ["rados_write_4m_t16"]
