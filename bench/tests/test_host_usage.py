"""The readers of the host's ledger (PR 36: `cpu` on every span record,
`host.tick`, `host.usage`, a commit's detail spans), on a hand-made run
and log."""

import pytest

from bench import host_usage, span_stages
from bench import run as harness

RUN = {"ops": [], "t0": 100.0, "t1": 130.0, "window_s": 30.0,
       "counters": {}, "trace": {"busy_s": 0.1, "window_s": 5.0},
       "traced_ops": 4, "set_up_seconds": 1.0,
       "peaks": {"hbm_bytes_per_s": 819e9}}


def _rec(name, self_s, cpu, end=110.0, **more):
    return {"name": name, "start": end - self_s, "dur": self_s,
            "self": self_s, "trace_id": None, "nbytes": None, "cpu": cpu,
            "tid": 1, "parent": None, **more}


def _usage(start, process_s, user_s, system_s, minflt, threads):
    return _rec("host.usage", 0.0, 0.0, end=start, process_s=process_s,
                user_s=user_s, system_s=system_s, minflt=minflt, nvcsw=0,
                nivcsw=0, cpus=13, switch_interval_s=0.005, threads=threads)


def _tick(start, late):
    return _rec("host.tick", 0.0, 0.0, end=start, late=late)


LOG = [
    _usage(102.0, 50.0, 40.0, 10.0, 1000, [
        [11, "MainThread", 5.0], [12, "osd.0-shard0", 2.0],
        [13, "profiler-osd.0", 1.0], [14, "osd.0-hb", 0.5],
        [15, "msgr-osd.0-r0", 3.0], [16, "osd.1-tickets", 0.25],
        [17, "bench-loop-3", 1.0], [18, "msgr-osd.1-handshake", 0.75]]),
    _tick(102.01, 0.001), _tick(103.0, 0.004), _tick(104.0, 0.0),
    _tick(106.99, 0.007),
    _rec("ecbackend.write.stripe", 0.010, 0.004),
    _rec("ecbackend.write.stage", 0.002, 0.001),
    _rec("ecbackend.write.launch", 0.004, 0.002),
    _rec("ecbackend.write.txns", 0.008, 0.003),
    _rec("ecbackend.read.gather", 0.012, 0.002),
    _rec("ecbackend.write.fanout", 0.06, 0.001),     # nobody's cpu metric
    _rec("osd.store_lock.wait", 0.01, 0.0), _rec("store.apply", 0.02, 0.004),
    _rec("store.commit", 0.05, 0.016), _rec("store.read", 0.04, 0.008),
    _rec("store.commit.stage", 0.012, 0.004, detail=True, parent="store.commit"),
    _rec("store.commit.pwrite", 0.008, 0.002, detail=True, parent="store.commit"),
    _rec("store.commit.csum", 0.004, 0.004, detail=True, parent="store.commit"),
    _rec("store.commit.wal", 0.02, 0.006, detail=True, parent="store.commit"),
    _rec("xla.compile", 2.0, None, end=50.0),
    # 5 s later: 8 s of CPU, 6 user and 2 system, 20,000 faults; the
    # ticket thread is gone, ident 18 is a new handshake, a loop was born
    _usage(107.0, 58.0, 46.0, 12.0, 21000, [
        [11, "MainThread", 5.5], [12, "osd.0-shard0", 4.0],
        [13, "profiler-osd.0", 1.25], [14, "osd.0-hb", 0.65],
        [15, "msgr-osd.0-r0", 4.5], [17, "bench-loop-3", 1.5],
        [18, "msgr-osd.1-handshake", 0.125], [19, "bench-loop-4", 0.375]]),
]

WANT = {"host.cores_busy": 1.6, "host.cpu_ms_per_op": 2000.0,
        "host.gil_wait_ms": 3.0, "host.system_ms_per_op": 500.0,
        "host.planes_cpu_pct": 5.0,           # (0.25 + 0.15) of 8 s
        "ec.host_cpu_ms_per_op": 3.0, "store.apply_cpu_ms_per_op": 7.0,
        "store.commit_data_ms_per_op": 6.0, "store.commit_wal_ms_per_op": 5.0}


@pytest.fixture
def log(monkeypatch):
    from ceph_tpu.utils import tracing
    monkeypatch.setattr(tracing, "_LOG", list(LOG))


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_a_hand_made_log(name, log):
    reader = harness.load_module("layer_metrics", name)
    assert reader.compute(dict(RUN)) == pytest.approx(WANT[name])


def _without(log_records, what):
    if what == "usage pair":             # the probe's last record never came
        return log_records[:-1]
    if what == "ticks":
        return [r for r in log_records if r["name"] != "host.tick"]
    if what == "cpu":                    # the parent's records
        return [{k: v for k, v in r.items() if k != "cpu"}
                for r in log_records if not r["name"].startswith("host.")]
    if what == "commit parts":
        return [r for r in log_records if not r.get("detail")]
    return log_records


#: which readers each missing thing silences
SILENCED = {
    "usage pair": {"host.cores_busy", "host.cpu_ms_per_op",
                   "host.system_ms_per_op", "host.planes_cpu_pct"},
    "ticks": {"host.gil_wait_ms"},
    "cpu": {"host.cores_busy", "host.cpu_ms_per_op", "host.gil_wait_ms",
            "host.system_ms_per_op", "host.planes_cpu_pct",
            "ec.host_cpu_ms_per_op", "store.apply_cpu_ms_per_op"},
    "commit parts": {"store.commit_data_ms_per_op",
                     "store.commit_wal_ms_per_op"},
}


@pytest.mark.parametrize("name", sorted(WANT))
@pytest.mark.parametrize("lacks", ["trace", "traced_ops", "log", "program",
                                   *sorted(SILENCED)])
def test_reader_with_nothing_to_read_returns_nothing(name, lacks,
                                                     monkeypatch):
    from ceph_tpu.utils import tracing
    run = dict(RUN)
    monkeypatch.setattr(tracing, "_LOG", list(_without(LOG, lacks)))
    if lacks == "trace":
        run["trace"] = None
    elif lacks == "traced_ops":
        run["traced_ops"] = 0
    elif lacks == "log":
        monkeypatch.setattr(tracing, "_LOG", [])
    elif lacks == "program":    # a program that keeps no span log
        monkeypatch.setattr(span_stages, "tracing", lambda: None)
    got = harness.load_module("layer_metrics", name).compute(run)
    per_second = name in ("host.cores_busy", "host.gil_wait_ms",
                          "host.planes_cpu_pct")
    if lacks == "traced_ops" and per_second:
        assert got == pytest.approx(WANT[name])      # no op in it
    elif lacks in SILENCED and name not in SILENCED[lacks]:
        assert got == pytest.approx(WANT[name])
    else:
        assert got is None


def test_cpu_per_op_times_ops_per_second_is_cores_busy(log):
    run = dict(RUN)
    ledger = host_usage.usage(run)
    per_op = harness.load_module("layer_metrics",
                                 "host.cpu_ms_per_op").compute(run)
    cores = harness.load_module("layer_metrics",
                                "host.cores_busy").compute(run)
    ops_per_s = run["traced_ops"] / ledger["seconds"]
    assert per_op * ops_per_s == pytest.approx(cores * 1000.0)


def test_the_ledger_by_role_and_the_runtime_s_remainder(log):
    ledger = host_usage.usage(dict(RUN))
    assert ledger["seconds"] == pytest.approx(5.0)
    assert ledger["user_s"] == pytest.approx(6.0)
    assert ledger["system_s"] == pytest.approx(2.0)
    # gone at the last record: left out; an ident reused or a thread
    # born between the two: its whole reading
    assert ledger["cpu_s_by_role"] == pytest.approx({
        "MainThread": 0.5, "shard": 2.0, "profiler": 0.25, "hb": 0.15,
        "msgr-r": 1.5, "bench-loop": 0.5 + 0.375, "msgr-handshake": 0.125})
    assert ledger["native_cpu_s"] == pytest.approx(8.0 - 5.4)


def test_the_program_s_own_ledger_agrees(log):
    """`trace stop`'s `host` block and the benchmark's reader are two
    statements of one rule."""
    from ceph_tpu.utils import tracing
    theirs = tracing.host_usage(tracing.span_log())
    ours = host_usage.usage(dict(RUN))
    for key in ("seconds", "cpu_s", "user_s", "system_s", "minor_faults",
                "native_cpu_s"):
        assert theirs[key] == pytest.approx(ours[key]), key
    assert theirs["cpu_s_by_role"] == pytest.approx(ours["cpu_s_by_role"])
    assert theirs["late_mean_ms"] == pytest.approx(
        host_usage.late_ms(dict(RUN)))
    assert theirs["cores_busy"] == pytest.approx(WANT["host.cores_busy"])
    for name in ("osd.3-shard0", "msgr-mon.0-r0", "profiler-osd.11",
                 "osd.3-recover-build-5", "bench-loop-12", "MainThread"):
        assert host_usage.role_of(name) == tracing.thread_role(name)


@pytest.mark.parametrize("copy,original", [
    ("ec.host_cpu_ms_per_op", "ec.host_ms_per_op"),
    ("store.apply_cpu_ms_per_op", "store.apply_ms_per_op")])
def test_a_cpu_reader_sums_its_wall_reader_s_names(copy, original):
    assert harness.load_module("layer_metrics", copy).NAMES \
        == harness.load_module("layer_metrics", original).NAMES


def test_the_wall_readers_still_read_the_commit_whole(log):
    """The detail spans took nothing from `store.commit`'s self time."""
    got = harness.load_module("layer_metrics",
                              "store.apply_ms_per_op").compute(dict(RUN))
    assert got == pytest.approx(30.0)


def test_every_host_reader_is_in_the_manifest_and_nowhere_it_reads_nothing():
    manifest = harness.load_json(harness.ROOT, "BENCHMARK.json")
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert set(WANT) <= set(entries)
    assert [m["name"] for m in manifest["per_layer"]][-9:] == [
        "host.cores_busy", "host.cpu_ms_per_op", "host.gil_wait_ms",
        "host.system_ms_per_op", "host.planes_cpu_pct",
        "ec.host_cpu_ms_per_op", "store.apply_cpu_ms_per_op",
        "store.commit_data_ms_per_op", "store.commit_wal_ms_per_op"]
    served = {c["name"] for c in manifest["workloads"]} \
        - {"ecbench_encode_4m_b32"}
    for name in WANT:
        cells = set(entries[name]["workloads"])
        assert cells <= served
        if name.startswith("host.") or name == "store.apply_cpu_ms_per_op":
            assert cells == served
    assert entries["ec.host_cpu_ms_per_op"]["workloads"] \
        == entries["ec.host_ms_per_op"]["workloads"]
