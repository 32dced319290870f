"""Aux subsystem tests: PerfCounters, Config layering/observers,
Log ring + gates, OpTracker stage timing."""

import io
import time

import pytest

from ceph_tpu.utils.config import Config, Option
from ceph_tpu.utils.log import Log
from ceph_tpu.utils.op_tracker import OpTracker
from ceph_tpu.utils.perf_counters import (PerfCountersBuilder,
                                          PerfCountersCollection)


class TestPerfCounters:
    def build(self):
        return (PerfCountersBuilder("osd")
                .add_u64_counter("op_w", "writes")
                .add_u64("numpg", "placement groups")
                .add_time_avg("op_latency", "op latency")
                .add_histogram("op_size_hist", "op sizes", n_buckets=8)
                .create_perf_counters())

    def test_counter_gauge(self):
        c = self.build()
        c.inc("op_w")
        c.inc("op_w", 4)
        assert c.get("op_w") == 5
        c.set("numpg", 33)
        c.dec("numpg", 3)
        assert c.get("numpg") == 30
        with pytest.raises(TypeError):
            c.dec("op_w")  # counters are monotonic

    def test_time_avg_and_timer(self):
        c = self.build()
        c.tinc("op_latency", 0.5)
        c.tinc("op_latency", 1.5)
        got = c.get("op_latency")
        assert got["count"] == 2 and got["avg"] == 1.0
        with c.time("op_latency"):
            pass
        assert c.get("op_latency")["count"] == 3

    def test_histogram_buckets(self):
        c = self.build()
        for v in (1, 2, 3, 130):
            c.hinc("op_size_hist", v)
        assert sum(c.get("op_size_hist")) == 4
        assert c.get("op_size_hist")[7] == 1  # 130 -> bucket 7

    def test_histogram_bucket_boundaries_in_dump(self):
        """Slot i holds samples in [2^i, 2^(i+1)): exact powers of two
        land in their OWN slot, the last slot is the overflow clamp,
        and the dump carries the raw (non-cumulative) buckets."""
        c = self.build()
        for v in (1, 2, 4, 8, 127, 128, 1 << 30):  # 1<<30 >> 8 buckets
            c.hinc("op_size_hist", v)
        dumped = c.dump()["op_size_hist"]
        assert dumped[0] == 1          # 1
        assert dumped[1] == 1          # 2..3
        assert dumped[2] == 1          # 4..7
        assert dumped[3] == 1          # 8..15
        assert dumped[6] == 1          # 64..127
        assert dumped[7] == 2          # 128 + the overflow clamp
        assert sum(dumped) == 7

    def test_time_avg_math(self):
        """time_avg dump is (avgcount, sum); avg = sum/count exactly,
        0 when empty (no div-by-zero)."""
        c = self.build()
        assert c.get("op_latency") == {"sum": 0.0, "count": 0,
                                       "avg": 0.0}
        for s in (0.25, 0.25, 1.0):
            c.tinc("op_latency", s)
        got = c.get("op_latency")
        assert got == {"sum": 1.5, "count": 3, "avg": 0.5}
        d = c.dump()["op_latency"]
        assert d == {"avgcount": 3, "sum": 1.5}

    def test_concurrent_inc_from_threads(self):
        """inc/inc_many are atomic under the counter lock: N threads
        hammering one counter lose nothing."""
        import threading
        c = self.build()

        def worker():
            for _ in range(500):
                c.inc("op_w")
                c.inc_many((("op_w", 2),))
        threads = [threading.Thread(target=worker) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.get("op_w") == 8 * 500 * 3

    def test_dump_reset_roundtrip(self):
        """perf reset zeroes every kind; declarations (schema) and
        dump SHAPE survive — a post-reset dump has the same keys with
        zero values."""
        c = self.build()
        c.inc("op_w", 7)
        c.set("numpg", 4)
        c.tinc("op_latency", 1.0)
        c.hinc("op_size_hist", 9)
        before = c.dump()
        schema_before = c.schema()
        c.reset()
        after = c.dump()
        assert set(after) == set(before)
        assert after["op_w"] == 0 and after["numpg"] == 0
        assert after["op_latency"] == {"avgcount": 0, "sum": 0.0}
        assert sum(after["op_size_hist"]) == 0
        assert len(after["op_size_hist"]) == len(before["op_size_hist"])
        assert c.schema() == schema_before
        c.inc("op_w")               # still usable after reset
        assert c.get("op_w") == 1

    def test_declared_registry(self):
        from ceph_tpu.utils.perf_counters import is_declared
        self.build()
        assert is_declared("osd", "op_w")
        assert is_declared("osd", "op_size_hist")
        assert not is_declared("osd", "totally_made_up")

    def test_dump_delta_and_fold(self):
        from ceph_tpu.utils.perf_counters import dump_delta, fold_delta
        c = self.build()
        c.inc("op_w", 3)
        c.tinc("op_latency", 1.0)
        c.hinc("op_size_hist", 2)
        before = c.dump()
        c.inc("op_w", 4)
        c.tinc("op_latency", 0.5)
        c.hinc("op_size_hist", 2)
        delta = dump_delta({"osd": before}, {"osd": c.dump()})["osd"]
        assert delta["op_w"] == 4
        assert delta["op_latency"] == {"avgcount": 1, "sum": 0.5}
        assert sum(delta["op_size_hist"]) == 1
        # fold_delta(before, delta) == after
        refold = fold_delta({"osd": before},
                            {"osd": delta})["osd"]
        assert refold["op_w"] == c.dump()["op_w"]
        assert refold["op_size_hist"] == c.dump()["op_size_hist"]

    def test_collection_dump(self):
        coll = PerfCountersCollection()
        c = coll.add(self.build())
        c.inc("op_w")
        d = coll.dump()
        assert d["osd"]["op_w"] == 1
        assert d["osd"]["op_latency"] == {"avgcount": 0, "sum": 0.0}
        coll.remove("osd")
        assert coll.dump() == {}


class TestConfig:
    def test_defaults_and_layering(self):
        c = Config()
        assert c.get("osd_recovery_max_active") == 3
        c.load_file({"osd_recovery_max_active": "5"})
        assert c.get("osd_recovery_max_active") == 5
        c.set("osd_recovery_max_active", 7)           # mon layer
        assert c.get("osd_recovery_max_active") == 7
        c.set("osd_recovery_max_active", 9, level="override")
        assert c.get("osd_recovery_max_active") == 9
        c.rm("osd_recovery_max_active", level="override")
        assert c.get("osd_recovery_max_active") == 7

    def test_validation(self):
        c = Config()
        with pytest.raises(KeyError):
            c.get("nope")
        with pytest.raises(ValueError):
            c.set("osd_recovery_max_active", 0)       # min=1
        with pytest.raises(ValueError):
            c.set("osd_scrub_auto_repair", "maybe")
        c.set("osd_scrub_auto_repair", "true")
        assert c.get("osd_scrub_auto_repair") is True

    def test_observers(self):
        c = Config()
        seen = []
        c.observe("osd_heartbeat_grace", lambda k, v: seen.append((k, v)))
        c.set("osd_heartbeat_grace", 10.0)
        c.set("osd_heartbeat_grace", 10.0)  # no change -> no callback
        c.set("osd_heartbeat_grace", 12.0)
        assert seen == [("osd_heartbeat_grace", 10.0),
                        ("osd_heartbeat_grace", 12.0)]

    def test_diff(self):
        c = Config()
        c.set("debug_level", 5)
        d = c.diff()
        assert d == {"debug_level": {"value": 5, "level": "mon"}}


class TestLog:
    def test_gather_more_than_logged(self):
        sink = io.StringIO()
        lg = Log(max_recent=100, sink=sink)
        lg.set_level("ec", 1, gather=5)
        lg.dout("ec", 1, "printed and gathered")
        lg.dout("ec", 4, "gathered only")
        lg.dout("ec", 9, "dropped")
        printed = sink.getvalue()
        assert "printed and gathered" in printed
        assert "gathered only" not in printed
        recent = lg.dump_recent()
        assert any("gathered only" in ln for ln in recent)
        assert not any("dropped" in ln for ln in recent)

    def test_ring_bounded(self):
        lg = Log(max_recent=10, sink=None)
        for i in range(50):
            lg.dout("osd", 1, f"m{i}")
        recent = lg.dump_recent()
        assert len(recent) == 10
        assert "m49" in recent[-1]

    def test_crash_dump_format(self):
        sink = io.StringIO()
        lg = Log(max_recent=10, sink=None)
        lg.dout("osd", 1, "boom context")
        lg.dump_recent(file=sink)
        out = sink.getvalue()
        assert "begin dump of recent events" in out
        assert "boom context" in out


class TestOpTracker:
    def test_stages_and_history(self):
        tr = OpTracker(history_size=5)
        with tr.create_op("osd_op(client.1 write obj1)") as op:
            op.mark_event("queued")
            op.mark_event("encoded")
        assert tr.dump_ops_in_flight()["num_ops"] == 0
        hist = tr.dump_historic_ops()
        assert hist["num_ops"] == 1
        events = [e["event"] for e in
                  hist["ops"][0]["type_data"]["events"]]
        assert events == ["initiated", "queued", "encoded", "done"]

    def test_in_flight_and_slow(self):
        tr = OpTracker(complaint_time=0.01)
        op = tr.create_op("slow op")
        assert tr.dump_ops_in_flight()["num_ops"] == 1
        time.sleep(0.02)
        assert len(tr.slow_ops()) == 1
        op.finish()
        assert tr.slow_ops() == []

    def test_history_bounded_and_slowest(self):
        tr = OpTracker(history_size=3)
        for i in range(10):
            tr.create_op(f"op{i}").finish()
        assert tr.dump_historic_ops()["num_ops"] == 3
        assert tr.dump_historic_ops(by_duration=True)["num_ops"] == 3

    def test_exception_marks_failure(self):
        tr = OpTracker()
        with pytest.raises(RuntimeError):
            with tr.create_op("bad") as op:
                raise RuntimeError("x")
        events = [e["event"] for e in
                  tr.dump_historic_ops()["ops"][0]["type_data"]["events"]]
        assert any("failed: RuntimeError" in e for e in events)


class TestOpTrackerConfig:
    def test_thresholds_resolve_through_config(self):
        """osd_op_complaint_time / osd_op_history_* come from the
        config system LIVE — a runtime `config set` retunes a running
        tracker, no restart (the md_config_obs_t behavior)."""
        cfg = Config()
        tr = OpTracker(config=cfg)
        assert tr.complaint_time == 30.0          # schema default
        assert tr.history_size == 20
        cfg.set("osd_op_complaint_time", 0.01)
        op = tr.create_op("will be slow")
        time.sleep(0.02)
        assert len(tr.slow_ops()) == 1            # new threshold live
        cfg.set("osd_op_complaint_time", 60.0)
        assert tr.slow_ops() == []                # retuned again
        op.finish()

    def test_history_size_shrinks_live(self):
        cfg = Config()
        cfg.set("osd_op_history_size", 5)
        tr = OpTracker(config=cfg)
        for i in range(10):
            tr.create_op(f"op{i}").finish()
        assert tr.dump_historic_ops()["num_ops"] == 5
        cfg.set("osd_op_history_size", 2)
        assert tr.dump_historic_ops()["num_ops"] == 2
        assert tr.dump_historic_ops(
            by_duration=True)["num_ops"] == 2

    def test_ctor_fallbacks_without_config(self):
        tr = OpTracker(history_size=3, complaint_time=1.5)
        assert tr.history_size == 3
        assert tr.complaint_time == 1.5


def test_historic_ops_expire_by_age():
    tr = OpTracker(history_size=10, history_duration=0.05)
    tr.create_op("old").finish()
    time.sleep(0.08)
    tr.create_op("new").finish()
    ops = tr.dump_historic_ops()["ops"]
    descs = [o["description"] for o in ops]
    assert descs == ["new"]
    assert [o["description"] for o in
            tr.dump_historic_ops(by_duration=True)["ops"]] == ["new"]


# ------------------------------------------------ prometheus + tracing

class TestPrometheusExport:
    def test_exposition_format(self):
        from ceph_tpu.utils.perf_counters import (PerfCountersBuilder,
                                                  PerfCountersCollection)
        coll = PerfCountersCollection()
        pc = coll.add(PerfCountersBuilder("osd")
                      .add_u64_counter("ops", "client operations")
                      .add_u64("degraded", "degraded pgs")
                      .add_time_avg("op_lat")
                      .add_histogram("sizes", n_buckets=4)
                      .create_perf_counters())
        pc.inc("ops", 41)
        pc.set("degraded", 3)
        pc.tinc("op_lat", 0.5)
        pc.tinc("op_lat", 1.5)
        pc.hinc("sizes", 2)
        text = coll.prometheus_text()
        assert "# HELP ceph_tpu_osd_ops client operations" in text
        assert "# TYPE ceph_tpu_osd_ops counter" in text
        assert "ceph_tpu_osd_ops 41" in text
        assert "# TYPE ceph_tpu_osd_degraded gauge" in text
        assert "ceph_tpu_osd_degraded 3" in text
        assert "ceph_tpu_osd_op_lat_sum 2" in text
        assert "ceph_tpu_osd_op_lat_count 2" in text
        assert 'ceph_tpu_osd_sizes_bucket{le="+Inf"} 1' in text
        # every non-comment line is "name[{labels}] value"
        for line in text.strip().splitlines():
            if not line.startswith("#"):
                assert len(line.rsplit(" ", 1)) == 2

    def test_cluster_counters_export(self):
        from cluster_helpers import corpus, make_cluster
        from ceph_tpu.utils.perf_counters import PerfCountersCollection
        c = make_cluster(pg_num=2)
        c.write(corpus(4, 200, seed=20))
        coll = PerfCountersCollection()
        coll.add(c.perf)
        text = coll.prometheus_text()
        assert "ceph_tpu_cluster_recovered_objects" in text
        assert "ceph_tpu_cluster_degraded_pgs 0" in text


class TestTracing:
    def test_span_noop_and_counter(self):
        from ceph_tpu.utils.perf_counters import PerfCountersBuilder
        from ceph_tpu.utils.tracing import span
        pc = (PerfCountersBuilder("t").add_time_avg("lat")
              .create_perf_counters())
        with span("unit.test.span", counters=pc, key="lat"):
            pass
        got = pc.get("lat")
        assert got["count"] == 1 and got["sum"] >= 0

    @pytest.mark.slow
    def test_trace_capture_roundtrip(self, tmp_path):
        # nightly since r20: the jax.profiler device-trace capture
        # costs ~100 s of the 870 s tier-1 cap on a loaded box; the
        # span/counter tracing cells above keep the plane tier-1
        # profiler capture around a real device op; degrades gracefully
        import jax.numpy as jnp
        from ceph_tpu.utils.tracing import span, trace
        with trace(str(tmp_path)) as ok:
            with span("unit.capture"):
                jnp.arange(8).sum().block_until_ready()
        if ok:
            import os
            assert any(os.scandir(str(tmp_path)))


class TestOpTracking:
    def test_client_rpc_ops_are_tracked(self):
        from cluster_helpers import corpus, make_cluster
        from ceph_tpu.client.objecter import Objecter
        c = make_cluster(pg_num=2)
        ob = Objecter(c)
        objs = corpus(4, 200, seed=30)
        ob.write(objs)
        ob.read(list(objs))
        hist = c.op_tracker.dump_historic_ops()
        assert hist["num_ops"] >= 2
        descs = " ".join(o["description"] for o in hist["ops"])
        assert "client_rpc write" in descs
        assert "client_rpc read" in descs
        events = [ev["event"] for o in hist["ops"]
                  for ev in o["type_data"]["events"]]
        assert "reached_pg" in events
        inflight = c.op_tracker.dump_ops_in_flight()
        assert inflight.get("num_ops", inflight.get("num", 0)) == 0


class TestDevicePlacedFromOutside:
    """One process per chip: nothing claims a device, or chooses a
    compile-cache directory, behind the caller's back."""

    @staticmethod
    def _run(code: str, **env_extra) -> str:
        import os
        import subprocess
        import sys
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        env.update(JAX_PLATFORMS="cpu", PYTHONPATH=repo, **env_extra)
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        return out.stdout.strip().splitlines()[-1]

    def test_importing_the_served_path_creates_no_backend(self):
        got = self._run(
            "import ceph_tpu.osd.standalone, ceph_tpu.osd.multiproc, "
            "ceph_tpu.csum, ceph_tpu.ec.registry\n"
            "from jax._src import xla_bridge\n"
            "print(xla_bridge.backends_are_initialized())")
        assert got == "False"

    @pytest.mark.parametrize("outside", ["", "/some/outside/dir"])
    def test_compile_cache_directory(self, outside):
        from ceph_tpu.utils.jax_cache import DEFAULT_CACHE_DIR
        env = {"JAX_COMPILATION_CACHE_DIR": outside} if outside else {}
        got = self._run(
            "import jax\n"
            "from ceph_tpu.utils.jax_cache import "
            "enable_persistent_compile_cache as on\n"
            "before = jax.config.jax_compilation_cache_dir\n"
            "ret = on()\n"
            "print(before, jax.config.jax_compilation_cache_dir, ret)",
            **env)
        if outside:   # placed from outside: the code sets no directory
            assert got.split() == [outside] * 3
        else:         # one fixed path inside the checkout
            assert got.split() == ["None", DEFAULT_CACHE_DIR,
                                   DEFAULT_CACHE_DIR]
            assert DEFAULT_CACHE_DIR.endswith("/.jax_bench_cache")
