"""The span log of utils/tracing (PR 25): one `span()` with two sinks
while a profiler session is live, none while it is not.

Units: self time with children subtracted, `record_wait`, the compile
listener, `stage_table`, `start_trace`/`stop_trace` on this jax. Live
(one small cephx + secure EC cluster on TinStore, the fused device
write path switched on for the CPU backend as bench/tests does): with
no session a client write and read log nothing; with one, a write
leaves every write-path stage and a read every read-path stage, the
spans of one op share its trace id on the client, its primary and the
replicas, and the new `ec` verify counters rise. A read that rebuilds
a row (its PG's data-slot holder down and not yet out, PR 27) leaves
the decode's stage, launch and fetch and feeds `degraded_reads`,
`decode_rows_rebuilt` and `decode_bytes_rebuilt`: one case each.
A read's gather (PR 28) is one `readv` frame a remote slot with the
hinfo in the answer: the sub-op kinds the OSDs serve, `gather_rounds`
and `gather_frames`, a slot planned around, rot still caught, and the
peer-latency EWMA fed by the pipelined reads.
A partial overwrite (PR 31) leaves `ecbackend.rmw`, its six children and
`osd.persist_meta` (PR 32: the record's encode, inside the RMW, sent on
its one apply round), one case each, and nothing with no session;
`rmw_host_delta_launches` tells the host's delta launches from the
device's; the log counts the records it drops.
A backfill (PR 34: the degraded pool's victim marked out under a live
session) leaves `recovery.reserve.wait` and `recovery.grant` with its
children `.pull`, `.stage`, `.launch`, `.fetch`, `.push` and `.settle`,
one case each, so that every `recovery.*` reader keeps a number.
The host's ledger (PR 36): under a live session every record carries its
thread's CPU time by the rule of `self`, its `tid` and its `parent`; a
`detail=True` child is logged beside its parent's whole; one
`trace-probe` thread lives exactly as long as a capture and logs
`host.tick` (`late`) and two `host.usage` records; `stop_trace` answers
with `cpu_s` by stage and a `host` block; a store commit shows its four
parts; the cluster's threads carry their role in their name.
"""

import os
import re
import threading
import time

import pytest

from ceph_tpu.utils import tracing
from ceph_tpu.utils.flight_recorder import (FlightRecorder, TraceContext,
                                            activate, is_span_declared)
from ceph_tpu.utils.tracing import (record_wait, span, span_log,
                                    stage_table, start_trace, stop_trace)

WRITE_STAGES = {
    "client.op", "msgr.seal", "msgr.open", "osd.queue", "osd.op",
    "osd.pg_lock.wait", "ecbackend.write.stripe", "ecbackend.write.encode",
    "ecbackend.write.stage", "ecbackend.write.launch",
    "ecbackend.write.fetch", "ecbackend.write.txns",
    "ecbackend.write.fanout", "osd.subop", "osd.store_lock.wait",
    "store.apply", "store.commit"}
READ_STAGES = {
    "client.op", "msgr.seal", "msgr.open", "osd.queue", "osd.op",
    "osd.pg_lock.wait", "ecbackend.read.gather", "ecbackend.read.verify",
    "ecbackend.read.verify.stage", "ecbackend.read.verify.launch",
    "ecbackend.read.verify.fetch", "ecbackend.read.decode",
    "ecbackend.read.unstripe", "osd.subop",
    "osd.store_lock.wait", "store.apply", "store.read"}


@pytest.fixture
def session(tmp_path):
    """A live capture, Python tracer off: what makes tracing 'on'."""
    assert start_trace(str(tmp_path / "capture"))
    yield tmp_path / "capture"
    if tracing._session[0] is not None:
        stop_trace()


def _mine(since: float) -> dict:
    """name -> records logged since `since`; compiles, the probe's
    `host.*` records and the samplers' own spans left out."""
    out: dict = {}
    for r in span_log(since=since):
        if r["name"] not in ("xla.compile", "profiler.sample") \
                and not r["name"].startswith("host."):
            out.setdefault(r["name"], []).append(r)
    return out


class TestSpanLogUnits:
    def test_no_session_no_record_but_the_counter_ticks(self):
        from ceph_tpu.utils.perf_counters import PerfCountersBuilder
        pc = (PerfCountersBuilder("t").add_time_avg("lat")
              .create_perf_counters())
        t0 = time.perf_counter()
        with span("unit.off", counters=pc, key="lat"):
            pass
        record_wait("unit.off.wait", t0, 0.001)
        assert _mine(t0) == {}
        assert pc.get("lat")["count"] == 1

    def test_a_spans_tags_ride_its_record(self, session):
        t0 = time.perf_counter()
        with span("unit.tagged", tags={"objects": 4}):
            pass
        with span("unit.plain"):
            pass
        got = _mine(t0)
        assert got["unit.tagged"][0]["tags"] == {"objects": 4}
        assert "tags" not in got["unit.plain"][0]

    def test_only_a_mapped_code_logs_its_slot_order(self, session):
        """An LRC write puts its rows in slot order under
        `ecbackend.write.slots`; an identity-mapped RS write has nothing
        to put there and logs no such span."""
        import numpy as np

        import ceph_tpu.osd.standalone  # noqa: F401 — declares the spans
        from ceph_tpu.osd.ecbackend import ECBackend
        assert is_span_declared("ecbackend.write.slots")
        for profile, n, logged in (("plugin=lrc k=4 m=2 l=3", 8, 1),
                                   ("plugin=jerasure k=4 m=2", 6, 0)):
            be = ECBackend(profile, "0.0", list(range(n)), chunk_size=256)
            t0 = time.perf_counter()
            be._encode_shards_with_crcs(np.zeros((1, 4, 256), np.uint8),
                                        256)
            assert len(_mine(t0).get("ecbackend.write.slots", [])) \
                == logged, profile

    def test_self_time_subtracts_children_on_the_same_thread(self, session):
        t0 = time.perf_counter()
        with span("unit.parent"):
            with span("unit.child", nbytes=7):
                time.sleep(0.02)
            with span("unit.child"):
                time.sleep(0.01)
            time.sleep(0.005)
        got = _mine(t0)
        (parent,), kids = got["unit.parent"], got["unit.child"]
        assert len(kids) == 2 and kids[0]["nbytes"] == 7
        for r in kids + [parent]:
            assert 0 <= r["self"] <= r["dur"]
        covered = sum(k["dur"] for k in kids)
        assert parent["self"] == pytest.approx(parent["dur"] - covered)
        assert 0.004 < parent["self"] < parent["dur"] - 0.029
        # the child ended first, and inside its parent
        assert kids[0]["start"] >= parent["start"]
        assert span_log(since=t0)[-1]["name"] == "unit.parent"

    def test_a_span_that_raises_is_logged_and_unwinds_the_stack(self,
                                                                 session):
        t0 = time.perf_counter()
        with pytest.raises(ValueError):
            with span("unit.outer"):
                with span("unit.raises"):
                    raise ValueError("boom")
        with span("unit.after"):
            pass
        got = _mine(t0)
        assert set(got) == {"unit.outer", "unit.raises", "unit.after"}
        assert tracing._tls.stack == []

    def test_trace_id_is_the_bound_context_s_sampled_or_not(self, session):
        t0 = time.perf_counter()
        fr = FlightRecorder("osd.9")
        with activate(TraceContext(0xABC, 1, sampled=False), fr):
            with span("unit.unsampled"):
                pass
        with activate(TraceContext(0xDEF, 1, sampled=True), fr):
            with span("osd.op", nbytes=3):
                pass
        with span("unit.unbound"):
            pass
        got = _mine(t0)
        assert got["unit.unsampled"][0]["trace_id"] == 0xABC
        assert got["osd.op"][0]["trace_id"] == 0xDEF
        assert got["unit.unbound"][0]["trace_id"] is None
        # the flight ring takes the sampled one only, with its tag
        ring = fr.dump()["spans"]
        assert [s["name"] for s in ring] == ["osd.op"]
        assert ring[0]["tags"] == {"nbytes": 3}

    def test_locked_spans_the_wait_and_lets_go_when_the_body_raises(
            self, session):
        import threading
        from ceph_tpu.utils.tracing import locked
        lock = threading.Lock()
        t0 = time.perf_counter()
        with pytest.raises(KeyError):
            with locked(lock, "osd.pg_lock.wait"):
                assert lock.locked()
                raise KeyError("body")
        assert not lock.locked()
        (wait,) = _mine(t0)["osd.pg_lock.wait"]
        assert wait["dur"] < 0.01            # the wait, not the hold

    def test_record_wait_logs_the_wait_as_given(self, session):
        t0 = time.perf_counter()
        record_wait("osd.queue", t0, 0.25, trace_id=5)
        (r,) = _mine(t0)["osd.queue"]
        assert (r["start"], r["dur"], r["self"], r["trace_id"]) == (
            t0, 0.25, 0.25, 5)

    def test_stage_table_sums_self_by_name(self):
        recs = [{"name": "a", "self": 0.010}, {"name": "a", "self": 0.030},
                {"name": "b", "self": 0.002}]
        table = stage_table(recs, 4)
        assert table["a"]["count"] == 2
        assert table["a"]["self_s"] == pytest.approx(0.040)
        assert table["a"]["self_ms_per_op"] == pytest.approx(10.0)
        assert table["b"]["self_ms_per_op"] == pytest.approx(0.5)
        assert stage_table(recs, 0)["a"]["self_ms_per_op"] is None

    def test_a_fresh_jit_is_logged_with_no_session_live(self):
        import jax
        import jax.numpy as jnp
        assert not tracing.TraceAnnotation.is_enabled()
        t0 = time.perf_counter()
        salt = float(time.time())            # a program never built before

        def fresh_program(x):
            return x * salt + 25
        jax.jit(fresh_program)(jnp.arange(25)).block_until_ready()
        t1 = time.perf_counter()
        compiles = [r for r in span_log(since=t0, until=t1)
                    if r["name"] == "xla.compile"]
        assert compiles and all(0 < r["dur"] <= t1 - t0 for r in compiles)
        assert all(t0 <= r["start"] for r in compiles)
        assert "jit(fresh_program)" in [r["program"] for r in compiles]

    def test_a_compile_inside_a_span_is_not_the_span_s_self_time(self,
                                                                  session):
        import jax
        import jax.numpy as jnp
        t0 = time.perf_counter()
        salt = float(time.time()) + 1.0
        with span("unit.launch"):
            jax.jit(lambda x: x - salt)(jnp.arange(26)).block_until_ready()
        (launch,) = _mine(t0)["unit.launch"]
        compiled = sum(r["dur"] for r in span_log(since=t0)
                       if r["name"] == "xla.compile")
        assert compiled > 0
        assert launch["self"] == pytest.approx(launch["dur"] - compiled)

    def test_start_and_stop_take_the_python_tracer_off_branch(self,
                                                              tmp_path):
        out = tmp_path / "cap"
        assert start_trace(str(out))
        # the session is the binding's own, not jax.profiler's default
        from jax._src.lib import _profiler
        assert isinstance(tracing._session[0], _profiler.ProfilerSession)
        assert tracing.TraceAnnotation.is_enabled()
        with span("osd.op"):
            with span("store.apply"):
                time.sleep(0.002)
        got = stop_trace()
        assert not tracing.TraceAnnotation.is_enabled()
        assert got["dir"] == str(out) and got["ops"] == 1
        assert got["stages"]["store.apply"]["self_ms_per_op"] >= 2.0
        assert got["stages"]["osd.op"]["count"] == 1
        assert any(f.endswith(".xplane.pb")
                   for _, _, files in os.walk(out) for f in files)
        assert stop_trace() is None          # nothing left to stop


    # before the live cluster below switches the CPU backend's host
    # shortcut off for the rest of the module
    @pytest.mark.parametrize("branch,profile", [
        ("native host codec", "plugin=tpu_rs k=4 m=2"),
        ("fused device program", "plugin=tpu_rs k=4 m=2"),
        ("generic parity_delta", "plugin=clay k=4 m=2")])
    def test_rmw_host_delta_launches_rises_on_the_host_branches_only(
            self, branch, profile, monkeypatch):
        from ceph_tpu.osd import ecbackend
        from ceph_tpu.osd.ecbackend import ECBackend, ShardSet
        if branch == "native host codec":
            if not ecbackend._host_crc_available():
                pytest.skip("no native host codec built here")
        else:
            monkeypatch.setattr(ecbackend, "_host_crc_available",
                                lambda: False)
        n = 6
        be = ECBackend(profile, "1.0", list(range(n)), ShardSet(),
                       chunk_size=512)
        be.write_objects({"o": os.urandom(be.sinfo.stripe_width * 2)})
        be.write_ranges([("o", 16, os.urandom(64))])
        got = {k: int(be.perf.get(k)) for k in
               ("rmw_ops", "rmw_delta_launches", "rmw_host_delta_launches",
                "rmw_full_fallbacks")}
        assert got == {"rmw_ops": 1, "rmw_delta_launches": 1,
                       "rmw_host_delta_launches":
                           0 if branch == "fused device program" else 1,
                       "rmw_full_fallbacks": 0}
        assert "rmw_host_delta_launches" in be.perf.dump()   # `perf dump`

    def test_a_full_log_counts_the_records_it_drops(self, monkeypatch,
                                                    tmp_path):
        import collections
        monkeypatch.setattr(tracing, "_LOG", collections.deque(maxlen=4))
        # the probe's records would count too (PR 36): held off here
        monkeypatch.setattr(tracing, "_start_probe", lambda: None)
        before = tracing.span_log_dropped()
        assert start_trace(str(tmp_path / "cap"))
        for i in range(7):
            with span("osd.op"):
                pass
        # the newest four are kept; `trace stop`'s table says three of
        # the capture's records are gone
        assert len(span_log()) == 4
        assert tracing.span_log_dropped() == before + 3
        got = stop_trace()
        assert got["dropped"] == 3 and got["ops"] == 4
        # a capture that fits drops none
        monkeypatch.setattr(tracing, "_LOG", collections.deque(maxlen=64))
        assert start_trace(str(tmp_path / "cap2"))
        with span("osd.op"):
            pass
        assert stop_trace()["dropped"] == 0



# -- the host's ledger (PR 36) -------------------------------------------------

def _burn(cpu_s: float) -> None:
    """Spin until this thread has used `cpu_s` of CPU."""
    end = time.thread_time() + cpu_s
    while time.thread_time() < end:
        pass


def _retried(attempt, tries: int = 4):
    """A case that reads the scheduler's timing: steady under `-n 6`
    when any of a few attempts holds."""
    for left in range(tries - 1, -1, -1):
        try:
            return attempt()
        except AssertionError:
            if not left:
                raise
            time.sleep(0.2)


def _probe_threads() -> list:
    return [t for t in threading.enumerate() if t.name == "trace-probe"]


def _bare_session():
    """A profiler session opened the way bench/run.py opens its own:
    no `start_trace`."""
    import jax
    from jax._src.lib import _profiler
    jax.devices()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return _profiler.ProfilerSession(opts)


def _wait_probe_gone(timeout: float) -> float:
    """Seconds until no `trace-probe` thread is left (the timeout's
    length if one still is)."""
    t0 = time.perf_counter()
    while _probe_threads() and time.perf_counter() - t0 < timeout:
        time.sleep(0.001)
    return time.perf_counter() - t0


class TestCpuTidParentDetail:
    def test_a_span_that_spins_reads_cpu_near_its_self_time(self, session):
        t0 = time.perf_counter()
        with span("unit.spin"):
            _burn(0.02)
        (r,) = _mine(t0)["unit.spin"]
        assert 0.02 <= r["cpu"] <= r["self"] + 1e-3
        assert r["cpu"] < 0.03

    def test_a_span_that_sleeps_reads_next_to_no_cpu(self, session):
        t0 = time.perf_counter()
        with span("unit.sleep"):
            time.sleep(0.02)
        (r,) = _mine(t0)["unit.sleep"]
        assert r["self"] >= 0.02 and 0.0 <= r["cpu"] < 0.002

    def test_a_parent_s_cpu_is_its_own_less_its_children_s(self, session):
        t0 = time.perf_counter()
        c0 = time.thread_time()
        with span("unit.parent"):
            _burn(0.01)
            with span("unit.child"):
                _burn(0.02)
        used = time.thread_time() - c0
        got = _mine(t0)
        (parent,), (child,) = got["unit.parent"], got["unit.child"]
        assert 0.02 <= child["cpu"] < 0.03
        assert 0.01 <= parent["cpu"] < 0.02
        assert parent["cpu"] + child["cpu"] == pytest.approx(used, abs=2e-3)

    def test_a_detail_child_is_logged_beside_its_parent_s_whole(self,
                                                                session):
        t0 = time.perf_counter()
        with span("unit.whole"):
            with span("unit.part", detail=True):
                _burn(0.01)
                with span("unit.inside"):
                    _burn(0.005)
            with span("unit.child"):
                time.sleep(0.005)
        got = _mine(t0)
        (whole,), (part,) = got["unit.whole"], got["unit.part"]
        (inside,), (child,) = got["unit.inside"], got["unit.child"]
        assert part["detail"] is True and part["parent"] == "unit.whole"
        assert "detail" not in whole and "detail" not in child
        # the part took nothing from the whole: only the plain child did
        assert whole["self"] == pytest.approx(whole["dur"] - child["dur"])
        assert whole["cpu"] >= 0.015
        # a detail span is a parent like any other
        assert inside["parent"] == "unit.part"
        assert part["self"] == pytest.approx(part["dur"] - inside["dur"])
        assert 0.01 <= part["cpu"] < 0.015

    def test_the_cpu_clock_is_read_once_for_boundaries_close_together(
            self, session, monkeypatch):
        reads = []

        def clock():
            reads.append(time.perf_counter())
            return time.thread_time()
        monkeypatch.setattr(tracing, "_cpu_now", clock)
        monkeypatch.setattr(tracing, "_CPU_TRUST_S", 0.005)
        time.sleep(0.006)                # past the last reading's trust
        t0 = time.perf_counter()
        with span("unit.a"):             # one reading serves all four
            pass
        with span("unit.b"):
            pass
        assert len(reads) == 1
        time.sleep(0.006)
        with span("unit.c"):
            _burn(0.01)                  # entered past it, left past it
        assert len(reads) == 3
        got = _mine(t0)
        (a,), (b,), (c,) = got["unit.a"], got["unit.b"], got["unit.c"]
        # within the trust a boundary reads the wall time since
        assert a["cpu"] == pytest.approx(a["dur"], abs=1e-5)
        assert b["cpu"] == pytest.approx(b["dur"], abs=1e-5)
        assert 0.01 <= c["cpu"] < 0.02
        assert min(a["cpu"], b["cpu"]) >= 0.0

    def test_tid_and_parent_across_two_threads(self, session):
        t0 = time.perf_counter()
        tids = {}

        def work(key):
            tids[key] = threading.get_ident()
            with span(f"unit.outer.{key}"):
                with span(f"unit.inner.{key}"):
                    time.sleep(0.005)
        workers = [threading.Thread(target=work, args=(k,), name=f"unit-{k}")
                   for k in ("a", "b")]
        for w in workers:
            w.start()
        for w in workers:
            w.join()
        got = _mine(t0)
        for key in ("a", "b"):
            (outer,), (inner,) = (got[f"unit.outer.{key}"],
                                  got[f"unit.inner.{key}"])
            assert outer["tid"] == inner["tid"] == tids[key]
            assert outer["parent"] is None
            assert inner["parent"] == f"unit.outer.{key}"
        assert tids["a"] != threading.get_ident()

    def test_record_wait_and_compiles_carry_the_keys_every_record_has(
            self, session):
        import jax
        import jax.numpy as jnp
        t0 = time.perf_counter()
        record_wait("unit.wait", t0, 0.25, trace_id=9)
        salt = float(time.time()) + 2.0
        jax.jit(lambda x: x * salt)(jnp.arange(27)).block_until_ready()
        (wait,) = _mine(t0)["unit.wait"]
        assert wait["cpu"] == 0.0 and wait["parent"] is None
        assert wait["tid"] == threading.get_ident()
        compiles = [r for r in span_log(since=t0)
                    if r["name"] == "xla.compile"]
        assert compiles and all(r["cpu"] is None and "tid" in r
                                for r in compiles)

    def test_stage_table_sums_cpu_beside_self(self):
        recs = [{"name": "a", "self": 0.3, "cpu": 0.1},
                {"name": "a", "self": 0.1, "cpu": 0.05},
                {"name": "xla.compile", "self": 1.0, "cpu": None},
                {"name": "old", "self": 0.2}]
        table = stage_table(recs, ops=2)
        assert table["a"]["cpu_s"] == pytest.approx(0.15)
        assert table["a"]["cpu_ms_per_op"] == pytest.approx(75.0)
        assert table["a"]["self_ms_per_op"] == pytest.approx(200.0)
        assert table["xla.compile"]["cpu_s"] == table["old"]["cpu_s"] == 0.0
        assert stage_table(recs, ops=0)["a"]["cpu_ms_per_op"] is None


class TestHostProbe:
    def test_no_session_no_probe_and_no_host_record(self):
        assert _wait_probe_gone(2.0) < 2.0
        t0 = time.perf_counter()
        with span("unit.off"):
            time.sleep(0.03)
        assert _probe_threads() == []
        assert [r for r in span_log(since=t0)
                if r["name"].startswith("host.")] == []

    def test_a_bare_session_and_one_span_start_it_and_its_end_ends_it(self):
        def attempt():
            assert _wait_probe_gone(2.0) < 2.0
            t0 = time.perf_counter()
            sess = _bare_session()
            try:
                assert _probe_threads() == []    # nothing entered a span yet
                with span("unit.first"):
                    pass
                assert len(_probe_threads()) == 1
                time.sleep(0.1)
                with span("unit.second"):        # and no second probe
                    pass
                assert len(_probe_threads()) == 1
            finally:
                sess.stop()
            gone_after = _wait_probe_gone(1.0)
            recs = [r for r in span_log(since=t0)
                    if r["name"].startswith("host.")]
            names = [r["name"] for r in recs]
            assert names[0] == names[-1] == "host.usage"
            assert names.count("host.usage") == 2
            ticks = [r for r in recs if r["name"] == "host.tick"]
            assert 3 <= len(ticks) <= 11
            assert all(r["late"] >= 0.0 and r["dur"] == r["self"] == 0.0
                       and r["cpu"] == 0.0 for r in ticks)
            first, last = recs[0], recs[-1]
            assert first["start"] <= ticks[0]["start"]
            assert ticks[-1]["start"] <= last["start"]
            for key in ("process_s", "user_s", "system_s", "minflt",
                        "nvcsw", "nivcsw"):
                assert last[key] >= first[key], key
            assert first["cpus"] >= 1 and first["switch_interval_s"] > 0
            by_name = {name: s for _, name, s in last["threads"]}
            assert by_name["MainThread"] > 0 and "trace-probe" in by_name
            assert gone_after < 0.05
        _retried(attempt)

    def test_spinning_threads_make_the_probe_late(self):
        """What a thread that becomes runnable pays to take the GIL
        back: well above the idle floor (~0.1-0.4 ms here) once eight
        threads spin in the interpreter."""
        def attempt():
            assert _wait_probe_gone(2.0) < 2.0
            stop = threading.Event()

            def spin():
                while not stop.is_set():
                    pass
            spinners = [threading.Thread(target=spin, name=f"unit-spin-{i}",
                                         daemon=True) for i in range(8)]
            t0 = time.perf_counter()
            sess = _bare_session()
            try:
                with span("unit.kick"):
                    pass
                for s in spinners:
                    s.start()
                time.sleep(0.5)
            finally:
                sess.stop()
                _wait_probe_gone(2.0)    # its last record sees them live
                stop.set()
                for s in spinners:
                    s.join()
            host = tracing.host_usage(span_log(since=t0))
            assert host["ticks"] >= 3
            assert host["late_mean_ms"] >= 1.0
            assert host["late_p95_ms"] >= host["late_mean_ms"] * 0.5
            assert host["cpu_s_by_role"]["unit-spin"] > 0.1
        _retried(attempt)

    def test_stop_trace_answers_with_the_host_s_ledger(self, tmp_path):
        assert start_trace(str(tmp_path / "cap"))
        assert len(_probe_threads()) == 1        # start_trace starts it
        with span("osd.op"):
            _burn(0.05)
        time.sleep(0.05)
        got = stop_trace()
        assert _probe_threads() == []            # and stop_trace waits for it
        assert not any(n.startswith("host.") for n in got["stages"])
        assert got["stages"]["osd.op"]["cpu_s"] >= 0.05
        host = got["host"]
        assert host["seconds"] >= 0.1 and host["ticks"] >= 3
        assert host["cpu_s"] >= 0.05
        assert host["cores_busy"] == pytest.approx(
            host["cpu_s"] / host["seconds"])
        assert host["user_s"] + host["system_s"] == pytest.approx(
            host["cpu_s"], abs=0.02)
        assert host["minor_faults"] >= 0
        assert host["late_p95_ms"] >= 0 and host["late_mean_ms"] >= 0
        assert host["cpu_s_by_role"]["MainThread"] >= 0.05
        assert sum(host["cpu_s_by_role"].values()) + host["native_cpu_s"] \
            == pytest.approx(host["cpu_s"])

    @pytest.mark.parametrize("name,role", [
        ("osd.3-shard0", "shard"), ("msgr-osd.3-r0", "msgr-r"),
        ("msgr-client.0-ack", "msgr-ack"), ("profiler-mon.1", "profiler"),
        ("osd.11-hb", "hb"), ("mon.0-hb", "hb"),
        ("osd.3-recover-build-5", "recover-build"),
        ("bench-loop-7", "bench-loop"), ("MainThread", "MainThread"),
        ("trace-probe", "trace-probe"), ("rados-aio_0", "rados-aio")])
    def test_a_thread_s_role_is_its_name_less_daemon_and_digits(self, name,
                                                                role):
        assert tracing.thread_role(name) == role


# -- live cluster ------------------------------------------------------------

@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    from ceph_tpu.osd import ecbackend
    from ceph_tpu.osd.standalone import StandaloneCluster
    # the fused device write path, as on a chip (the CPU backend's
    # native-codec shortcut has no stage, launch or fetch)
    keep = ecbackend._host_crc_available
    ecbackend._host_crc_available = lambda: False
    # a grace no loaded host runs out: a peer held for dead would turn
    # a healthy read into a degraded one under the counts below
    c = StandaloneCluster(
        n_osds=4, pg_num=2, cephx=True, secret=os.urandom(32),
        hb_interval=0.5, hb_grace=30.0,
        store="tin", store_dir=str(tmp_path_factory.mktemp("tin")))
    try:
        c.wait_for_clean(timeout=40)
        yield c
    finally:
        c.shutdown()
        ecbackend._host_crc_available = keep


@pytest.fixture(scope="module")
def client(cluster):
    cl = cluster.client()
    cl.trace_sample_rate = 0.0       # ids travel, nothing is sampled
    cl.write({"warm": b"w" * 3000})  # programs compiled, sessions up
    assert cl.read("warm") == b"w" * 3000
    return cl


def _ec(cluster, key):
    return sum(int(d.ec_perf.get(key)) for d in cluster.osds.values())


class TestLiveSpanLog:
    def test_no_session_a_write_and_a_read_log_nothing(self, cluster,
                                                       client):
        t0 = time.perf_counter()
        client.write({"dark": b"d" * 3000})
        assert client.read("dark") == b"d" * 3000
        assert _mine(t0) == {}

    def test_a_traced_write_leaves_every_write_stage(self, cluster,
                                                     client, session):
        t0 = time.perf_counter()
        launches = _ec(cluster, "fused_write_launches")
        client.write({"lit": b"l" * 3000})
        got = _mine(t0)
        assert _ec(cluster, "fused_write_launches") == launches + 1
        assert WRITE_STAGES <= set(got), WRITE_STAGES - set(got)
        assert all(is_span_declared(name) for name in got)
        for recs in got.values():
            assert all(-1e-9 <= r["self"] <= r["dur"] + 1e-9 for r in recs)
        # stage, launch and fetch are the encode span's children
        (enc,) = got["ecbackend.write.encode"]
        kids = sum(got[f"ecbackend.write.{k}"][0]["dur"]
                   for k in ("stage", "launch", "fetch"))
        assert enc["self"] == pytest.approx(enc["dur"] - kids)
        # one identifier from the client through the primary to the
        # replicas' store applies
        tid = got["client.op"][-1]["trace_id"]
        assert tid
        for name in ("osd.queue", "osd.op", "ecbackend.write.fanout"):
            assert got[name][-1]["trace_id"] == tid, name
        applies = [r for r in got["store.apply"] if r["trace_id"] == tid]
        assert len(applies) == 2             # k+m-1 shards are remote
        # osd.op's stages cover it: the queue wait lies before it
        (op,) = got["osd.op"]
        (queue,) = got["osd.queue"]
        assert queue["start"] + queue["dur"] <= op["start"] + 1e-4
        assert op["self"] < op["dur"]

    def test_a_traced_read_leaves_every_read_stage(self, cluster, client,
                                                   session):
        client.write({"seen": b"s" * 3000})
        t0 = time.perf_counter()
        before = {k: _ec(cluster, k) for k in ("verify_launches",
                                               "verify_bytes")}
        timed = sum(d.ec_perf.get("verify_time")["count"]
                    for d in cluster.osds.values())
        assert client.read("seen") == b"s" * 3000
        got = _mine(t0)
        assert READ_STAGES <= set(got), READ_STAGES - set(got)
        assert all(is_span_declared(name) for name in got)
        # a healthy read is one verify launch over the k rows it used
        assert _ec(cluster, "verify_launches") == before["verify_launches"] + 1
        assert _ec(cluster, "verify_bytes") == before["verify_bytes"] + 3072
        assert sum(d.ec_perf.get("verify_time")["count"]
                   for d in cluster.osds.values()) == timed + 1
        (verify,) = got["ecbackend.read.verify"]
        kids = sum(got[f"ecbackend.read.verify.{k}"][0]["dur"]
                   for k in ("stage", "launch", "fetch"))
        assert verify["self"] == pytest.approx(verify["dur"] - kids)
        tid = got["client.op"][-1]["trace_id"]
        assert got["osd.op"][-1]["trace_id"] == tid
        assert any(r["trace_id"] == tid for r in got["store.read"])

    def test_seal_and_open_feed_their_counters(self, cluster, client):
        d = next(iter(cluster.osds.values()))
        before = {k: d.msgr.perf.get(k)["count"]
                  for k in ("seal_time", "open_time")}
        client.write({"sealed": b"q" * 3000})
        client.read("sealed")
        total = {k: sum(x.msgr.perf.get(k)["count"]
                        for x in cluster.osds.values())
                 for k in before}
        assert all(total[k] > before[k] for k in before)

    def test_trace_start_stop_over_the_admin_socket(self, cluster, client,
                                                    tmp_path):
        from ceph_tpu.utils.admin_socket import admin_command
        d = next(iter(cluster.osds.values()))
        out = str(tmp_path / "asok-capture")
        got = admin_command(cluster.asok_path(d.name), f"trace start {out}")
        assert got == {"started": True, "dir": out}
        client.write({"by-operator": b"o" * 3000})
        got = admin_command(cluster.asok_path(d.name), "trace stop")
        assert got["stopped"] is True and got["dir"] == out
        assert got["ops"] >= 1
        assert got["stages"]["osd.op"]["count"] == got["ops"]
        assert got["stages"]["ecbackend.write.fanout"]["self_ms_per_op"] > 0
        again = admin_command(cluster.asok_path(d.name), "trace stop")
        assert again == {"stopped": False}

    # -- the host's ledger on the served path (PR 36) --------------------------

    def test_a_traced_write_shows_each_commit_s_parts(self, cluster, client,
                                                      session):
        t0 = time.perf_counter()
        client.write({"parts": b"p" * 3000})
        recs = span_log(since=t0)
        commits = [r for r in recs if r["name"] == "store.commit"]
        parts = [r for r in recs if r["name"].startswith("store.commit.")]
        assert all(p["detail"] is True and p["parent"] == "store.commit"
                   for p in parts)
        four = {"store.commit.stage", "store.commit.pwrite",
                "store.commit.csum", "store.commit.wal"}
        with_bytes = inside_all = 0
        for c in commits:
            end = c["start"] + c["dur"]
            inside = [p for p in parts if p["tid"] == c["tid"]
                      and c["start"] <= p["start"]
                      and p["start"] + p["dur"] <= end + 1e-9]
            names = {p["name"] for p in inside}
            inside_all += len(inside)
            assert "store.commit.wal" in names
            if names != {"store.commit.wal"}:    # a commit that holds bytes
                assert names == four
                with_bytes += 1
            assert sum(p["dur"] for p in inside) <= c["dur"] + 1e-6
            # the parts take nothing from the whole: what
            # `store.apply_ms_per_op` sums is the commit's time
            assert c["self"] == pytest.approx(c["dur"])
            assert 0.0 <= c["cpu"] <= c["dur"] + 1e-3
        # a row on each of the k+m shards (a loaded host may hold one
        # peer for slow and ack without it)
        assert with_bytes >= 2
        assert inside_all == len(parts)          # and none outside a commit

    def test_trace_stop_over_the_admin_socket_shows_cpu_and_the_host(
            self, cluster, client, tmp_path):
        from ceph_tpu.utils.admin_socket import admin_command
        d = next(iter(cluster.osds.values()))
        out = str(tmp_path / "asok-host")
        assert admin_command(cluster.asok_path(d.name),
                             f"trace start {out}")["started"]
        for i in range(4):
            client.write({f"by-operator-{i}": b"o" * 3000})
        time.sleep(0.1)
        got = admin_command(cluster.asok_path(d.name), "trace stop")
        assert got["stopped"] is True and got["ops"] >= 4
        for row in got["stages"].values():
            assert {"count", "self_s", "self_ms_per_op", "cpu_s",
                    "cpu_ms_per_op"} <= set(row)
        assert got["stages"]["osd.op"]["cpu_s"] > 0
        assert got["stages"]["store.commit.wal"]["self_s"] > 0
        host = got["host"]
        assert {"seconds", "cores_busy", "user_s", "system_s",
                "minor_faults", "late_mean_ms", "late_p95_ms",
                "cpu_s_by_role", "native_cpu_s"} <= set(host)
        assert host["cores_busy"] > 0 and host["ticks"] >= 3
        assert {"shard", "msgr-r", "profiler", "hb"} <= set(
            host["cpu_s_by_role"])

    def test_host_crc32c_native_is_in_perf_dump(self, cluster, client):
        from ceph_tpu.utils.admin_socket import admin_command
        d = next(iter(cluster.osds.values()))
        try:
            from ceph_tpu.native import lib
            lib()
            want = 1
        except Exception:
            want = 0
        dump = admin_command(cluster.asok_path(d.name), "perf dump")
        assert dump["kv"]["host_crc32c_native"] == want
        schema = admin_command(cluster.asok_path(d.name), "perf schema")
        assert schema["kv"]["host_crc32c_native"]["kind"] == "gauge"

    def test_a_write_stages_each_shard_object_once(self, cluster, client):
        from ceph_tpu.utils.admin_socket import admin_command
        d = next(iter(cluster.osds.values()))

        def kv():
            return admin_command(cluster.asok_path(d.name), "perf dump")["kv"]

        before = kv()
        client.write({"staged-once": b"s" * 3000})
        after = kv()
        shards = sum(1 for x in cluster.osds.values()
                     for cid in x.store.list_collections()
                     if x.store.exists(cid, "staged-once"))
        assert shards >= 2
        # write + same-length truncate + hinfo: one extent a shard, the
        # truncate folded into it
        assert after["store_objects_staged"] \
            - before["store_objects_staged"] == shards
        assert after["store_byte_ops_folded"] \
            - before["store_byte_ops_folded"] == shards

    def test_a_sampler_s_pass_is_a_span_and_tags_nothing(self, cluster,
                                                         client, session):
        from ceph_tpu.utils import profiler
        samplers = {t.ident: t.name for t in threading.enumerate()
                    if t.name.startswith("profiler-")}
        assert samplers
        t0, recs = time.perf_counter(), []
        while not recs and time.perf_counter() - t0 < 5.0:
            time.sleep(0.05)
            recs = [r for r in span_log(since=t0)
                    if r["name"] == "profiler.sample"]
        assert recs and all(r["tid"] in samplers for r in recs)
        assert all(r["parent"] is None and r["cpu"] >= 0 for r in recs)
        assert is_span_declared("profiler.sample")
        # it samples every thread but its own, and is no category of
        # the profile it takes
        assert not any(tid in profiler._SPAN_CATS for tid in samplers)
        assert profiler.push_span(profiler.SAMPLE_SPAN) is False

    def test_every_thread_of_the_cluster_carries_its_role(self, cluster,
                                                          client):
        """One convention (utils/tracing.thread_role): the daemon is
        the dash-separated token with a dot, the role the others less
        their trailing digits. No `Thread-N`; the benchmark's own rule
        and its list of always-on planes hold to the program's names."""
        from bench import host_usage

        def of_the_program(t):
            fn = getattr(t, "_target", None) or getattr(t, "function", None)
            mod = getattr(fn, "__module__", None) or type(t).__module__
            return (mod or "").startswith("ceph_tpu")
        client.write({"threads": b"t" * 3000})
        mine = [t for t in threading.enumerate() if of_the_program(t)]
        assert len(mine) > 20
        assert [t.name for t in mine
                if re.match(r"Thread-\d+", t.name)] == []
        roles = {tracing.thread_role(t.name) for t in mine}
        assert {"shard", "msgr-r", "msgr-ack", "msgr-dispatch", "profiler",
                "hb", "asok"} <= roles
        assert not any("." in role for role in roles), roles
        for t in threading.enumerate():
            assert host_usage.role_of(t.name) == tracing.thread_role(t.name)
        assert set(host_usage.PLANE_ROLES) <= roles
        # every daemon has one of each plane
        daemons = len(cluster.osds) + len(cluster.mons)
        for role in host_usage.PLANE_ROLES:
            assert sum(tracing.thread_role(t.name) == role
                       for t in mine) == daemons, role


# -- a partial overwrite (PR 31) ------------------------------------------------

RMW_STAGES = ("ecbackend.rmw", "ecbackend.rmw.prefetch",
              "ecbackend.rmw.delta.stage", "ecbackend.rmw.delta.launch",
              "ecbackend.rmw.delta.fetch", "ecbackend.rmw.journal",
              "ecbackend.rmw.apply", "osd.persist_meta")


class TestOverwriteSpansAndCounters:
    """`Client.write_at` inside one stripe of a stored object: the
    parity-delta RMW on the fused device program (the module's cluster
    has the CPU backend's host shortcut off), the PG's metadata record
    on its apply round (PR 32)."""

    def test_no_session_a_write_at_logs_nothing(self, cluster, client):
        client.write({"over-dark": b"o" * 3000})
        t0 = time.perf_counter()
        client.write_at("over-dark", 40, b"n" * 100)
        assert client.read("over-dark")[:200] \
            == b"o" * 40 + b"n" * 100 + b"o" * 60
        assert _mine(t0) == {}

    @pytest.mark.parametrize("stage", RMW_STAGES)
    def test_a_traced_write_at_leaves_the_rmw_s_stage(self, cluster,
                                                      client, session,
                                                      stage):
        client.write({"over": b"o" * 3000})
        client.write_at("over", 8, b"w" * 16)    # the column's program
        t0 = time.perf_counter()
        before = {k: _ec(cluster, k) for k in
                  ("rmw_ops", "rmw_delta_launches",
                   "rmw_host_delta_launches", "rmw_full_fallbacks",
                   "meta_rides", "meta_persist_rounds")}
        client.write_at("over", 40, b"n" * 100)
        got = _mine(t0)
        assert {k: _ec(cluster, k) - v for k, v in before.items()} == {
            "rmw_ops": 1, "rmw_delta_launches": 1,
            "rmw_host_delta_launches": 0, "rmw_full_fallbacks": 0,
            "meta_rides": 1, "meta_persist_rounds": 0}
        assert is_span_declared(stage) and stage in got, sorted(got)
        assert "ecbackend.rmw.full" not in got
        for rec in got[stage]:
            assert -1e-9 <= rec["self"] <= rec["dur"] + 1e-9
        (rmw,), (op,) = got["ecbackend.rmw"], got["osd.op"]
        tid = got["client.op"][-1]["trace_id"]
        assert rmw["trace_id"] == op["trace_id"] == tid
        if stage == "ecbackend.rmw":
            # the six children and the metadata record's encode cover
            # it: its self time is what is left
            kids = sum(r["dur"] for name in RMW_STAGES[1:]
                       for r in got[name])
            assert rmw["self"] == pytest.approx(rmw["dur"] - kids)
            # the rows' delta and the pad to the bucket: two stage spans
            assert len(got["ecbackend.rmw.delta.stage"]) == 2
            # three rounds, one of each
            assert [len(got[f"ecbackend.rmw.{r}"]) for r in
                    ("prefetch", "journal", "apply")] == [1, 1, 1]
        elif stage == "osd.persist_meta":
            # one record: the encode, inside the RMW and before its
            # journal round; no send among its children, the apply
            # round carries it
            (meta,), (journal,) = got[stage], got["ecbackend.rmw.journal"]
            assert rmw["start"] <= meta["start"]
            assert meta["start"] + meta["dur"] <= journal["start"] + 1e-6
            assert meta["self"] == pytest.approx(meta["dur"])
            assert meta["trace_id"] == tid
        else:
            (rec,) = got[stage][-1:]
            assert rmw["start"] <= rec["start"]
            assert rec["start"] + rec["dur"] <= rmw["start"] + rmw["dur"] + 1e-6

    def test_a_write_the_delta_path_refuses_leaves_the_full_span(
            self, cluster, client, session):
        """3000 bytes over k=2 in 256-byte units: a write of 600 bytes
        spans a whole stripe, and the ladder shows where it happens."""
        client.write({"over-wide": b"o" * 3000})
        t0 = time.perf_counter()
        fallbacks = _ec(cluster, "rmw_full_fallbacks")
        client.write_at("over-wide", 100, b"n" * 600)
        got = _mine(t0)
        assert _ec(cluster, "rmw_full_fallbacks") == fallbacks + 1
        assert is_span_declared("ecbackend.rmw.full")
        assert len(got["ecbackend.rmw.full"]) == 1
        assert "ecbackend.rmw" not in got and "osd.persist_meta" in got


# -- a read that rebuilds a row ------------------------------------------------

@pytest.fixture(scope="module")
def degraded():
    """(cluster, client, name, bytes) of an object whose PG has lost a
    data slot: its holder killed, marked down by the admin `down`, and
    kept in by the interval."""
    from ceph_tpu.osd.standalone import StandaloneCluster
    c = StandaloneCluster(n_osds=4, pg_num=2, hb_interval=0.5,
                          hb_grace=30.0, down_out_interval=600.0)
    try:
        c.wait_for_clean(timeout=40)
        cl = c.client(hedge_delay_ms=-1)
        cl.trace_sample_rate = 0.0
        acting = [cl.osdmap.pg_to_up_acting_osds(1, ps)[2] for ps in (0, 1)]
        primaries = {a[0] for a in acting}
        ps, victim = next((ps, a[1]) for ps, a in enumerate(acting)
                          if a[1] not in primaries)   # data slot 1 of k=2
        name = next(f"lost-{i}" for i in range(64)
                    if cl.osdmap.object_to_pg(1, f"lost-{i}")[1] == ps)
        cl.write({name: b"r" * 3000})
        c.kill_osd(victim)
        cl.osd_down(victim)
        c._wait(lambda: all(not d.osdmap.osd_up[victim]
                            for d in c.osds.values()
                            if not d._stop.is_set()), 15, "maps show down")
        assert cl.read(name) == b"r" * 3000   # the pattern's program
        yield c, cl, name, b"r" * 3000
    finally:
        c.shutdown()


class TestDegradedReadSpansAndCounters:
    """One case a span and a counter, beside `verify_time`'s and
    `open_time`'s above."""

    @pytest.mark.parametrize("stage", ["stage", "launch", "fetch"])
    def test_a_degraded_read_leaves_the_decode_s_stage(self, degraded,
                                                       session, stage):
        _, client, name, want = degraded
        t0 = time.perf_counter()
        assert client.read(name) == want
        got = _mine(t0)
        kid = f"ecbackend.read.decode.{stage}"
        assert is_span_declared(kid)
        (decode,), (rec,) = got["ecbackend.read.decode"], got[kid]
        assert decode["start"] <= rec["start"]
        assert rec["start"] + rec["dur"] \
            <= decode["start"] + decode["dur"] + 1e-6
        kids = sum(got[f"ecbackend.read.decode.{k}"][0]["dur"]
                   for k in ("stage", "launch", "fetch"))
        assert decode["self"] == pytest.approx(decode["dur"] - kids)

    @pytest.mark.parametrize("key,rise", [("degraded_reads", 1),
                                          ("decode_rows_rebuilt", 1),
                                          ("decode_bytes_rebuilt", 1536)])
    def test_a_degraded_read_feeds_its_counter(self, degraded, key, rise):
        """3000 bytes over k=2 in 256-byte units: one 1536-byte row
        rebuilt, on the device."""
        cluster, client, name, want = degraded
        keys = (key, "decode_launches", "decode_time",
                "host_decode_launches")

        def read():
            return {k: sum(d.ec_perf.get(k)["count"] if k == "decode_time"
                           else int(d.ec_perf.get(k))
                           for d in cluster.osds.values()
                           if not d._stop.is_set()) for k in keys}
        before = read()
        assert client.read(name) == want
        after = read()
        assert after[key] == before[key] + rise
        assert after["decode_launches"] == before["decode_launches"] + 1
        assert after["decode_time"] == before["decode_time"] + 1
        assert after["host_decode_launches"] == before["host_decode_launches"]


# -- a read's gather: one overlapped round (PR 28) ------------------------------

# -- a backfill -----------------------------------------------------------

@pytest.fixture(scope="module")
def backfill_log(tmp_path_factory):
    """The span records of one backfill under a live session: a pool of
    its own, degraded as `degraded` is, its victim then marked out and
    the pool run to clean."""
    from ceph_tpu.osd.standalone import StandaloneCluster
    c = StandaloneCluster(n_osds=4, pg_num=2, hb_interval=0.5,
                          hb_grace=30.0, down_out_interval=600.0)
    try:
        c.wait_for_clean(timeout=40)
        cl = c.client(hedge_delay_ms=-1)
        cl.trace_sample_rate = 0.0
        acting = [cl.osdmap.pg_to_up_acting_osds(1, ps)[2] for ps in (0, 1)]
        primaries = {a[0] for a in acting}
        victim = next(a[1] for a in acting if a[1] not in primaries)
        objs = {f"bf-{i}": bytes([i]) * 3000 for i in range(6)}
        cl.write(objs)
        c.kill_osd(victim)
        cl.osd_down(victim)
        c._wait(lambda: all(not d.osdmap.osd_up[victim]
                            for d in c.osds.values()
                            if not d._stop.is_set()), 15, "maps show down")
        assert start_trace(str(tmp_path_factory.mktemp("backfill-trace")))
        try:
            t0 = time.perf_counter()
            cl.osd_out(victim)
            c._wait(lambda: all(d.osdmap.osd_weight[victim] == 0
                                for d in c.osds.values()
                                if not d._stop.is_set()), 15,
                    "maps show out")
            c.wait_for_clean(timeout=60)
        finally:
            table = stop_trace()
        for name, want in objs.items():
            assert cl.read(name) == want
        yield _mine(t0), table
    finally:
        c.shutdown()


GRANT_KIDS = ("pull", "stage", "launch", "fetch", "push", "settle")


class TestBackfillSpans:
    @pytest.mark.parametrize("kid", GRANT_KIDS)
    def test_a_grant_holds_its_child(self, backfill_log, kid):
        got, table = backfill_log
        name = f"recovery.{kid}"
        assert is_span_declared(name) and is_span_declared("recovery.grant")
        assert name in table["stages"]
        grants = got["recovery.grant"]
        for rec in got[name]:
            assert 0 <= rec["self"] <= rec["dur"] + 1e-9
            assert any(g["start"] <= rec["start"] and rec["start"]
                       + rec["dur"] <= g["start"] + g["dur"] + 1e-6
                       for g in grants), name

    def test_a_grant_s_self_time_is_what_its_children_leave(self,
                                                            backfill_log):
        got, _ = backfill_log
        grants = got["recovery.grant"]
        kids = sum(r["dur"] for kid in GRANT_KIDS
                   for r in got[f"recovery.{kid}"])
        assert sum(g["self"] for g in grants) == pytest.approx(
            sum(g["dur"] for g in grants) - kids, abs=1e-3)
        # a launching grant says what it is to stage; the launch what it
        # staged: one 1536-byte row of k=2 an object
        staged = sum(r["nbytes"] for r in got["recovery.launch"])
        assert staged > 0 and staged % (2 * 1536) == 0
        assert sum(g["nbytes"] for g in grants) >= staged

    def test_a_launch_carries_its_objects_and_helper_reads(self,
                                                          backfill_log):
        """`recover_helper_reads`, as each launch's record carries its
        part: k = 2 helper rows an object of this pool."""
        got, _ = backfill_log
        tags = [r["tags"] for r in got["recovery.launch"]]
        objects = sum(t["objects"] for t in tags)
        assert objects == sum(r["nbytes"] for r in got["recovery.launch"]
                              ) // (2 * 1536) >= 1
        assert sum(t["recover_helper_reads"] for t in tags) == 2 * objects

    def test_the_reservation_s_wait_is_logged_once_a_pg(self, backfill_log):
        got, _ = backfill_log
        waits = got["recovery.reserve.wait"]
        assert is_span_declared("recovery.reserve.wait")
        assert 1 <= len(waits) <= 2            # the pool has two PGs
        assert all(w["dur"] >= 0 and w["self"] == w["dur"] for w in waits)


@pytest.fixture(scope="module")
def clay_serve_log(tmp_path_factory):
    """The span records and `ec` counters of one Clay backfill under a
    live session: a k=4 m=2 d=5 pool on 7 OSDs, a non-primary marked
    down and out, the lost rows rebuilt from ranges of d helpers."""
    from ceph_tpu.osd.standalone import StandaloneCluster
    c = StandaloneCluster(n_osds=7, pg_num=2, hb_interval=0.5,
                          hb_grace=30.0, down_out_interval=600.0,
                          profile="plugin=clay k=4 m=2 d=5")
    try:
        c.wait_for_clean(timeout=40)
        cl = c.client(hedge_delay_ms=-1)
        cl.trace_sample_rate = 0.0
        acting = [cl.osdmap.pg_to_up_acting_osds(1, ps)[2] for ps in (0, 1)]
        primaries = {a[0] for a in acting}
        victim = next(o for a in acting for o in a[1:4]
                      if o not in primaries)
        objs = {f"clay-{i}": bytes([i + 1]) * 4096 for i in range(6)}
        cl.write(objs)
        c.kill_osd(victim)
        cl.osd_down(victim)
        c._wait(lambda: all(not d.osdmap.osd_up[victim]
                            for d in c.osds.values()
                            if not d._stop.is_set()), 15, "maps show down")
        keys = ("recover_range_frames_served", "recover_range_bytes_served",
                "recover_range_verify_bytes", "recovered_objects")

        def ec():
            return {k: sum(int(d.ec_perf.get(k)) for d in c.osds.values()
                           if not d._stop.is_set()) for k in keys}
        before = ec()
        assert start_trace(str(tmp_path_factory.mktemp("clay-trace")))
        try:
            t0 = time.perf_counter()
            cl.osd_out(victim)
            c._wait(lambda: all(d.osdmap.osd_weight[victim] == 0
                                for d in c.osds.values()
                                if not d._stop.is_set()), 15,
                    "maps show out")
            c.wait_for_clean(timeout=60)
        finally:
            table = stop_trace()
        rise = {k: v - before[k] for k, v in ec().items()}
        for name, want in objs.items():
            assert cl.read(name) == want
        yield _mine(t0), table, rise
    finally:
        c.shutdown()


class TestClaySourceSpans:
    """The source half of a sub-chunk pull: `recovery.serve_ranges` round
    each frame a helper serves, its parts beside it, and the three `ec`
    counters of what the frames shipped and checked."""

    @pytest.mark.parametrize("part", ["read", "verify", "slice"])
    def test_a_serve_holds_its_part(self, clay_serve_log, part):
        got, table, _ = clay_serve_log
        name = f"recovery.serve_ranges.{part}"
        assert is_span_declared(name)
        assert name in table["stages"]
        serves = got["recovery.serve_ranges"]
        for rec in got[name]:
            assert rec.get("detail") is True
            assert any(s["start"] <= rec["start"] and rec["start"]
                       + rec["dur"] <= s["start"] + s["dur"] + 1e-6
                       for s in serves)

    def test_the_serves_carry_the_bytes_they_shipped(self, clay_serve_log):
        got, table, rise = clay_serve_log
        serves = got["recovery.serve_ranges"]
        assert is_span_declared("recovery.serve_ranges")
        assert "recovery.serve_ranges" in table["stages"]
        assert len(serves) == rise["recover_range_frames_served"] >= 5
        assert sum(s["nbytes"] for s in serves) \
            == rise["recover_range_bytes_served"]
        # d = 5 helpers ship half a 1024-byte row (q = 2) an object, and
        # check the whole row first
        assert rise["recover_range_bytes_served"] \
            == rise["recovered_objects"] * 5 * 512 > 0
        assert rise["recover_range_verify_bytes"] \
            == 2 * rise["recover_range_bytes_served"]


@pytest.fixture
def served_kinds(monkeypatch):
    """kind -> store sub-ops the OSDs served since the fixture."""
    from ceph_tpu.osd.standalone import OSDDaemon
    served: dict = {}
    keep = OSDDaemon._store_op

    def counted(self, kind, body):
        served[kind] = served.get(kind, 0) + 1
        return keep(self, kind, body)
    monkeypatch.setattr(OSDDaemon, "_store_op", counted)
    return served


def _placed(cluster, cl, name):
    """(primary daemon, acting, pg seed) of `name`'s PG."""
    ps = cl.osdmap.object_to_pg(1, name)[1]
    acting = cl.osdmap.pg_to_up_acting_osds(1, ps)[2]
    return cluster.osds[acting[0]], acting, ps


def _quiet_client(cluster):
    cl = cluster.client(hedge_delay_ms=-1)   # one view a read, no hedge
    cl.trace_sample_rate = 0.0
    return cl


class TestLiveGather:
    @pytest.mark.parametrize("pool", ["healthy", "degraded"])
    def test_a_read_is_one_readv_a_remote_slot_and_no_getattr(
            self, request, pool, served_kinds, session):
        """k=2 m=1: the primary holds data slot 0 itself and asks one
        remote store, for slot 1 or, with that one's holder down, for
        the parity slot: one `readv` frame a read, rows and hinfo in
        it; no `read`, no `getattr`; one gather span a plan."""
        if pool == "healthy":
            cluster = request.getfixturevalue("cluster")
            request.getfixturevalue("client")
            cl, want = _quiet_client(cluster), b"g" * 3000
            names = [f"gather-{i}" for i in range(3)]
            cl.write({n: want for n in names})
        else:
            cluster, cl, name, want = request.getfixturevalue("degraded")
            names = [name] * 3
        keys = ("gather_rounds", "gather_frames", "degraded_reads")

        def counters():
            return [sum(int(d.ec_perf.get(k)) for d in cluster.osds.values()
                        if not d._stop.is_set()) for k in keys]
        before = counters()
        served_kinds.clear()
        t0 = time.perf_counter()
        for n in names:
            assert cl.read(n) == want
        got = _mine(t0)
        assert served_kinds.get("readv") == 3
        assert "getattr" not in served_kinds and "read" not in served_kinds
        rise = [a - b for a, b in zip(counters(), before)]
        assert rise == [3, 3, 3 if pool == "degraded" else 0]
        # and `perf dump` shows them
        from ceph_tpu.utils.admin_socket import admin_command
        dumped = [admin_command(cluster.asok_path(d.name), "perf dump")["ec"]
                  for d in cluster.osds.values() if not d._stop.is_set()]
        assert [sum(int(e[k]) for e in dumped) for k in keys] == counters()
        assert len(got["ecbackend.read.gather"]) == 3
        if pool == "healthy":                # TinStore: 2 rows a read
            assert len(got["store.read"]) == 6

    def test_a_slot_whose_store_lacks_the_object_is_planned_around(
            self, cluster, client, served_kinds):
        from ceph_tpu.osd.memstore import Transaction
        from ceph_tpu.osd.pgbackend import shard_cid
        cl, want = _quiet_client(cluster), os.urandom(3000)
        cl.write({"unlanded": want})
        primary, acting, ps = _placed(cluster, cl, "unlanded")
        cluster.osds[acting[1]].store.queue_transaction(
            Transaction().remove(shard_cid(f"1.{ps}", 1), "unlanded"))
        before = {k: int(primary.ec_perf.get(k)) for k in
                  ("gather_rounds", "gather_frames", "degraded_reads")}
        served_kinds.clear()
        assert cl.read("unlanded") == want
        rise = {k: int(primary.ec_perf.get(k)) - v
                for k, v in before.items()}
        assert rise == {"gather_rounds": 2, "gather_frames": 2,
                        "degraded_reads": 1}
        assert served_kinds.get("readv") == 2
        assert "getattr" not in served_kinds and "read" not in served_kinds
        # no handle of either round is left in flight
        assert int(primary.rpc.perf.get("inflight_ops")) == 0
        assert not primary.rpc._pending

    @pytest.mark.parametrize("repair", [True, False])
    def test_a_rotten_row_still_takes_the_eio_path(self, cluster, client,
                                                   repair):
        """The stored crc comes with the row now; the row is still
        checked against it before a byte goes back, and a view that
        may not repair writes nothing."""
        from ceph_tpu.osd.memstore import Transaction
        from ceph_tpu.osd.pgbackend import shard_cid
        cl, want = _quiet_client(cluster), os.urandom(3000)
        name = f"rot-{int(repair)}"
        cl.write({name: want})
        primary, acting, ps = _placed(cluster, cl, name)
        st, cid = cluster.osds[acting[1]].store, shard_cid(f"1.{ps}", 1)
        good = st.read(cid, name).tobytes()
        flipped = bytes(b ^ 0xFF for b in good[9:11])
        st.queue_transaction(Transaction().write(cid, name, 9, flipped))
        rotten = st.read(cid, name).tobytes()
        assert rotten != good
        eio = int(primary.ec_perf.get("read_eio"))
        if repair:
            assert cl.read(name) == want
        else:
            be = primary.backends[ps]
            assert be.read_objects([name], repair=False)[name].tobytes() \
                == want
        assert int(primary.ec_perf.get("read_eio")) == eio + 1
        assert st.read(cid, name).tobytes() == (good if repair else rotten)

    def test_pipelined_reads_alone_move_the_peer_latency_ewma(
            self, cluster, client, monkeypatch):
        from ceph_tpu.osd.standalone import RemoteStore
        cl, want = _quiet_client(cluster), b"e" * 3000
        cl.write({"ewma": want})
        primary, acting, _ = _placed(cluster, cl, "ewma")
        called = []
        keep = RemoteStore._call

        def call(self, kind, body=b""):
            if self._on_latency == primary._note_peer_latency \
                    and self._peer == f"osd.{acting[1]}":
                called.append(kind)
            return keep(self, kind, body)
        monkeypatch.setattr(RemoteStore, "_call", call)
        primary._peer_lat.pop(acting[1], None)
        assert cl.read("ewma") == want
        first = primary._peer_lat[acting[1]]
        assert 0 < first < 5
        assert cl.read("ewma") == want
        assert primary._peer_lat[acting[1]] != first    # blended again
        assert called == []              # no blocking call fed it

    def test_a_handle_reports_submit_to_reply_not_to_collection(self):
        """The round trip a pipelined read reports ends when the reply
        lands, however late the caller collects it."""
        from ceph_tpu.osd.standalone import (MStoreReply, RemoteStore,
                                             _PendingCall)
        from ceph_tpu.utils.encoding import Encoder

        class Rpc:
            def submit(self, peer, make_msg):
                self.ent = _PendingCall(self, 1, peer, 0)
                self.msg = make_msg(1)
                return self.ent

            def _retire(self, ent):
                pass
        rpc, seen = Rpc(), []
        rs = RemoteStore(rpc, "osd.3", on_latency=lambda p, dt:
                         seen.append((p, dt)))
        handle = rs.readv_submit("1.0s1", ["a", "b"], 4, "hinfo")
        assert rpc.msg.kind == "readv" and seen == []
        body = Encoder().blob(b"aaaabbbb").list([b"x", b"y"], Encoder.blob)
        rpc.ent._replies.append(MStoreReply(1, True, "readv", body.bytes()))
        rpc.ent._notify()
        time.sleep(0.08)
        assert handle.result() == (b"aaaabbbb", [b"x", b"y"])
        ((peer, dt),) = seen
        assert peer == "osd.3" and 0 <= dt < 0.06
