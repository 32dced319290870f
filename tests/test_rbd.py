"""librbd-shaped image API tests (ref: src/librbd/ Image semantics;
src/pybind/rbd/rbd.pyx surface)."""

import numpy as np
import pytest

from ceph_tpu.client.rados import Rados
from ceph_tpu.client.rbd import RBD, Image
from cluster_helpers import make_cluster


def make_rbd(**kw):
    c = make_cluster(**kw)
    io = Rados(c).open_ioctx()
    return c, RBD(io, stripe_unit=4096, stripe_count=4,
                  object_size=16384)


class TestImageLifecycle:
    def test_create_list_remove(self):
        c, rbd = make_rbd()
        rbd.create("vm1", 1 << 20)
        rbd.create("vm2", 1 << 16)
        assert rbd.list() == ["vm1", "vm2"]
        with pytest.raises(FileExistsError):
            rbd.create("vm1", 1)
        rbd.remove("vm1")
        assert rbd.list() == ["vm2"]
        with pytest.raises(KeyError):
            Image(rbd, "vm1")

    def test_block_device_io(self):
        c, rbd = make_rbd()
        img = rbd.create("disk", 200_000)
        rng = np.random.default_rng(0)
        # sparse image: unwritten regions read as zeros
        assert img.read(0, 512) == b"\x00" * 512
        blob = rng.integers(0, 256, 50_000, np.uint8).tobytes()
        img.write(10_000, blob)
        assert img.read(10_000, 50_000) == blob
        assert img.read(9_000, 2_000) == b"\x00" * 1_000 + blob[:1_000]
        # read past EOF truncates like a block device's size
        tail = img.read(199_000, 5_000)
        assert len(tail) == 1_000

    def test_bounds_enforced(self):
        c, rbd = make_rbd()
        img = rbd.create("small", 1_000)
        with pytest.raises(ValueError):
            img.write(900, b"x" * 200)
        with pytest.raises(ValueError):
            img.write(-1, b"x")
        with pytest.raises(ValueError):
            img.read(2_000, 10)

    def test_resize_grow_and_shrink(self):
        c, rbd = make_rbd()
        img = rbd.create("vol", 10_000)
        img.write(0, b"A" * 10_000)
        img.resize(20_000)
        img.write(15_000, b"B" * 5_000)
        assert img.read(15_000, 5_000) == b"B" * 5_000
        img.resize(5_000)
        assert img.size() == 5_000
        assert img.read(0, 10_000) == b"A" * 5_000  # truncated view
        with pytest.raises(ValueError):
            img.write(5_000, b"x")

    def test_image_survives_osd_loss(self):
        c, rbd = make_rbd(down_out_interval=60.0)
        img = rbd.create("durable", 100_000)
        rng = np.random.default_rng(1)
        blob = rng.integers(0, 256, 100_000, np.uint8).tobytes()
        img.write(0, blob)
        c.kill_osd(c.pgs[0].acting[0])
        c.tick(30)
        c.tick(90)
        for _ in range(60):
            if not c.backfills:
                break
            c.tick(6)
        assert img.read(0, 100_000) == blob


def test_shrink_then_regrow_reads_zeros():
    # regression: shrink must DISCARD bytes, not just move the size
    # header — a re-grown region reads zeros, never resurrected data
    c, rbd = make_rbd()
    img = rbd.create("vol2", 10_000)
    img.write(0, b"A" * 10_000)
    img.resize(5_000)
    img.resize(10_000)
    assert img.read(5_000, 5_000) == b"\x00" * 5_000
    assert img.read(0, 5_000) == b"A" * 5_000


@pytest.mark.slow   # ~15 s CLI bench smoke; nightly (r10 cap fix)
def test_rbd_bench_cli_smoke(tmp_path):
    """`rbd bench` (ref: src/tools/rbd/action/Bench.cc) emits sane
    JSON for both io types through the saved-state CLI."""
    import json
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    state = str(tmp_path / "st")
    run = lambda *args: subprocess.run(
        [sys.executable, "tools/rbd_cli.py", "--state", state, *args],
        capture_output=True, text=True, timeout=300, env=env, cwd=repo)
    r = run("create", "img", "--size", "2M")
    assert r.returncode == 0, r.stderr[-300:]
    for io_type in ("write", "read"):
        r = run("bench", "img", "--io-type", io_type,
                "--io-size", "64K", "--io-total", "512K")
        assert r.returncode == 0, r.stderr[-300:]
        d = json.loads(r.stdout.strip().splitlines()[-1])
        assert d["ios"] == 8 and d["iops"] > 0
