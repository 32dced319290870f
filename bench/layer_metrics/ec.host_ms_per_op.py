"""The EC backend's own host work a client op: striping, padding to the
batch bucket, the launch call and the per-shard transactions of a write;
gathering the rows, staging and launching their crc, the decode and the
un-striping of a read. The wait for the device is `ec.device_wait_ms_per_op`."""

from bench.span_stages import self_ms_per_op

META = {"layer": "EC backend", "source": "program_span",
        "moves": "client_mb_s"}
NAMES = ("ecbackend.write.stripe", "ecbackend.write.stage",
         "ecbackend.write.launch", "ecbackend.write.txns",
         "ecbackend.read.gather", "ecbackend.read.verify.stage",
         "ecbackend.read.verify.launch", "ecbackend.read.decode",
         "ecbackend.read.unstripe")


def compute(run: dict) -> float | None:
    return self_ms_per_op(run, NAMES)
