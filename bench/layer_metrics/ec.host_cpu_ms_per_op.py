"""The CPU time of `ec.host_ms_per_op`'s spans a client op: what the EC
backend's threads ran of that host work, where the other reads what they
ran or waited. `NAMES` is that metric's, copied (its file is not this
PR's to import from by a dotted name; a test holds the two equal)."""

from bench.host_usage import cpu_ms_per_op

META = {"layer": "EC backend", "source": "program_span",
        "moves": "client_mb_s"}
NAMES = ("ecbackend.write.stripe", "ecbackend.write.stage",
         "ecbackend.write.launch", "ecbackend.write.txns",
         "ecbackend.read.gather", "ecbackend.read.verify.stage",
         "ecbackend.read.verify.launch", "ecbackend.read.decode",
         "ecbackend.read.unstripe")


def compute(run: dict) -> float | None:
    return cpu_ms_per_op(run, NAMES)
