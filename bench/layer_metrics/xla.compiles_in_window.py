"""Programs compiled, or loaded from the compile cache, inside the window
by any layer: the `xla.compile` records of the program's span log, which
one `jax.monitoring` listener writes for every jitted program, the read
path's too. Should read 0."""

from bench.span_stages import tracing

META = {"layer": "device programs", "source": "program_counter",
        "moves": "op_p95_ms"}


def compute(run: dict) -> float | None:
    module = tracing()
    if module is None or not run.get("trace"):
        return None
    return sum(1 for r in module.span_log(since=run["t0"], until=run["t1"])
               if r["name"] == "xla.compile")
