"""What a thread that becomes runnable pays to run again: the mean of the
probe's `late` (seconds past its 10 ms sleep, every tick of the traced
seconds), in ms. The interpreter's hand-off plus the OS's wake-up; on an
idle host the latter alone, tens of microseconds."""

from bench.host_usage import late_ms

META = {"layer": "host", "source": "program_span",
        "moves": "op_p95_ms"}


def compute(run: dict) -> float | None:
    return late_ms(run)
