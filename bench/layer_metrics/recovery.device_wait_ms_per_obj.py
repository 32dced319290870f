"""Recovery's wait for the device a rebuilt object: the self time of
`recovery.fetch` (`jax.device_get` of the rebuilt rows, their crcs and
the helpers' verdict; the copy home was started at the launch, one grant
ahead) over the objects whose decode was launched in the traced
seconds."""

from bench.recovery_stages import FETCH, self_ms_per_object

META = {"layer": "recovery", "source": "program_span",
        "moves": "client_mb_s"}


def compute(run: dict) -> float | None:
    return self_ms_per_object(run, (FETCH,))
