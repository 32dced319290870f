"""Recovery's own host work a rebuilt object: the self time of
`recovery.grant` and of every span inside it but `recovery.fetch`
(`.pull`: the helper rows read over the messenger into the stage buffer;
`.stage`; `.launch`: the dispatch with its copy to the device; `.push`:
the rebuilt rows sent to the new member; `.settle`), over the objects
whose decode was launched in the traced seconds."""

from bench.recovery_stages import HOST, self_ms_per_object

META = {"layer": "recovery", "source": "program_span",
        "moves": "client_mb_s"}


def compute(run: dict) -> float | None:
    return self_ms_per_object(run, HOST)
