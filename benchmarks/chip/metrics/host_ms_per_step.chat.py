"""Host work each streamed token waits on, in milliseconds: the median,
over the traced slice's decode steps, of the engine's ``engine.step``
span less its blocking waits on the device (``engine.token_pull``,
``engine.first_token_pull``, ``engine.admit_sync``)."""

from chipbench import host_spans


def read(rec):
    tr = rec["trace"]
    if tr is None:
        return None
    return host_spans.host_ms_per_step(tr["host_spans"])
