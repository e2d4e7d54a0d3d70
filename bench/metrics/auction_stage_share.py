"""Share of the window's timed seconds in staging each kernel round of
``core.jax_cycles.multi_cycle``: the reset of the resident round buffers
and every member's proposal written into them
(``CycleRequest.propose_into``), the ``repro.auction.stage`` phase of the
engines' profile blocks (``profile=True`` in the traced run)."""


def read(record):
    p = record["profile"]
    if "auction.stage_s" not in p or not record["timed_s"]:
        return None
    return 100.0 * p["auction.stage_s"] / record["timed_s"]
