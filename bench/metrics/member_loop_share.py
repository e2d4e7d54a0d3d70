"""Share of the window's timed seconds in the grid engine's member loop
(``core.jax_engine.BatchSimEngine.run``): resuming the parked members
(``repro.round.members``, which holds the members that never park), the
parked cycles run per task (``repro.round.serial``) and the commit of
auction placements (``repro.round.apply``), from the engines' profile
blocks (``profile=True`` in the traced run)."""

PHASES = ("round.members_s", "round.serial_s", "round.apply_s")


def read(record):
    p = record["profile"]
    if not all(k in p for k in PHASES) or not record["timed_s"]:
        return None
    return 100.0 * sum(p[k] for k in PHASES) / record["timed_s"]
