"""Share of the window's timed seconds spent in Algorithm 3
(``core.budget`` redistribution), from the ``REPRO_PROFILE`` counters of
``core.engine.SimState`` (``profile=True`` in the traced run)."""


def read(record):
    p = record["profile"]
    if "redistribute_s" not in p or not record["timed_s"]:
        return None
    return 100.0 * p["redistribute_s"] / record["timed_s"]
