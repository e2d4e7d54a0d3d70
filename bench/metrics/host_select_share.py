"""Share of the window's timed seconds spent in the host's per-task
``core.scheduler.select`` calls, from the ``REPRO_PROFILE`` counters of
``core.engine.SimState`` (``profile=True`` in the traced run)."""


def read(record):
    p = record["profile"]
    if "select_s" not in p or not record["timed_s"]:
        return None
    return 100.0 * p["select_s"] / record["timed_s"]
