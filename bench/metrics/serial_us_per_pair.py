"""Microseconds of host time per pair of the rendezvous rounds kept off
the kernel: the ``repro.round.serial`` phase (parked cycles run per task
by ``core.engine.SimState.sequential_cycle``) less its cycles with no
idle VM (``zero_pair_s``), over ``parked_pairs``, from the engines'
profile blocks (``profile=True`` in the traced run)."""


def read(record):
    p = record["profile"]
    if "round.serial_s" not in p or not p.get("parked_pairs"):
        return None
    return 1e6 * (p["round.serial_s"] - p["zero_pair_s"]) / p["parked_pairs"]
