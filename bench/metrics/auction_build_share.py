"""Share of the window's timed seconds in building the batched auction's
requests: the drain of each riding member's queue and its pair arrays
(``core.jax_cycles.build_pair_arrays`` through ``CycleRequest``), the
``repro.auction.build`` phase of the engines' profile blocks
(``profile=True`` in the traced run)."""


def read(record):
    p = record["profile"]
    if "auction.build_s" not in p or not record["timed_s"]:
        return None
    return 100.0 * p["auction.build_s"] / record["timed_s"]
