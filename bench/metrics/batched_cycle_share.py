"""Share of parked member-cycles that the grid driver
(``core.jax_engine.BatchSimEngine``) sent to the batched auction, from
``dispatch_stats()``: batched / (batched + serial), over the window."""


def read(record):
    d = record["dispatch"]
    total = d["batched_cycles"] + d["serial_cycles"]
    return 100.0 * d["batched_cycles"] / total if total else None
