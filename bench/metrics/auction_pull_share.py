"""Share of the window's timed seconds in pulling the kernel's four
outputs in ``core.jax_cycles.multi_cycle`` (``np.asarray``): the wait for
the device and the device-to-host copy, the ``repro.auction.pull`` phase
of the engines' profile blocks (``profile=True`` in the traced run)."""


def read(record):
    p = record["profile"]
    if "auction.pull_s" not in p or not record["timed_s"]:
        return None
    return 100.0 * p["auction.pull_s"] / record["timed_s"]
