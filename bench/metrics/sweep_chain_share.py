"""Share of Algorithm 3's SFTD sweeps (``core.budget._bulk_sweep``) that
took the exact chain rather than the per-pass loop: ``sweeps_chain`` /
(``sweeps_chain`` + ``sweeps_loop``) of the members' profile blocks
(``profile=True`` in the traced run).  A program that does not count its
sweeps, or ran none, has nothing to read."""


def read(record):
    p = record["profile"]
    total = p.get("sweeps_chain", 0) + p.get("sweeps_loop", 0)
    if not total:
        return None
    return 100.0 * p["sweeps_chain"] / total
