"""Microseconds of host time per pair of the rendezvous rounds that rode
the kernel: the batched auction's phases (``repro.auction.build``,
``.stage``, ``.dispatch``, ``.pull``, ``.commit``, ``.tail`` of
``core.jax_cycles.multi_cycle``, and ``repro.round.apply``) over
``ridden_pairs``, from the engines' profile blocks (``profile=True`` in
the traced run)."""

PHASES = ("auction.build_s", "auction.stage_s", "auction.dispatch_s",
          "auction.pull_s", "auction.commit_s", "auction.tail_s",
          "round.apply_s")


def read(record):
    p = record["profile"]
    if any(k not in p for k in PHASES) or not p.get("ridden_pairs"):
        return None
    return 1e6 * sum(p[k] for k in PHASES) / p["ridden_pairs"]
