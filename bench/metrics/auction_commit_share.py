"""Share of the window's timed seconds in the auction's commits
(``CycleRequest.commit``, ``repro.auction.commit``) and its serial tails
(``CycleRequest.finish_serial``, ``repro.auction.tail``), whose per-task
``select`` calls ``host_select_share`` leaves out, from the engines'
profile blocks (``profile=True`` in the traced run)."""

PHASES = ("auction.commit_s", "auction.tail_s")


def read(record):
    p = record["profile"]
    if not all(k in p for k in PHASES) or not record["timed_s"]:
        return None
    return 100.0 * sum(p[k] for k in PHASES) / record["timed_s"]
