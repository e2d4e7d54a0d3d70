"""Backend compiles inside the timed part of the window (JAX's
compile-duration events), e.g. a round-buffer bucket the warm-up episode
served from a larger resident bucket and never compiled."""


def read(record):
    return float(record["window_compiles"])
