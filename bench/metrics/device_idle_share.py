"""Share of the traced episode in which no operation ran on the device:
1 - (union of device-op intervals) / (episode span)."""


def read(record):
    tr = record["trace"]
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
