"""Share of the measured window in which no operation ran on the device,
in %: 1 - (union of device-op intervals / window), from the trace."""


def read(rec):
    red = rec.get("trace") or {}
    if not red.get("window_s") or red.get("busy_s") is None:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
