"""Share of the traced window in which no operation ran on the device."""


def read(rec):
    t = rec.trace
    if not t or not t["window_s"]:
        return None
    return (1 - t["busy_s"] / t["window_s"]) * 100
