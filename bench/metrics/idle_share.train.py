"""Device: the share of the traced window in which no operation ran on
the chips (1 - busy over window, averaged over the chips), in percent."""


def read(rec):
    t = rec["trace"]
    if rec["peaks"] is None or not t or not t["device_planes"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
