"""device_idle_share: percent of the traced window of rounds in which no
operation ran on the device (1 - union of device-op intervals / window),
averaged over the chips used."""


def read(run):
    if not run.get("window_s") or not run.get("busy_s"):
        return None
    return 100.0 * (1.0 - run["busy_s"] / run["window_s"])
