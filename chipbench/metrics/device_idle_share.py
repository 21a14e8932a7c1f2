"""device_idle_share: percent of the traced window in which no operation
ran on the device (1 - union of op intervals / window), worst device.
Moves ``tokens_per_s``."""


def read(run):
    share = (run.trace or {}).get("idle_share_worst")
    return None if share is None else 100.0 * share
