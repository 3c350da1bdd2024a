"""Device, training: 1 - (union of the device's op intervals / traced
window), from the device trace."""


def read(run):
    red = run["reduction"]
    return 100.0 * red.idle_share if red.window_s > 0 else None
