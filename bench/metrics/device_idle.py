"""Device: share of the traced window in which no operation ran on the
chip (1 - union of the device's op intervals / window), averaged over the
chips used. Reads every ``device_idle.<cell kind>`` metric."""


def read(cell):
    if cell.reduced is None:
        return None
    idle = cell.reduced.idle_share()
    return None if idle is None else 100.0 * idle
