"""Data loader (``data/loader.DataLoader``): host milliseconds per batch,
timed by the harness around the loader's ``next`` inside the window."""


def read(cell):
    times = cell.layer.get("loader_times")
    if not times:
        return None
    return 1e3 * sum(b - a for a, b in times) / len(times)
