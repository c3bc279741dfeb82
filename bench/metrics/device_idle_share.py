"""device_idle_share: 1 - (union of device-op intervals / traced
window), averaged over the cell's chips."""
import devtrace


def read(ctx):
    ids = ctx["device_ids"]
    busy = sum(devtrace.busy_ns(ctx["trace"]["devices"].get(i, []))
               for i in ids) / len(ids) * 1e-9
    if busy <= 0:
        return None
    return 100.0 * max(0.0, 1.0 - busy / ctx["window_s"])
