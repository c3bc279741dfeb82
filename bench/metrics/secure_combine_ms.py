"""secure_combine_ms: device time per round of the secure-aggregation
kernel (``kernels/secure_agg.masked_sum_2d``) on the first chip."""
import devtrace

# the Pallas call of kernels/secure_agg.masked_sum_2d
PATTERN = r"^%masked_sum_2d(\.\d+)? = "


def read(ctx):
    ev = devtrace.matching(
        ctx["trace"]["devices"].get(ctx["device_ids"][0], []), PATTERN)
    if not ev:
        return None
    return devtrace.total_ns(ev) * 1e-6 / ctx["trace_rounds"]
