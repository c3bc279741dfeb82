"""server_step_ms: device time per round of the fused SSCA server step
(``kernels/ssca_update.ssca_update_2d``, one call per parameter leaf) on
the first chip."""
import devtrace

# the Pallas calls of kernels/ssca_update.ssca_update_2d
PATTERN = r"^%ssca_update_2d(\.\d+)? = "


def read(ctx):
    ev = devtrace.matching(
        ctx["trace"]["devices"].get(ctx["device_ids"][0], []), PATTERN)
    if not ev:
        return None
    return devtrace.total_ns(ev) * 1e-6 / ctx["trace_rounds"]
