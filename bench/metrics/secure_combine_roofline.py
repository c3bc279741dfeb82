"""secure_combine_roofline: the least time the secure combine needs on
one chip -- read its S_loc uploads of n float32 words and write n int32
words, at the HBM peak -- over the kernel's measured time per round.
Mask generation is not counted: it is work of this implementation, not
of the layer, so a kernel that does less of it can only read higher."""
import devtrace

# the Pallas call of kernels/secure_agg.masked_sum_2d
PATTERN = r"^%masked_sum_2d(\.\d+)? = "


def read(ctx):
    ev = devtrace.matching(
        ctx["trace"]["devices"].get(ctx["device_ids"][0], []), PATTERN)
    if not ev:
        return None
    s_loc = -(-ctx["traffic"]["cohort"] // ctx["chips"])
    need = (s_loc + 1) * 4.0 * ctx["n_params"] / ctx["peaks"]["hbm_bytes_per_s"]
    took = devtrace.total_ns(ev) * 1e-9 / ctx["trace_rounds"]
    return 100.0 * need / took
