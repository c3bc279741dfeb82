"""server_step_roofline: the least time the fused SSCA server step needs
on one chip -- read ω, lin and the aggregate ĝ and write ω' and lin',
n float32 words each (20·n bytes), plus β's read and write (8·n more)
where λ > 0 -- at the HBM peak, over the kernel's measured time per
round.  Only for a cell whose model lives in HBM: a small model's
operands stay in VMEM between rounds and read above the HBM bound."""
import devtrace

# the Pallas calls of kernels/ssca_update.ssca_update_2d
PATTERN = r"^%ssca_update_2d(\.\d+)? = "


def read(ctx):
    ev = devtrace.matching(
        ctx["trace"]["devices"].get(ctx["device_ids"][0], []), PATTERN)
    if not ev:
        return None
    words = 7 if ctx["traffic"]["algorithm"]["lam"] else 5
    need = words * 4.0 * ctx["n_params"] / ctx["peaks"]["hbm_bytes_per_s"]
    took = devtrace.total_ns(ev) * 1e-9 / ctx["trace_rounds"]
    return 100.0 * need / took
