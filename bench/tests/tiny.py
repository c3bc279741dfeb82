"""Cells of ``BENCHMARK.json`` cut to a size a CPU test run can hold:
the same harness, configuration modules and limits, smaller numbers."""
import run

SMALL_CONFIG = {
    "mlp-paper": {"hidden_size": 16},
}
SMALL_TRAFFIC = {
    "full10-secure": {"samples_per_client": 60, "test_samples": 100,
                      "batch_size": 10, "eval_samples": 200,
                      "rounds_per_call": 20},
    "cohort512-secure": {"clients": 300, "cohort": 32,
                         "aggregation": {"kind": "secure", "scale_bits": 20,
                                         "num_sampled": 32},
                         "test_samples": 100, "eval_samples": 500},
}


def ctx(name: str, **traffic) -> dict:
    """Cell ``name`` at its small size, with ``traffic`` keys replaced."""
    c = run.load_cell(name)
    c["config"] = dict(c["config"], **SMALL_CONFIG[c["cell"]["config"]])
    c["traffic"] = {**c["traffic"], **SMALL_TRAFFIC[c["cell"]["traffic"]],
                    **traffic}
    c["replay"].replayable(c["traffic"])
    return c
