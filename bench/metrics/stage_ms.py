"""stage_ms: staging and collection per round -- the self time of the
program's ``engine.stage`` span (host arrays to a runnable loop:
transfers, keys, state, the eval subset, the ledger) and
``engine.collect`` span (the batched result transfer) in the traced
window, over its rounds."""
import spans


def read(ctx):
    return spans.per_round_ms(ctx, {"engine.stage", "engine.collect"})
