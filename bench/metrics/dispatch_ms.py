"""dispatch_ms: the round step's host side per round -- the self time of
the program's ``engine.chunk`` spans (eager slices and the chunk
dispatch) and ``engine.probe`` spans (the eval probe dispatch) in the
traced window, over its rounds."""
import spans


def read(ctx):
    return spans.per_round_ms(ctx, {"engine.chunk", "engine.probe"})
