"""schedule_ms: host sampling per round -- the self time of the
program's ``engine.schedule`` span (the cohort and mini-batch schedule
draw) in the traced window, over its rounds."""
import spans


def read(ctx):
    return spans.per_round_ms(ctx, {"engine.schedule"})
