"""Device idle time the engine's host work causes, per decode step: the
idle instants of the traced window whose innermost program span
(`repro.spans`) is an `actor.*` or `engine.*` span (the decode step's
dispatch, its wait on `finished`, the read-back and scoring of finished
rollouts, admission, weight installs, the rest of the actor's tick),
divided by the window's `engine.decode` spans."""
import program_spans as PS


def read(ctx):
    return PS.gap_ms(ctx, ("actor.", "engine."), "engine.decode")
