"""Device idle time the trainer stage's host work causes, per optimizer
step: the idle instants of the traced window whose innermost program
span (`repro.spans`) is a `trainer.*` span (packing, staging and dispatch,
host reads of device values, publication), divided by the window's
`trainer.step` spans."""
import program_spans as PS


def read(ctx):
    return PS.gap_ms(ctx, ("trainer.",), "trainer.step")
