"""Device idle time outside every program span (`repro.spans`), per
decode step: the event loop between calls into the stages, and whatever
else runs on the host between them, divided by the window's
`engine.decode` spans. With `decode_host_gap_ms` and `train_host_gap_ms`
(each times its count of spans) it makes up the device's idle time."""
import program_spans as PS


def read(ctx):
    return PS.gap_ms(ctx, (None,), "engine.decode")
