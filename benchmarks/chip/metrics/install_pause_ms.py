"""Host time of the engine's weight installs per publication swapped in
(program spans, `repro.spans`): the summed length of the outermost
`engine.install` and `engine.swap` spans that start in the window (a
stream's last chunk nests its `engine.swap` in its `engine.install`, and
counts once), divided by the publications swapped in (`engine.install`
spans with `swapped` 1, plus `engine.swap` spans outside any install).
On one chip an install is a pointer swap: this is what it costs decode."""
import program_spans as PS

INSTALL = ("engine.install", "engine.swap")


def read(ctx):
    recs = PS.window_spans(ctx)
    if recs is None:
        return None
    outer = [r for r in recs if r.name in INSTALL and r.parent not in INSTALL]
    n = sum(r.counts.get("swapped", 0) for r in outer
            if r.name == "engine.install")
    n += sum(1 for r in outer if r.name == "engine.swap")
    if n == 0:
        return None
    return 1e-6 * sum(r.end_ns - r.start_ns for r in outer) / n
