"""Seconds the trainer's side spends executing one publication into one
engine on a mesh: the window's delta of the pipeline's
`broadcast_stats()["exec_seconds"]` (the program's counter: each chunk
resharded onto the engine's devices and waited for with
`block_until_ready`, at publish time) over the window's delta of
`executed` (publications executed, one per engine). Without a mesh no
publication is executed, and the reader reads nothing."""


def read(ctx):
    w = ctx.window
    if w.get("executed", 0) <= 0:
        return None
    return w["exec_seconds"] / w["executed"]
