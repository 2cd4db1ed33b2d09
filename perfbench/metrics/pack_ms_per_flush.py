"""Fleet: host seconds spent preparing inputs (``timings["pack_s"]``:
request intake, canvas embed, settings bank, ship enqueue) per dispatch
in the window.  Every flush of these configurations is one dispatch."""


def read(ctx):
    d = ctx.fleet_delta
    return d["pack_s"] / d["dispatches"] * 1e3 if d["dispatches"] else None
