"""Single-chip driver: XLA programs compiled inside the window, built
minus those the persistent cache served. Should be 0."""


def snapshot(ctx):
    built, hits = ctx["compiles"]
    return {"compiled": built - hits}


def read(run):
    before, after = run["snapshots"]["window_compiles"]
    return after["compiled"] - before["compiled"]
