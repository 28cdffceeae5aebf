"""Device: the share of the chips' busy time that lies under collective
operations in the traced slice: the time of every operation whose name
starts with one of COLLECTIVES, over busy_s x chips. XLA names a
collective after its kind (`all-gather.12`) unless the program named
it: jax names the all-reduce a `psum` / `pmax` / `pmin` lowers to after
that primitive (`psum.161`), so those prefixes count too.

`trace["device_ops"]` keeps the ten names with the most time (summed
over the chips), so a collective that is not among them is not counted:
the number is a lower bound. A trace of one chip, or of none, gives
nothing to read."""

COLLECTIVES = ("all-reduce", "all-gather", "collective-permute",
               "reduce-scatter", "psum", "pmax", "pmin")


def read(run):
    trace = run.get("trace")
    if not trace or trace.get("chips", 0) < 2 or not trace["busy_s"]:
        return None
    under = sum(seconds for name, seconds in trace["device_ops"]
                if name.startswith(COLLECTIVES))
    return 100.0 * under / (trace["busy_s"] * trace["chips"])
