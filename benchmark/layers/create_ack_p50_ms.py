"""Door / store / fan-out: the round trip of one bulk create as the
generator's creators saw it, median over the window's requests."""


def read(run):
    return run["loadgen"].get("create_ack_p50_ms")
