"""Door / store / fan-out, seen from the daemon: from the moment a wave
was decided (its `wave.assume` span begins) to the moment the apiserver
had acknowledged its binds (its `wave.bind` span ends), pod-weighted
median over the waves that began in the window."""

from benchmark.layers import _waves

snapshot = _waves.snapshot


def _commit(wave):
    if "assume" not in wave or "bind" not in wave:
        return None
    return wave["bind"][0] + wave["bind"][1] - wave["assume"][0]


def read(run):
    return _waves.weighted_median_ms(run, "bind_commit_p50_ms", _commit)
