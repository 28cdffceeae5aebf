"""Single-chip driver: how long the algorithm took for the wave of the
median pod: the pod-weighted median duration of the `wave.algorithm`
span over the waves that began in the window."""

from benchmark.layers import _waves

snapshot = _waves.snapshot


def read(run):
    return _waves.weighted_median_ms(
        run, "wave_algorithm_p50_ms",
        lambda w: w["algorithm"][1] if "algorithm" in w else None)
